"""Kernels: ``nest_gemm``'s share of its roofline."""
from bench.metrics import roofline


def read(ctx):
    return roofline(ctx, "nest_gemm")
