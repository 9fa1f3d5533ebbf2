"""Scheduler: mean ``decode_tick`` span."""
from bench.metrics import mean_ms


def read(ctx):
    return mean_ms(ctx.spans_named("decode_tick"))
