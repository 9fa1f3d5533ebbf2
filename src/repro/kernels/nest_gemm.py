"""NEST GEMM: the paper's compute atom as a Pallas TPU kernel.

TPU adaptation of FEATHER+'s NEST (DESIGN.md §2): the AH-element PE dot
product becomes the K-block of an MXU-tiled matmul; a NEST column's
"VN group" (vn stationary VNs x T streamed VNs) becomes one (bm x bk) x
(bk x bn) VMEM tile-pair; BIRRD's reorder-in-reduction becomes the output
BlockSpec index map, which lets the caller pick the *output layout*
(row-major or block-transposed) at reduction time for free -- the paper's
(dataflow, layout) co-switching insight expressed in Mosaic terms.

Grid: (M/bm, N/bn, K/bk); K is innermost (sequential on TPU) and the output
block is revisited across it, accumulating in a VMEM fp32 scratch.

``repro.backends.pallas_backend`` compiles lowered Programs onto this
kernel: the Program's snapped tiling becomes (bm, bk, bn), an IO-S
(transposed-accumulator) SetOVNLayout becomes ``out_block_t``, and an
elementwise Activation drain becomes ``act`` (fused at the final K step,
exactly where the interpreter applies it to the drained tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped-VMEM ceiling a kernel may ask Mosaic for (v5e has 128 MiB of
#: VMEM per core; the default scoped limit is far lower).
VMEM_LIMIT_CAP = 100 << 20


def mxu_dot(a, b):
    """fp32-accumulating matmul at full fp32 contract precision: Mosaic's
    default precision for fp32 operands may round them to bf16 on the
    MXU, which the stream oracle's fp32 tolerances do not admit."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    """Scoped-VMEM request for a kernel whose blocks need ``vmem_bytes``
    (with headroom for Mosaic's internal scratch)."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(VMEM_LIMIT_CAP,
                                 max(32 << 20, 2 * vmem_bytes))))


#: Elementwise activations fusable at the output-store step (the MINISA
#: Activation instruction's elementwise subset; row-wise functions such as
#: softmax/norms need full rows and are applied by the caller instead).
ACT_FNS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _nest_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                      out_block_t: bool, act: str | None):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], w_ref[...])

    @pl.when(k_idx == n_k - 1)
    def _store():
        acc = acc_ref[...]
        if act is not None:
            acc = ACT_FNS[act](acc)
        if out_block_t:
            acc = acc.T
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype", "out_block_t",
                                             "act"))
def nest_gemm(x: jax.Array, w: jax.Array, *, bm: int = 128, bn: int = 128,
              bk: int = 128, interpret: bool = False, out_dtype=None,
              out_block_t: bool = False,
              act: str | None = None) -> jax.Array:
    """O = X[M, K] @ W[K, N]; shapes must divide by the blocks, which
    must be TPU-legal (``ops.nest_gemm`` legalises and pads).

    out_block_t=True stores output *tiles* to transposed tile coordinates
    (O_t[j, i] blocks) -- the BIRRD-style free output re-layout: the next
    consumer can read a column-major-of-blocks layout with zero extra
    passes.  O then has shape (N//bn * bn rows of blocks ...) == (N, M) with
    per-block transposition applied.

    ``act`` fuses an elementwise activation (a key of :data:`ACT_FNS`) into
    the final-K store, before the optional block transpose.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"{(m, k, n)} not divisible by blocks {(bm, bk, bn)}"
    assert act is None or act in ACT_FNS, act
    n_k = k // bk
    out_dtype = out_dtype or x.dtype
    elem = jnp.dtype(x.dtype).itemsize
    vmem = (2 * elem * (bm * bk + bk * bn)
            + 2 * jnp.dtype(out_dtype).itemsize * bm * bn + 4 * bm * bn)

    if out_block_t:
        out_spec = pl.BlockSpec((bn, bm), lambda i, j, kk: (j, i))
        out_shape = jax.ShapeDtypeStruct((n, m), out_dtype)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)

    return pl.pallas_call(
        functools.partial(_nest_gemm_kernel, n_k=n_k,
                          out_block_t=out_block_t, act=act),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(x, w)
