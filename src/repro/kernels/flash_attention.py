"""Flash attention (causal/full) as a Pallas TPU kernel.

Grid: (batch*heads, Q blocks, KV blocks); KV is the innermost sequential
dimension.  Running (max, sum, acc) live in VMEM scratch and the output
block is finalised on the last KV step -- the classic online-softmax
recurrence, with causal block skipping via pl.when.

``flash_decode`` is the serving twin: one launch advances a whole batch
of decode requests, each row attending over its *own* gathered K/V pages
masked to its own true length (grid (requests, KV blocks); per-request
length rides along as a [B, 1] int32 operand, whole in SMEM).  Unlike the prefill
kernel it applies no ``d**-0.5`` scaling by default -- the MINISA GEMM
stream's score GEMM carries none, and the batched path must stay on the
sequential path's numeric trajectory.

``flash_decode_proj`` is the block-fused variant: at the last KV step
the finalised context is adapt-cycled (the runtime's head-merge
permutation, ``fused_chain.adapt_block`` in VMEM)
and multiplied by the resident output projection, so attention + Wo for
the whole decode batch is ONE launch instead of two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_chain import adapt_block
from repro.kernels.nest_gemm import compiler_params, mxu_dot

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bkv: int, causal: bool, scale: float,
                  kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                       # [bq, d]
        k = k_ref[0]                       # [bkv, d]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        if kv_len < n_kv * bkv:
            # zero-padded KV tail (ops.py raggedness) must not contribute --
            # guard on the padded extent, not kv_len % bkv: a block-aligned
            # kv_len shorter than the padded buffer must still be masked
            s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # exp(s - m_new) == 1, not 0, when an entire row is masked so far
        # (s == m_new == NEG_INF); zero those explicitly or padding leaks
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked blocks: kv block strictly after the q block
        pl.when(ki * bkv <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret", "kv_len"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bkv: int = 128,
                    interpret: bool = False,
                    kv_len: int | None = None) -> jax.Array:
    """q, k, v: [BH, S, d] (heads folded into batch); returns [BH, S, d]."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % bq == 0 and sk % bkv == 0, (sq, sk, bq, bkv)
    n_kv = sk // bkv
    scale = d ** -0.5
    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, bq=bq, bkv=bkv, causal=causal, scale=scale,
        kv_len=kv_len if kv_len is not None else sk)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, n_kv: int, sq: int, bkv: int, scale: float):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                           # [sq, d]
    k = k_ref[0]                           # [bkv, d]
    v = v_ref[0]
    s = mxu_dot(q, k.T) * scale
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 1)
    # this request's true KV length -- everything past it (other requests'
    # retired pages, zero padding) is masked out of the softmax
    s = jnp.where(kpos < len_ref[pl.program_id(0), 0], s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + mxu_dot(p.astype(v.dtype), v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_proj_kernel(q_ref, k_ref, v_ref, len_ref, wo_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, n_kv: int, sq: int,
                        true_sq: int, d: int, bkv: int, scale: float,
                        m_out: int, k_out: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                           # [sq, d]
    k = k_ref[0]                           # [bkv, d]
    v = v_ref[0]
    s = mxu_dot(q, k.T) * scale
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (sq, bkv), 1)
    s = jnp.where(kpos < len_ref[pl.program_id(0), 0], s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + mxu_dot(p.astype(v.dtype), v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _project():
        ctx = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # [sq, d]
        # runtime adapt on the TRUE context rows (ravel row-major, cycle
        # to m_out*k_out elements, refold): the head-merge permutation
        # the per-layer path does on the host between pv and wo
        h = adapt_block(ctx, true_sq, d, m_out, k_out, m_out, k_out)
        o_ref[0] = mxu_dot(h, wo_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "true_sq", "m_out", "k_out", "bkv", "interpret", "scale"))
def flash_decode_proj(q: jax.Array, k: jax.Array, v: jax.Array,
                      lengths: jax.Array, wo: jax.Array, *, true_sq: int,
                      m_out: int, k_out: int, bkv: int = 128,
                      interpret: bool = False,
                      scale: float = 1.0) -> jax.Array:
    """Block-fused batched decode attention: softmax(q k^T) v followed by
    the adapt-cycled output projection, one launch for the whole batch.

    q: [B, sq, d] (rows past ``true_sq`` are carrier padding and are
    dropped before the adapt), k, v: [B, skv, d], lengths: [B, 1] int32,
    wo: [k_out, n_out] shared across requests (its BlockSpec is pinned,
    so it streams HBM->VMEM once).  Returns [B, m_out, n_out].
    """
    b, sq, d = q.shape
    sk = k.shape[1]
    n_out = wo.shape[1]
    assert sk % bkv == 0, (sk, bkv)
    assert wo.shape[0] == k_out, (wo.shape, k_out)
    n_kv = sk // bkv
    kernel = functools.partial(
        _decode_proj_kernel, n_kv=n_kv, sq=sq, true_sq=true_sq, d=d,
        bkv=bkv, scale=scale, m_out=m_out, k_out=k_out)
    return pl.pallas_call(
        kernel,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # [B, 1] lengths
            pl.BlockSpec((k_out, n_out), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m_out, n_out), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m_out, n_out), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, d), jnp.float32),
        ],
        compiler_params=compiler_params(
            4 * (2 * (sq * d + 2 * bkv * d + k_out * n_out + m_out * n_out)
                 + sq * (d + 2))),
        interpret=interpret,
    )(q, k, v, lengths, wo)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret", "scale"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, bkv: int = 128,
                 interpret: bool = False, scale: float = 1.0) -> jax.Array:
    """Batched ragged decode attention: one launch for the whole batch.

    q: [B, sq, d] (one decode carrier per request), k, v: [B, skv, d]
    (per-request gathered KV pages), lengths: [B, 1] int32 true KV
    lengths.  Softmax for request b runs over k[b, :lengths[b]] only.
    No default ``d**-0.5``: score scaling is the GEMM stream's business.
    """
    b, sq, d = q.shape
    sk = k.shape[1]
    assert sk % bkv == 0, (sk, bkv)
    n_kv = sk // bkv
    kernel = functools.partial(_decode_kernel, n_kv=n_kv, sq=sq, bkv=bkv,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # [B, 1] lengths
        ],
        out_specs=pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, lengths)
