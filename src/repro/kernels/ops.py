"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` resolves through :func:`interpret_mode`: the kernel
bodies run in Pallas interpret mode on the CPU backend only (tests), and
lower to Mosaic on every other platform -- a TPU that failed to come up
never silently falls back to interpreting.

Block shapes: callers pass FEATHER+ tile extents (the Program IR's
tiling); :func:`tpu_block` is the one place that turns them into blocks
Mosaic accepts -- second-to-last dimension a multiple of 8, last a
multiple of 128, or the full array dimension -- and the wrappers zero-pad
operands to the legal block grid (the paper's implicit zero-padding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_chain as _fc
from repro.kernels import mamba_scan as _ms
from repro.kernels import nest_gemm as _ng


#: Mosaic's (8, 128) tiling of 32-bit arrays: sublane (second-to-last)
#: and lane (last) block alignment.
SUBLANE, LANE = 8, 128


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether Pallas kernels run interpreted: an explicit flag wins,
    otherwise only on the CPU backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tpu_block(tile: int, dim: int, align: int) -> int:
    """Kernel block along one axis of extent ``dim``: the FEATHER+ tile
    extent rounded up to ``align``, or the whole axis when that covers
    it (a full-extent block is legal at any size)."""
    b = _round_up(max(1, min(tile, dim)), align)
    return dim if b >= dim else b


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def nest_gemm(x, w, *, bm=128, bn=128, bk=128, interpret=None,
              out_dtype=None, out_block_t=False, act=None):
    """Ragged-shape-safe NEST GEMM (zero-pads to block multiples, the
    paper's implicit zero-padding semantics).  ``act`` fuses an
    elementwise activation from :data:`nest_gemm.ACT_FNS` into the store.

    (bm, bk, bn) are tile extents, legalised by :func:`tpu_block`: K and
    N are lane axes of an operand; M is a sublane axis, or a lane axis
    of the block-transposed output."""
    interpret = interpret_mode(interpret)
    m, k = x.shape
    n = w.shape[1]
    bm_ = tpu_block(bm, m, LANE if out_block_t else SUBLANE)
    bk_ = tpu_block(bk, k, LANE)
    bn_ = tpu_block(bn, n, LANE)
    x, _ = _pad_to(x, 0, bm_)
    x, _ = _pad_to(x, 1, bk_)
    w, _ = _pad_to(w, 0, bk_)
    w, _ = _pad_to(w, 1, bn_)
    o = _ng.nest_gemm(x, w, bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
                      out_dtype=out_dtype, out_block_t=out_block_t, act=act)
    if out_block_t:
        return o[:n, :m]
    return o[:m, :n]


def _rnd(x):
    """Largest power of two <= x (min 8) for block sizing on small shapes
    (flash attention and the scan, whose blocks are sublane axes)."""
    p = 8
    while p * 2 <= x:
        p *= 2
    return p


def fused_chain(x, ws, *, bm=128, bks=None, acts=None, adapts=None,
                dims=None, interpret=None, out_dtype=None):
    """Ragged-shape-safe fused chained GEMM: ONE kernel launch for
    ``act_{L-1}(... act_0(x @ ws[0]) ...) @ ws[-1]`` with every layer's
    weight streamed HBM->VMEM in double-buffered K tiles and every
    interior activation resident in VMEM (the kernel zero-pads M and K
    to the tile grid, the paper's implicit zero-padding semantics).

    ``bks`` sets each layer's weight-streaming granularity; ``acts``
    names per-layer activations from :data:`fused_chain.FUSED_ACT_FNS`
    (None entries skip); ``adapts``/``dims`` carry the runtime's shape-
    glue boundaries and true per-layer (m, k, n) so a whole transformer
    block (attention + MLP, spanning head-split reshapes) runs as one
    launch.
    """
    interpret = interpret_mode(interpret)
    n_layers = len(ws)
    if bks is None:
        bks = (128,) * n_layers
    if acts is None:
        acts = (None,) * n_layers
    if dims is None:
        dims = tuple((x.shape[0], w.shape[0], w.shape[1]) for w in ws)
    # every layer's K tile is a lane-axis slab read: a multiple of 128
    # (the kernel zero-pads each weight's K to its tile grid)
    bks_ = tuple(_round_up(max(1, min(bk, d[1])), LANE)
                 for bk, d in zip(bks, dims))
    if adapts is not None and any(adapts):
        # the in-kernel adapt needs every row of every layer resident
        bm_ = _round_up(max(d[0] for d in dims), SUBLANE)
    else:
        bm_ = _round_up(max(1, min(bm, dims[0][0])), SUBLANE)
    return _fc.fused_chain(
        x, *ws, bm=bm_, bks=bks_, acts=tuple(acts),
        adapts=None if adapts is None else tuple(adapts),
        dims=None if dims is None else tuple(tuple(d) for d in dims),
        interpret=interpret, out_dtype=out_dtype)


def flash_attention(q, k, v, *, causal=True, bq=128, bkv=128,
                    interpret=None):
    """q, k, v: [B, S, H, D] -> [B, S, H, D]."""
    interpret = interpret_mode(interpret)
    b, s, h, d = q.shape
    sk = k.shape[1]
    bq_, bkv_ = min(bq, _rnd(s)), min(bkv, _rnd(sk))
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    qf, pq = _pad_to(qf, 1, bq_)
    kf, _ = _pad_to(kf, 1, bkv_)
    vf, _ = _pad_to(vf, 1, bkv_)
    # padded KV columns must not contribute: they are causally masked for
    # causal=True; for full attention, mask via large-negative k rows
    o = _fa.flash_attention(qf, kf, vf, causal=causal, bq=bq_, bkv=bkv_,
                            interpret=interpret, kv_len=sk)
    o = o[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return o


def flash_decode(q, k, v, lengths=None, *, bkv=128, interpret=None,
                 scale=1.0):
    """Ragged-shape-safe batched decode attention: one launch advances
    every request in the batch, each masked to its own KV length.

    q: [B, sq, d], k, v: [B, skv, d], lengths: [B] or [B, 1] int true
    lengths (defaults to the full skv for every request).
    """
    interpret = interpret_mode(interpret)
    b, sq, d = q.shape
    sk = k.shape[1]
    if lengths is None:
        lengths = jnp.full((b, 1), sk, dtype=jnp.int32)
    else:
        lengths = jnp.asarray(lengths, dtype=jnp.int32).reshape(b, 1)
    bkv_ = min(bkv, _rnd(sk))
    q, _ = _pad_to(q, 1, 8)
    k, _ = _pad_to(k, 1, bkv_)
    v, _ = _pad_to(v, 1, bkv_)
    o = _fa.flash_decode(q, k, v, lengths, bkv=bkv_, interpret=interpret,
                         scale=scale)
    return o[:, :sq]


def flash_decode_proj(q, k, v, wo, lengths=None, *, m_out, k_out,
                      bkv=128, interpret=None, scale=1.0):
    """Block-fused batched decode attention: one launch computes
    softmax(q k^T) v AND the adapt-cycled output projection ``wo`` for
    every request in the batch.

    q: [B, sq, d], k, v: [B, skv, d], wo: [k_out, n_out] shared across
    requests, lengths: [B] or [B, 1] int true KV lengths.  Each
    request's [sq, d] context is raveled row-major, cycled to
    m_out * k_out elements and refolded to [m_out, k_out] in VMEM (the
    runtime ``adapt`` head-merge) before the projection.  Returns
    [B, m_out, n_out].
    """
    interpret = interpret_mode(interpret)
    b, sq, d = q.shape
    sk = k.shape[1]
    if lengths is None:
        lengths = jnp.full((b, 1), sk, dtype=jnp.int32)
    else:
        lengths = jnp.asarray(lengths, dtype=jnp.int32).reshape(b, 1)
    bkv_ = min(bkv, _rnd(sk))
    q, _ = _pad_to(q, 1, 8)
    k, _ = _pad_to(k, 1, bkv_)
    v, _ = _pad_to(v, 1, bkv_)
    return _fa.flash_decode_proj(q, k, v, lengths, jnp.asarray(wo),
                                 true_sq=sq, m_out=m_out, k_out=k_out,
                                 bkv=bkv_, interpret=interpret,
                                 scale=scale)


def mamba_scan(da, dbx, c, h0, *, d_blk=256, chunk=64, interpret=None):
    interpret = interpret_mode(interpret)
    b, l, d, n = da.shape
    d_blk = min(d_blk, _rnd(d))
    chunk = min(chunk, _rnd(l))
    assert d % d_blk == 0 and l % chunk == 0, (
        "mamba_scan requires power-of-two-friendly shapes; "
        f"got d={d}, l={l}")
    return _ms.mamba_scan(da, dbx, c, h0, d_blk=d_blk, chunk=chunk,
                          interpret=interpret)
