"""Fused chained-GEMM megakernel with double-buffered weight streaming:
one ``pl.pallas_call`` for a whole MINISA chained segment (paper §IV-G
at kernel granularity), VMEM bounded by the largest layer.

The PR-5 kernel kept every layer's FULL weight VMEM-resident per grid
step, so the VMEM budget capped segment length at the *sum* of the
weights and ``adapt`` (head-split) boundaries broke fusion.  This kernel
streams instead: the grid is ``(M/bm, sum_l K_l/bk_l)`` — the second
axis walks every layer's host-K tiles back to back, and Pallas's grid
pipeline double-buffers each weight's ``(bk_l, n_l)`` window because its
BlockSpec index advances between consecutive steps (and pins once the
layer is done, eliding refetch).  Per grid step::

  layer l, K-tile j:   acc[:, :n_l] += h[:, j*bk : (j+1)*bk] @ W_l_tile
  at j == kt_l - 1:    acc = act_l(acc)          (Activation drain)
                       slab <- acc | adapt(acc)  (interior commit — the
                                                  chained activation and
                                                  the head-split/merge
                                                  permutation both live
                                                  in VMEM scratch)

Only the segment input (one HBM read), the weight K-tiles (each shipped
once per M block) and the last layer's output (one HBM write) cross the
chip boundary; ``core/program.FusedSegment`` charges exactly that.

``adapt`` boundaries (the runtime's flatten/cycle/reshape shape glue
between chained layers) lower to :func:`adapt_block`: the ravel/cycle/
refold of the true ``(m_l, n_l)`` region, expressed as a few one-hot
matmuls (one per static column shift) so that Mosaic sees no reshape and
no unaligned lane slice.  The one-hot products are exact at fp32
contract precision, so the result equals ``runtime.executable.adapt``.
This requires the whole activation resident (one M block), which
``fuse_segment`` enforces.

TPU tiling: ``bm`` is a multiple of 8 and every K tile a multiple of 128
(``ops.fused_chain`` legalises the FEATHER+ tile extents); each layer's
output width is zero-padded to whole lanes.  Row-wise activations
(softmax / rmsnorm / layernorm) stay legal: the accumulator block spans
the layer's FULL output width, so it holds complete host rows; they are
told the true width so lane padding never enters a row statistic.  Their
numerics mirror ``runtime.executable.ACTIVATIONS`` (same eps, same
max-subtraction).

On CPU the kernel runs in Pallas interpret mode; on TPU the identical
call site lowers to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nest_gemm import ACT_FNS, compiler_params, mxu_dot
from repro.obs.trace import trace


_NEG = -1e30


def _valid_cols(x, n):
    """Mask of the true columns when ``x`` carries lane padding past
    ``n`` (None when it does not)."""
    if n is None or n >= x.shape[-1]:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) < n


def _softmax(x, n=None):
    valid = _valid_cols(x, n)
    if valid is not None:
        x = jnp.where(valid, x, _NEG)      # padded columns get weight 0
    z = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(z)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _rmsnorm(x, n=None):
    # padded columns are exact zeros (zero weight columns): they add
    # nothing to the sum, only the divisor must be the true width
    n = n or x.shape[-1]
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) / n + 1e-6)


def _layernorm(x, n=None):
    valid = _valid_cols(x, n)
    n = n or x.shape[-1]
    mu = jnp.sum(x, axis=-1, keepdims=True) / n
    d = x - mu
    if valid is not None:
        d = jnp.where(valid, d, 0.0)
    var = jnp.sum(d * d, axis=-1, keepdims=True) / n
    return d / jnp.sqrt(var + 1e-6)


#: Row-wise activations: they take the true row width, since the kernel
#: pads rows to whole lanes.
_ROW_WISE_FNS = {"softmax": _softmax, "rmsnorm": _rmsnorm,
                 "layernorm": _layernorm}

#: Activations applicable inside the fused kernel: the elementwise set
#: shared with the per-layer kernel, plus the row-wise ones (legal here
#: because a fused block holds full output rows).
FUSED_ACT_FNS = {**ACT_FNS, **_ROW_WISE_FNS}


def _apply_act(name, x, n):
    if name in _ROW_WISE_FNS:
        return _ROW_WISE_FNS[name](x, n)
    return ACT_FNS[name](x)            # elementwise: f(0) == 0 on pads


#: Exact-integer range of the fp32 index arithmetic in :func:`adapt_block`.
_EXACT_INT = 1 << 23


def _adapt_shifts(m_l, n_l, m_next, k_next) -> tuple[int, ...]:
    """Static column shifts of the runtime ``adapt`` glue.

    ``adapt`` ravels the true ``(m_l, n_l)`` region row-major, cycles it
    to ``m_next * k_next`` elements and refolds.  Output row ``i`` reads
    source row ``(i*k_next // n_l + w) % m_l`` at column ``j - s`` for
    ``s = w*n_l - (i*k_next) % n_l``: one term per distinct shift ``s``.
    """
    shifts = set()
    for i in range(m_next):
        delta = (i * k_next) % n_l
        for w in range((delta + k_next - 1) // n_l + 1):
            shifts.add(w * n_l - delta)
    return tuple(sorted(shifts))


def _floordiv(a, d: int):
    """Exact ``a // d`` for int32 ``a`` in [-2**23, 2**23] and static
    ``d > 0``, via fp32 (vector integer division does not lower on the
    TPU) plus a one-step integer fix-up."""
    q = jnp.floor(a.astype(jnp.float32) / float(d)).astype(jnp.int32)
    r = a - q * d
    return jnp.where(r < 0, q - 1, jnp.where(r >= d, q + 1, q))


def adapt_block(src, m_l, n_l, m_next, k_next, rows, width):
    """The runtime ``adapt`` (``runtime.executable.adapt``) on a VMEM
    value, with no reshape and no unaligned lane slice.

    ``src`` holds the true ``(m_l, n_l)`` region at its top-left.  The
    result is ``(rows, width)`` with ``adapt(src[:m_l, :n_l], m_next,
    k_next)`` at its top-left and zeros elsewhere.  Each shift term
    (:func:`_adapt_shifts`) is two one-hot matmuls -- a column shift,
    then a row gather -- exact at fp32 contract precision.
    """
    if (m_next + 1) * k_next + n_l >= _EXACT_INT:
        raise ValueError(
            f"adapt to ({m_next}, {k_next}) exceeds the exact in-kernel "
            f"index range")
    src_rows, src_cols = src.shape
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, src_rows), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, src_rows), 1)
    c = jax.lax.broadcasted_iota(jnp.int32, (src_cols, width), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (src_cols, width), 1)
    base = _floordiv(i * k_next, n_l)
    out = jnp.zeros((rows, width), jnp.float32)
    for s in _adapt_shifts(m_l, n_l, m_next, k_next):
        col_sel = ((j - c == s) & (c < n_l) & (j < k_next)
                   ).astype(jnp.float32)
        u = i * k_next + s
        q = _floordiv(u, n_l)
        src_row = q - _floordiv(q, m_l) * m_l
        row_sel = ((u - q * n_l == 0) & (q >= base) & (i < m_next)
                   & (r == src_row)).astype(jnp.float32)
        out = out + mxu_dot(row_sel, mxu_dot(src, col_sel))
    return out


def _fused_kernel(x_ref, *refs, dims, n_pads, bks, kts, offs, acts,
                  adapts, bm, k_slab):
    """One (m-block, K-tile) grid step: exactly one layer's tile fires."""
    n_layers = len(dims)
    w_refs = refs[:n_layers]
    o_ref = refs[n_layers]
    slab_ref = refs[n_layers + 1]     # resident interior activation
    acc_ref = refs[n_layers + 2]      # fp32 accumulator, n_max wide
    s = pl.program_id(1)

    for layer in range(n_layers):
        m_l, k_l, n_l = dims[layer]
        n_pad = n_pads[layer]
        off, kt, bk = offs[layer], kts[layer], bks[layer]
        j = s - off                   # this layer's local K-tile index

        @pl.when((s >= off) & (s < off + kt))
        def _layer_step(layer=layer, m_l=m_l, n_l=n_l, n_pad=n_pad,
                        kt=kt, bk=bk, j=j):
            if layer == 0:
                # the input block window IS this K tile (streamed too)
                h = x_ref[...].astype(jnp.float32)
            elif kt == 1:
                h = slab_ref[:, :bk]
            else:
                h = slab_ref[:, pl.ds(pl.multiple_of(j * bk, bk), bk)]
            partial = mxu_dot(h, w_refs[layer][...].astype(jnp.float32))

            @pl.when(j == 0)
            def _init():
                acc_ref[:, :n_pad] = jnp.zeros((bm, n_pad), jnp.float32)

            acc_ref[:, :n_pad] += partial

            @pl.when(j == kt - 1)     # final K tile: drain the layer
            def _drain():
                acc = acc_ref[:, :n_pad]
                if acts[layer] is not None:
                    acc = _apply_act(acts[layer], acc, n_l)
                if layer == n_layers - 1:
                    o_ref[...] = acc.astype(o_ref.dtype)
                    return
                if adapts[layer + 1]:
                    m_next, k_next = dims[layer + 1][:2]
                    nxt = adapt_block(acc, m_l, n_l, m_next, k_next, bm,
                                      min(k_slab, _lane_pad(k_next)))
                else:
                    nxt = acc
                # full overwrite, zero-padded: stale slab columns from
                # the previous (wider) layer can never leak, and the
                # zero K-pad matches the zero-padded weight rows
                width = nxt.shape[1]
                if width < k_slab:
                    slab_ref[:, width:] = jnp.zeros(
                        (bm, k_slab - width), jnp.float32)
                slab_ref[:, :width] = nxt


def _lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def _pad_axis(x, axis, target):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def fused_chain(x: jax.Array, *ws: jax.Array, bm: int,
                bks: tuple[int, ...], acts: tuple[str | None, ...],
                adapts: tuple[bool, ...] | None = None,
                dims: tuple[tuple[int, int, int], ...] | None = None,
                interpret: bool = False, out_dtype=None) -> jax.Array:
    """Traced entry point for the jitted megakernel: when the ``obs``
    tracer is enabled, the launch is timed to ``block_until_ready`` (the
    device-sync wall clock of the ONE ``pallas_call``); disabled, this
    is one attribute check on top of the jit dispatch."""
    if not trace.enabled:
        return _fused_chain_jit(x, *ws, bm=bm, bks=bks, acts=acts,
                                adapts=adapts, dims=dims,
                                interpret=interpret, out_dtype=out_dtype)
    with trace.span("kernel.fused_chain", n_layers=len(ws), bm=bm,
                    bks=tuple(bks), grid_k=sum(
                        -(-d[1] // max(1, min(bk, d[1])))
                        for d, bk in zip(dims, bks)) if dims else None):
        return jax.block_until_ready(
            _fused_chain_jit(x, *ws, bm=bm, bks=bks, acts=acts,
                             adapts=adapts, dims=dims,
                             interpret=interpret, out_dtype=out_dtype))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bks", "acts", "adapts", "dims",
                                    "interpret", "out_dtype"))
def _fused_chain_jit(x: jax.Array, *ws: jax.Array, bm: int,
                     bks: tuple[int, ...], acts: tuple[str | None, ...],
                     adapts: tuple[bool, ...] | None = None,
                     dims: tuple[tuple[int, int, int], ...] | None = None,
                     interpret: bool = False, out_dtype=None) -> jax.Array:
    """O = act_{L-1}(... act_0(X @ W_0) ... @ W_{L-1}) in ONE launch,
    each weight streamed HBM->VMEM in double-buffered (bk_l, n_l) tiles.

    ``dims`` carries each layer's TRUE (m, k, n); operands are zero-
    padded here to the K-tile grid (zero pad rows make stale slab
    columns inert).  ``adapts[l]`` marks the runtime shape-glue boundary
    before layer ``l``, lowered to the in-kernel slab permutation —
    which needs the whole activation in one M block (bm >= every m_l).
    """
    assert ws, "fused_chain needs at least one weight"
    n_layers = len(ws)
    if adapts is None:
        adapts = (False,) * n_layers
    if dims is None:
        m = x.shape[0]
        dims = tuple((m, w.shape[0], w.shape[1]) for w in ws)
    assert len(bks) == len(acts) == len(adapts) == len(dims) == n_layers
    assert not adapts[0], "layer 0 reads the host input, not the slab"
    for l in range(1, n_layers):
        if not adapts[l]:
            assert dims[l][1] == dims[l - 1][2], \
                f"chain shape mismatch at layer {l}: " \
                f"{dims[l - 1][2]} -> {dims[l][1]}"
    assert all(a is None or a in FUSED_ACT_FNS for a in acts), acts
    out_dtype = out_dtype or x.dtype

    assert bm % 8 == 0 and all(bk % 128 == 0 for bk in bks), \
        f"fused blocks bm={bm} bks={bks} are not TPU-legal (ops.py " \
        f"legalises them)"
    kts = tuple(-(-d[1] // bk) for d, bk in zip(dims, bks))
    padded_ks = tuple(kt * bk for kt, bk in zip(kts, bks))
    n_pads = tuple(_lane_pad(d[2]) for d in dims)
    offs = tuple(sum(kts[:l]) for l in range(n_layers))
    total = sum(kts)
    m0, m_out = dims[0][0], dims[-1][0]
    if any(adapts):
        # the slab permutation needs every row of every layer resident
        assert bm >= max(d[0] for d in dims), (bm, dims)
        n_m = 1
    else:
        n_m = -(-m0 // bm)
    k_slab = max(padded_ks[1:], default=128)

    x = _pad_axis(_pad_axis(x, 0, n_m * bm), 1, padded_ks[0])
    ws = tuple(_pad_axis(_pad_axis(w, 0, pk), 1, np_)
               for w, pk, np_ in zip(ws, padded_ks, n_pads))
    elem = jnp.dtype(x.dtype).itemsize
    vmem = (2 * elem * (bm * bks[0] + bm * n_pads[-1]
                        + sum(bk * np_ for bk, np_ in zip(bks, n_pads)))
            + 4 * bm * (k_slab + max(n_pads)))

    in_specs = [pl.BlockSpec(
        (bm, bks[0]),
        lambda i, s, kt0=kts[0]: (i, jnp.minimum(s, kt0 - 1)))]
    in_specs += [
        pl.BlockSpec(
            (bk, w.shape[1]),
            lambda i, s, off=off, kt=kt: (jnp.clip(s - off, 0, kt - 1), 0))
        for w, bk, off, kt in zip(ws, bks, offs, kts)]
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, dims=tuple(dims), n_pads=n_pads, bks=bks,
            kts=kts, offs=offs, acts=tuple(acts), adapts=tuple(adapts),
            bm=bm, k_slab=k_slab),
        grid=(n_m, total),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_pads[-1]), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_m * bm, n_pads[-1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, k_slab), jnp.float32),
                        pltpu.VMEM((bm, max(n_pads)), jnp.float32)],
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(x, *ws)
    return out[:m_out, :dims[-1][2]]
