"""The work of the window's passes and ticks: each launch's kernel and
its per-layer GEMM shapes, from the executable's steps, segments and
batch plan, costed by ``bench/kernels``."""

from __future__ import annotations

from bench import kernels


def _dims(steps, rows=None):
    return [((rows if rows is not None else s.op.gemm.m), s.op.gemm.k,
             s.op.gemm.n) for s in steps]


def pass_launches(ex) -> list[tuple[str, list]]:
    """(kernel, per-layer (m, k, n)) of each launch of one fused pass."""
    out = []
    for seg in ex.segments:
        steps = [ex.steps[i] for i in seg.indices]
        if seg.fused is not None:
            out.append(("fused_chain", _dims(steps)))
        else:
            out += [("nest_gemm", _dims([s])) for s in steps]
    return out


def tick_launches(decode, n: int) -> list[tuple[str, list]]:
    """(kernel, per-layer (m, k, n)) of each launch of one batched tick
    of ``n`` requests, counting the requests' rows and not the bucket's
    padding."""
    out = []
    for seg in decode.batch_plan(n).segments:
        steps = [decode.steps[i] for i in seg.indices]
        if seg.kind == "perreq":
            out += [("nest_gemm", _dims([s])) for _ in range(n)
                    for s in steps]
        elif seg.kind == "attention":
            out.append(("flash_decode", [(n * s.op.gemm.m, s.op.gemm.k,
                                          s.op.gemm.n) for s in steps]))
        elif seg.fused is not None:
            out.append(("fused_chain", _dims(steps, n * seg.m_rows)))
        else:
            out += [("nest_gemm", _dims([s], n * seg.m_rows))
                    for s in steps]
    return out


def launches(sched, spans) -> dict:
    """kernel -> [(flops, bytes)] of every launch the window's prefill
    passes and decode ticks made."""
    out: dict[str, list] = {}
    one_pass = pass_launches(sched.prefill)
    per_tick: dict[int, list] = {}
    for e in spans:
        if e.instant:
            continue
        if e.name == "prefill_chunk":
            made = one_pass
        elif e.name == "decode_tick":
            n = e.attrs["n_ready"]
            if n not in per_tick:
                per_tick[n] = tick_launches(sched.decode, n)
            made = per_tick[n]
        else:
            continue
        for kernel, dims in made:
            out.setdefault(kernel, []).append(kernels.cost(kernel, dims))
    return out
