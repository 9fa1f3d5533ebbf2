"""Build a cell's system under test through the entry points users call:
``ModelExecutable.for_cell(..., reduce_model=False)`` for the prefill and
decode streams and ``Scheduler(backend="pallas")`` over them, with the
scheduler's defaults (fused segments, batched decode)."""

from __future__ import annotations

from bench import adapter, reference


def build(config: dict, mix: dict, seed: int):
    from repro.configs.base import ShapeConfig
    from repro.configs.feather import feather_config
    from repro.runtime import ModelExecutable, ProgramCache, Scheduler

    cfg = feather_config(4, 16)
    cache = ProgramCache()
    chunk, kv = config["prefill_chunk"], mix["kv_extent"]

    def executable(kind, seq_len):
        return ModelExecutable.for_cell(
            config["arch"], ShapeConfig(f"{kind}_{seq_len}", seq_len=seq_len,
                                        global_batch=1, kind=kind),
            cfg, cache=cache, reduce_model=False)

    prefill = executable("prefill", chunk)
    decode = executable("decode", kv)
    for ex, kind, seq in ((prefill, "prefill", chunk), (decode, "decode", kv)):
        check_stream(ex, reference.stream(config, kind, seq))
    return Scheduler(prefill, decode, backend="pallas",
                     max_concurrent=mix["max_concurrent"],
                     weight_seed=seed, seed=seed)


def check_stream(ex, steps) -> None:
    """The program must run the stream the configuration file states."""
    ran = [(s.op.gemm.name, s.op.gemm.m, s.op.gemm.k, s.op.gemm.n)
           for s in ex.steps]
    want = [(s.name, s.m, s.k, s.n) for s in steps]
    if ran != want:
        raise SystemExit(f"{ex.name} runs {ran}, the configuration "
                         f"states {want}")


def warm_up(sched, mix: dict) -> list[int]:
    """Compile every decode shape the cell's traffic reaches, and no
    other: the M bucket of a tick of all sessions, and of a tick of one
    fewer (a finished session's replacement is admitted a tick after it
    leaves).  Admitting the sessions in set-up runs the prefill pass.
    Returns the batch sizes warmed."""
    n = mix["sessions"]
    warmed = {}
    for size in (n, n - 1):
        if size:
            warmed.setdefault(adapter.m_bucket(size), size)
    for size in warmed.values():
        adapter.warm_decode(sched, size)
    return sorted(warmed.values())
