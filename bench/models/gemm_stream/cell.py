"""Build a cell's system under test through the entry points users call:
``ModelExecutable.for_cell(..., reduce_model=False)`` for the prefill and
decode streams and ``Scheduler(backend="pallas")`` over them, with the
scheduler's defaults (fused segments, batched decode)."""

from __future__ import annotations

import numpy as np

from bench.models.gemm_stream import reference


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


def validate(config: dict) -> None:
    """Raise ValueError where the configuration file departs from the
    program's own configuration of ``config["arch"]``: a width, the
    family, the depth, or the stream a pass runs (built on the host,
    nothing runs)."""
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import get_config
    from repro.core.model_gemms import gemm_workloads

    mc = get_config(config["arch"])
    want = {"hidden_size": mc.d_model, "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "vocab_size": mc.vocab_size,
            "family": mc.family}
    if mc.family == "moe":
        want.update(num_local_experts=mc.num_experts,
                    num_experts_per_tok=mc.experts_per_token,
                    intermediate_size=mc.moe_d_ff)
    else:
        want["intermediate_size"] = mc.d_ff
    got = dict(config)
    got.setdefault("head_dim", mc.head_dim)
    bad = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    if bad:
        raise ValueError(f"{config['name']}: (file, program) differ: {bad}")
    # depth: the runtime executes each distinct layer once
    if (mc.num_layers, config["num_hidden_layers"]) != (32, 1):
        raise ValueError(f"{config['name']}: the program has "
                         f"{mc.num_layers} layers and runs 1; the file's "
                         f"num_hidden_layers is "
                         f"{config['num_hidden_layers']}")
    for kind, seq in (("prefill", config["prefill_chunk"]), ("decode", 512)):
        ops = gemm_workloads(mc, ShapeConfig("x", seq, 1, kind))
        ran = [(o.gemm.name, o.gemm.m, o.gemm.k, o.gemm.n, o.chained,
                o.dynamic) for o in ops]
        want_steps = [(s.name, s.m, s.k, s.n, s.chained, s.dynamic)
                      for s in reference.stream(config, kind, seq)]
        if ran != want_steps:
            raise ValueError(f"{config['name']}: the {kind} stream "
                             f"{ran} is not the reference's {want_steps}")


def warm_up(sched, mix: dict) -> list[int]:
    """Compile every decode shape the cell's traffic reaches, and no
    other: the M bucket of a tick of all sessions, and of a tick of one
    fewer (a finished session's replacement is admitted a tick after it
    leaves).  Admitting the sessions in set-up runs the prefill pass.
    Returns the batch sizes warmed."""
    from repro.core.program import m_bucket
    n = mix["sessions"]
    warmed = {}
    for size in (n, n - 1):
        if size:
            warmed.setdefault(m_bucket(size), size)
    for size in warmed.values():
        _warm_decode(sched, size)
    return sorted(warmed.values())


def _zeros_env(ex, weights) -> dict:
    env = dict(weights)
    for name, (shape, kind) in ex.tensor_specs().items():
        if kind != "weight":
            env[name] = np.zeros(shape, np.float32)
    return env


def _warm_decode(sched, n: int) -> None:
    """One batched decode tick of ``n`` requests (its M bucket)."""
    envs = [_zeros_env(sched.decode, sched.decode_weights)
            for _ in range(n)]
    sched.decode.run_batch(sched.backend, envs, fused=sched.use_fused)
