"""Fused-segment vs per-layer execution: kernel wall clock + decode tok/s.

    PYTHONPATH=src python -m benchmarks.fusion_compare [--quick]
        [--json PATH] [--merge]

Two measurements, both warm (the jit/pallas trace cost is paid before the
timed loop so the numbers are steady-state serving cost):

  chain kernels   a multi-layer ``program.chain`` segment executed (a) as
                  one compiled launch per layer (today's per-layer pallas
                  path) and (b) as ONE fused megakernel launch
                  (``PallasBackend.run_segment``), same tensors, outputs
                  cross-checked against the einsum oracle before timing;
                  reported with the modelled HBM bytes each mode ships
                  (the fused mode structurally elides every interior
                  activation round trip)
  block fusion    a gemma decode transformer block spanning former
                  ``adapt`` (head-split) breaks -- [wv, qk, pv, wo] --
                  executed per-layer vs as ONE streamed megakernel
                  launch (asserted via ``Backend.n_launches``), with the
                  streamed VMEM high-water vs the resident-weights
                  footprint
  decode serving  the continuous-batching Scheduler over a reduced
                  (arch x shape) cell with the batched decode fast path
                  off vs on (``use_fused``), reporting tok/s

``--merge`` folds the results into an existing ``BENCH_results.json``
(the CI perf-smoke step merges into the uploaded artifact);
``benchmarks/run.py`` also embeds these numbers directly.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _build_chain(cfg, dims, acts, cache):
    """Lower + chain an L-layer MLP-style stack; returns (programs, seg)."""
    from repro.core import program as programlib
    from repro.runtime.executable import ACTIVATIONS

    progs = []
    for i in range(len(dims) - 1):
        m, k, n = dims[0][0], dims[i][1], dims[i + 1][1]
        from repro.core.mapper import Gemm
        g = Gemm(m=m, k=k, n=n, name=f"chain-l{i}")
        plan = cache.plan(g, cfg)
        act = acts[i]
        progs.append(cache.lower(
            plan.gemm, plan.choice, cfg,
            activation=ACTIVATIONS.get(act), act_name=act,
            out_name=f"O{i}"))
    chained = programlib.chain(progs, lower_fn=cache.lower)
    seg = programlib.fuse_segment(chained)
    return chained, seg


def _time(fn, iters):
    fn()                                  # one extra warm call
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def bench_chain_kernels(quick: bool = False) -> dict:
    """Per-layer launches vs ONE fused launch over a chained MLP stack."""
    from repro import backends
    from repro.configs.feather import feather_config
    from repro.runtime import ProgramCache

    cfg = feather_config(4, 16)
    cache = ProgramCache()
    m = 64
    widths = [96, 128, 96, 64] if not quick else [64, 96, 64]
    dims = [(m, w) for w in widths]
    acts = ["relu"] * (len(widths) - 2) + ["none"]
    chained, seg = _build_chain(cfg, dims, acts, cache)
    assert seg is not None, "benchmark chain must be fusion-legal"

    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, widths[0])).astype(np.float32)
    ws = [rng.standard_normal((widths[i], widths[i + 1]))
          .astype(np.float32) / np.sqrt(widths[i])
          for i in range(len(widths) - 1)]
    seg_t = {"I": x, **{f"W{i}": w for i, w in enumerate(ws)}}

    be = backends.PallasBackend(cfg, compile_cache=cache)

    def per_layer():
        for i, prog in enumerate(chained):
            t = {"W": ws[i]}
            if i == 0:
                t["I"] = x
            be.run_program(prog, t)
        return be.outputs[chained[-1].out_name]

    def fused():
        return be.run_segment(seg, seg_t)[seg.out_name]

    # correctness before timing: both modes == the einsum oracle
    ref = x.copy()
    for i, w in enumerate(ws):
        ref = ref @ w
        if acts[i] == "relu":
            ref = np.maximum(ref, 0)
    np.testing.assert_allclose(per_layer(), ref, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(fused(), ref, rtol=2e-4, atol=2e-3)

    iters = 5 if quick else 20
    us_layer = _time(per_layer, iters)
    us_fused = _time(fused, iters)
    return {
        "n_layers": len(ws),
        "m": m,
        "widths": widths,
        "us_per_layer": us_layer,
        "us_fused": us_fused,
        "kernel_speedup": us_layer / max(us_fused, 1e-9),
        "hbm_bytes_per_layer": seg.per_layer_kernel_hbm_bytes(),
        "hbm_bytes_fused": seg.kernel_hbm_bytes(),
        "hbm_bytes_elided": seg.elided_hbm_bytes(),
        "n_launches_per_layer": len(ws),
        "n_launches_fused": 1,
        "vmem_highwater_bytes": seg.vmem_highwater_bytes(),
        "vmem_resident_bytes": seg.resident_vmem_bytes(),
    }


def bench_block_fusion(quick: bool = False, arch: str = "gemma-7b") -> dict:
    """A transformer block spanning former adapt breaks, per-layer vs
    ONE streamed launch.

    Picks the decode cell's adapt-spanning fused segment ([wv, qk
    softmax, pv, wo] -- attention with the head-split/merge permutations
    done in-kernel), cross-checks both modes, asserts the fused mode is
    exactly one ``pallas_call`` via ``Backend.n_launches``, and reports
    wall clock, launches per block, elided HBM bytes and the streamed
    VMEM high-water against the keep-every-weight-resident footprint.
    """
    from repro import backends
    from repro.backends.base import Backend
    from repro.configs.feather import feather_config
    from repro.runtime import ModelExecutable, ProgramCache

    cfg = feather_config(4, 16)
    ex = ModelExecutable.for_cell(arch, "decode_tiny", cfg,
                                  cache=ProgramCache())
    seg = next(s for s in ex.segments
               if s.fused is not None and any(s.fused.adapts))
    steps = [ex.steps[i] for i in seg.indices]
    env = ex.make_tensors(seed=3)
    rng = np.random.default_rng(7)
    g0 = steps[0].op.gemm
    t = {"I": rng.standard_normal((g0.m, g0.k)).astype(np.float32)}
    for j, s in enumerate(steps):
        t[f"W{j}"] = np.asarray(env[s.weight_name], np.float32)
    fused = seg.fused

    be = backends.get_backend("pallas", cfg)
    before = be.n_launches
    out = np.asarray(be.run_segment(fused, t)[fused.out_name])
    launches_fused = be.n_launches - before
    assert launches_fused == 1, \
        f"block fusion must be ONE launch, got {launches_fused}"
    per_be = backends.get_backend("pallas", cfg)
    # the base replay on a pallas instance = today's per-layer path
    before = per_be.n_launches
    ref = np.asarray(
        Backend.run_segment(per_be, fused, t)[fused.out_name])
    launches_per_layer = per_be.n_launches - before
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    iters = 5 if quick else 20
    us_layer = _time(lambda: Backend.run_segment(per_be, fused, t), iters)
    us_fused = _time(lambda: be.run_segment(fused, t), iters)
    return {
        "arch": arch,
        "n_layers": fused.n_layers,
        "adapts": list(fused.adapts),
        "launches_per_block_per_layer": launches_per_layer,
        "launches_per_block_fused": launches_fused,
        "us_per_layer": us_layer,
        "us_fused": us_fused,
        "block_speedup": us_layer / max(us_fused, 1e-9),
        "hbm_bytes_elided": fused.elided_hbm_bytes(),
        "vmem_highwater_bytes": fused.vmem_highwater_bytes(),
        "vmem_resident_bytes": fused.resident_vmem_bytes(),
        "max_layer_working_set_bytes": fused.max_layer_working_set_bytes(),
    }


def bench_decode_serving(quick: bool = False,
                         arch: str = "gemma-7b") -> dict:
    """Scheduler decode throughput with the fused fast path off vs on."""
    from repro.configs.feather import feather_config
    from repro.runtime import ModelExecutable, ProgramCache, Scheduler

    cfg = feather_config(4, 16)
    cache = ProgramCache()
    prefill = ModelExecutable.for_cell(arch, "prefill_tiny", cfg,
                                       cache=cache)
    decode = ModelExecutable.for_cell(arch, "decode_tiny", cfg,
                                      cache=cache)
    n_requests, decode_steps = (2, 2) if quick else (4, 4)

    def serve(use_fused: bool):
        sched = Scheduler(prefill, decode, backend="pallas",
                          max_concurrent=2, use_fused=use_fused)
        for _ in range(n_requests):
            sched.submit(decode_steps=decode_steps)
        return sched.run()

    serve(False), serve(True)             # warm both jit paths
    rep_layer = serve(False)
    rep_fused = serve(True)
    fusion = decode.fusion_stats()
    # traced re-run (untimed): checksum-gated span breakdown of where a
    # decode tick goes -- kernel launches vs host scheduling
    from repro.obs import export as obs_export
    from repro.obs.trace import trace
    trace.clear().enable()
    try:
        rep_traced = serve(True)
    finally:
        trace.disable()
    assert ([r.state_checksum for r in rep_traced.requests]
            == [r.state_checksum for r in rep_fused.requests]), \
        "tracing perturbed serving state"
    breakdown = obs_export.span_breakdown("decode_tick", {"launch"})
    return {
        "arch": arch,
        "tok_s_per_layer": rep_layer.tokens_per_sec,
        "tok_s_fused": rep_fused.tokens_per_sec,
        "decode_speedup": (rep_fused.tokens_per_sec
                           / max(rep_layer.tokens_per_sec, 1e-9)),
        "fused_segments": rep_fused.decode_fused_segments,
        "segments": rep_fused.decode_segments,
        "fused_steps": fusion["n_fused_steps"],
        "decode_hbm_elided_bytes": rep_fused.decode_hbm_elided_bytes,
        "decode_tick_kernel_frac": breakdown["child_frac"],
        "decode_tick_host_frac": breakdown["host_frac"],
        "state_checksums_equal": (
            [r.state_checksum for r in rep_layer.requests]
            == [r.state_checksum for r in rep_fused.requests]),
    }


def run(quick: bool = False) -> dict:
    out = {
        "chain_kernels": bench_chain_kernels(quick),
        "block_fusion": bench_block_fusion(quick),
        "decode_serving": bench_decode_serving(quick),
    }
    c, b, d = (out["chain_kernels"], out["block_fusion"],
               out["decode_serving"])
    print(f"{'mode':>12} {'us/chain':>10} {'HBM B':>8} "
          f"{'launch/blk':>10} {'VMEM B':>9}   {'tok/s':>8}")
    print(f"{'per-layer':>12} {c['us_per_layer']:10.0f} "
          f"{c['hbm_bytes_per_layer']:8.0f} "
          f"{b['launches_per_block_per_layer']:10d} "
          f"{b['vmem_resident_bytes']:9.0f}   "
          f"{d['tok_s_per_layer']:8.1f}")
    print(f"{'fused':>12} {c['us_fused']:10.0f} "
          f"{c['hbm_bytes_fused']:8.0f} "
          f"{b['launches_per_block_fused']:10d} "
          f"{b['vmem_highwater_bytes']:9.0f}   {d['tok_s_fused']:8.1f}")
    print(f"kernel_speedup={c['kernel_speedup']:.2f}x "
          f"block_speedup={b['block_speedup']:.2f}x "
          f"decode_speedup={d['decode_speedup']:.2f}x "
          f"elided={c['hbm_bytes_elided']:.0f}B/chain "
          f"checksums_equal={d['state_checksums_equal']}")
    return out


def flat_metrics(result: dict) -> dict:
    """JSON-friendly flat view (merged into BENCH_results.json)."""
    keep = {
        "chain_kernels": ("us_per_layer", "us_fused", "kernel_speedup",
                          "hbm_bytes_per_layer", "hbm_bytes_fused",
                          "hbm_bytes_elided", "vmem_highwater_bytes",
                          "vmem_resident_bytes"),
        "block_fusion": ("us_per_layer", "us_fused", "block_speedup",
                         "launches_per_block_per_layer",
                         "launches_per_block_fused", "hbm_bytes_elided",
                         "vmem_highwater_bytes", "vmem_resident_bytes",
                         "max_layer_working_set_bytes"),
        "decode_serving": ("tok_s_per_layer", "tok_s_fused",
                           "decode_speedup", "fused_segments",
                           "decode_hbm_elided_bytes",
                           "decode_tick_kernel_frac",
                           "decode_tick_host_frac"),
    }
    return {f"{section}.{key}": result[section][key]
            for section, keys in keep.items() for key in keys}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI sizes")
    ap.add_argument("--json", default="", help="write results to PATH")
    ap.add_argument("--merge", action="store_true",
                    help="merge into an existing BENCH_results.json "
                         "instead of overwriting")
    ap.add_argument("--gate", action="store_true",
                    help="fail unless the adapt-spanning block is ONE "
                         "launch with streamed VMEM below resident")
    args = ap.parse_args()
    result = run(quick=args.quick)
    if args.gate:
        b = result["block_fusion"]
        assert b["launches_per_block_fused"] == 1, b
        assert b["vmem_highwater_bytes"] < b["vmem_resident_bytes"], b
        print(f"gate ok: 1 launch/block "
              f"(vs {b['launches_per_block_per_layer']}), VMEM "
              f"{b['vmem_highwater_bytes']}B < "
              f"{b['vmem_resident_bytes']}B resident")
    if args.json:
        payload = {}
        if args.merge and os.path.exists(args.json):
            with open(args.json) as f:
                payload = json.load(f)
        payload.setdefault("results", {})["fusion_compare"] = {
            "derived": f"kernel_speedup="
                       f"{result['chain_kernels']['kernel_speedup']:.3g}",
            **flat_metrics(result),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()
