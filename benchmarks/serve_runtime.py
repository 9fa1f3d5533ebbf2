"""Serving-runtime benchmark: batched decode vs per-request fused decode.

    PYTHONPATH=src python -m benchmarks.serve_runtime [--quick]
        [--json PATH] [--merge] [--gate]

Runs the continuous-batching :class:`repro.runtime.Scheduler` over a
reduced (arch x shape) serving cell in three modes, all fed the identical
submission sequence (per-request ``state_checksum``s are asserted
bit-equal across the modes before any number is reported):

  interpreter         sequential per-request, per-layer Programs -- the
                      reference trajectory
  pallas_per_request  one fused-segment launch chain per request per
                      decode step (the prior serving fast path)
  pallas              cross-request batched decode: every active
                      request's token stacked along M, ONE launch per
                      segment per tick through the M-polymorphic
                      ``BatchPlan`` (+ flash-decode attention over the
                      paged per-request KV)

Per mode the table reports wall-clock tok/s, decode-phase tok/s,
time-to-first-token and end-to-end latency percentiles (TTFT is decode-
independent -- it measures queueing + prefill -- so it is reported
separately from decode throughput), kernel launches per decode tick and
ProgramCache reuse.  The headline ``decode_serving`` section records the
batched-vs-per-request decode speedup; ``--gate`` exits non-zero if the
batched path regresses below the per-request fused path.  ``--merge``
folds ``decode_serving`` into an existing ``BENCH_results.json`` (the CI
serving perf-smoke step); ``benchmarks/run.py`` also embeds the per-mode
summaries directly.

After the timed modes, the batched mode re-runs once under the ``obs``
tracer (untimed): its per-request ``state_checksum``s are asserted
bit-equal to the untraced run -- tracing must never perturb serving
numerics -- and the span buffer yields ``decode_tick_kernel_frac`` (the
measured fraction of a decode tick spent inside kernel launches vs host
scheduling) for the BENCH entry.  ``--trace PATH`` writes the
Chrome/Perfetto ``trace.json`` (per-request swimlanes) and
``--metrics PATH`` the Prometheus-style snapshot from that traced run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

#: (mode name, Scheduler kwargs) -- identical submissions, three paths
MODES = (
    ("interpreter", dict(backend="interpreter", batch_decode=False,
                         use_fused=False)),
    ("pallas_per_request", dict(backend="pallas", batch_decode=False,
                                use_fused=True)),
    ("pallas", dict(backend="pallas", batch_decode=True, use_fused=True)),
)


def _serve(prefill, decode, n_requests, decode_steps, max_concurrent,
           **kw):
    from repro.runtime import Scheduler
    sched = Scheduler(prefill, decode, max_concurrent=max_concurrent,
                      **kw)
    for _ in range(n_requests):
        sched.submit(decode_steps=decode_steps)
    return sched.run()


def run(quick: bool = False, arch: str = "gemma-7b",
        n_requests: int = 8, decode_steps: int = 4,
        max_concurrent: int = 8) -> dict[str, dict]:
    from repro.configs.feather import feather_config
    from repro.runtime import ModelExecutable, ProgramCache, Scheduler

    if quick:
        decode_steps = 3
    cfg = feather_config(4, 16)
    cache = ProgramCache()   # one cache across every mode
    prefill = ModelExecutable.for_cell(arch, "prefill_tiny", cfg,
                                       cache=cache)
    decode = ModelExecutable.for_cell(arch, "decode_tiny", cfg,
                                      cache=cache)

    # Warm the pallas compile tiers (m=1 fused segments + the batched
    # bucket this concurrency hits) so both timed pallas modes measure
    # steady-state serving, not first-call trace cost.
    for _, kw in MODES[1:]:
        _serve(prefill, decode, n_requests=max_concurrent, decode_steps=1,
               max_concurrent=max_concurrent, **kw)

    out: dict[str, dict] = {}
    checksums: dict[str, dict] = {}
    print(f"{'mode':>19} {'tok/s':>9} {'decode tok/s':>13} "
          f"{'ttft_p50 ms':>12} {'p95 lat ms':>11} {'launch/tick':>12} "
          f"{'hit_rate':>9}")
    for mode, kw in MODES:
        before = cache.stats.snapshot()
        rep = _serve(prefill, decode, n_requests=n_requests,
                     decode_steps=decode_steps,
                     max_concurrent=max_concurrent, **kw)
        s = rep.summary()
        s["cache_delta"] = cache.stats.delta(before)
        s["arch"] = arch
        s["decode_steps"] = decode_steps
        out[mode] = s
        checksums[mode] = {r.rid: r.state_checksum for r in rep.requests}
        lpt = s["launches_per_decode_tick"]
        print(f"{mode:>19} {s['tokens_per_sec']:9.1f} "
              f"{s['decode_tokens_per_sec']:13.1f} "
              f"{s['ttft_p50_s'] * 1e3:12.2f} "
              f"{s['latency_p95_s'] * 1e3:11.2f} "
              f"{lpt if lpt else 0.0:12.1f} {s['cache_hit_rate']:9.2f}")

    ref = checksums["interpreter"]
    for mode, sums in checksums.items():
        assert sums == ref, (
            f"state_checksum divergence: {mode} vs interpreter")

    # Traced re-run of the batched mode (untimed): the tracing-on
    # checksums must equal the tracing-off ones, and the span buffer
    # yields the decode-tick kernel/host breakdown for the BENCH entry.
    from repro.obs import export as obs_export
    from repro.obs import metrics as obs_metrics
    from repro.obs.trace import trace
    obs_metrics.reset()
    trace.clear().enable()
    try:
        traced = _serve(prefill, decode, n_requests=n_requests,
                        decode_steps=decode_steps,
                        max_concurrent=max_concurrent, **dict(MODES[2][1]))
    finally:
        trace.disable()
    traced_sums = {r.rid: r.state_checksum for r in traced.requests}
    assert traced_sums == ref, (
        "tracing perturbed serving state: traced pallas checksums "
        "diverged from the untraced run")
    breakdown = obs_export.span_breakdown("decode_tick", {"launch"})

    per, bat = out["pallas_per_request"], out["pallas"]
    speedup = (bat["decode_tokens_per_sec"]
               / max(per["decode_tokens_per_sec"], 1e-9))
    out["decode_serving"] = {
        "arch": arch,
        "n_requests": n_requests,
        "max_concurrent": max_concurrent,
        "decode_steps": decode_steps,
        "decode_tok_s_per_request": per["decode_tokens_per_sec"],
        "decode_tok_s_batched": bat["decode_tokens_per_sec"],
        "batched_decode_speedup": speedup,
        "launches_per_decode_tick_per_request":
            per["launches_per_decode_tick"],
        "launches_per_decode_tick_batched":
            bat["launches_per_decode_tick"],
        "ttft_p50_s": bat["ttft_p50_s"],
        "ttft_p95_s": bat["ttft_p95_s"],
        "ttft_p99_s": bat["ttft_p99_s"],
        "latency_p50_s": bat["latency_p50_s"],
        "latency_p95_s": bat["latency_p95_s"],
        "latency_p99_s": bat["latency_p99_s"],
        "kv_high_water_pages": bat["kv"].get("high_water_pages", 0),
        "checksums_match": True,
        "traced_checksums_match": True,
        "decode_tick_kernel_frac": breakdown["child_frac"],
        "decode_tick_host_frac": breakdown["host_frac"],
        "decode_ticks_traced": breakdown["n_parents"],
    }
    print(f"batched decode speedup over per-request fused: "
          f"{speedup:.2f}x at {max_concurrent} concurrent "
          f"({bat['launches_per_decode_tick']} launches/tick vs "
          f"{per['launches_per_decode_tick']})")
    print(f"decode tick breakdown (traced): "
          f"{breakdown['child_frac'] * 100:.1f}% in kernel launches, "
          f"{breakdown['host_frac'] * 100:.1f}% host scheduling "
          f"over {breakdown['n_parents']} ticks")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI sizes")
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=4)
    ap.add_argument("--concurrent", type=int, default=8)
    ap.add_argument("--json", default="", help="write results to PATH")
    ap.add_argument("--merge", action="store_true",
                    help="merge into an existing BENCH_results.json "
                         "instead of overwriting")
    ap.add_argument("--gate", action="store_true",
                    help="exit non-zero if batched decode tok/s falls "
                         "below the per-request fused path")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write the traced run's Chrome/Perfetto "
                         "trace.json (open in ui.perfetto.dev)")
    ap.add_argument("--metrics", default="", metavar="PATH",
                    help="write the traced run's Prometheus-style "
                         "metrics snapshot")
    args = ap.parse_args()
    result = run(quick=args.quick, arch=args.arch,
                 n_requests=args.requests,
                 decode_steps=args.decode_steps,
                 max_concurrent=args.concurrent)
    serving = result["decode_serving"]
    if args.trace:
        from repro.obs.export import write_chrome_trace
        print(f"wrote {write_chrome_trace(args.trace)}")
    if args.metrics:
        from repro.obs.export import write_metrics_snapshot
        print(f"wrote {write_metrics_snapshot(args.metrics)}")
    if args.json:
        payload = {}
        if args.merge and os.path.exists(args.json):
            with open(args.json) as f:
                payload = json.load(f)
        payload.setdefault("results", {})["decode_serving"] = {
            "derived": f"batched_decode_speedup="
                       f"{serving['batched_decode_speedup']:.3g}",
            **serving,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if args.gate and serving["batched_decode_speedup"] < 1.0:
        print(f"FAIL: batched decode "
              f"({serving['decode_tok_s_batched']:.1f} tok/s) regressed "
              f"below per-request fused "
              f"({serving['decode_tok_s_per_request']:.1f} tok/s)")
        sys.exit(1)


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()
