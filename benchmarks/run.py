"""Benchmark driver: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

Prints human-readable tables for each artifact, then the machine-readable
``name,us_per_call,derived`` CSV summary, and writes ``BENCH_results.json``
(name -> us_per_call + derived metrics) so the perf trajectory is tracked
across PRs (CI uploads it as a workflow artifact).
"""

from __future__ import annotations

import argparse
import json
import platform
import time


def _fmt(x):
    if isinstance(x, float):
        return f"{x:.4g}"
    return str(x)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the full 58x9 sweep-based figures")
    ap.add_argument("--json", default="BENCH_results.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args()

    from benchmarks import (arch_plans, backend_compare, breakdown,
                            instr_traffic, isa_bitwidth, roofline, scaling,
                            serve_runtime, speedup, stall_table,
                            tpu_gpu_compare)

    rows = []

    def bench(name, fn, derive, metrics=None):
        """metrics(result) -> flat dict of JSON-friendly derived numbers."""
        t0 = time.time()
        result = fn()
        us = (time.time() - t0) * 1e6
        extra = metrics(result) if metrics is not None else {}
        rows.append((name, us, derive(result), extra))
        return result

    bench("tabV_isa_bitwidths", isa_bitwidth.run,
          lambda r: "estream_exact=" + str(all(
              v["e_streaming"] == v["paper"][2] for v in r.values())))
    bench("tabI_stall_table", stall_table.run,
          lambda r: "stall_16x256=" + _fmt(r[(16, 256)][0]),
          lambda r: {"stall_16x256": r[(16, 256)][0]})
    if not args.quick:
        bench("fig10_speedup", speedup.run,
              lambda r: "geomean_16x256="
              + _fmt(r[(16, 256)]["geomean_speedup"]),
              lambda r: {"geomean_speedup_16x256":
                         r[(16, 256)]["geomean_speedup"]})
        bench("fig12_instr_traffic", instr_traffic.run,
              lambda r: "geomean_reduction_16x256="
              + _fmt(r[(16, 256)]["geomean_reduction"]),
              lambda r: {"geomean_reduction_16x256":
                         r[(16, 256)]["geomean_reduction"]})
        bench("fig11_tpu_gpu_modelled", tpu_gpu_compare.run,
              lambda r: "feather_vs_tpu_irregular=" + _fmt(
                  r["feather_util_irregular"]
                  / max(r["tpu_util_irregular"], 1e-9)))
    bench("fig13_breakdown", breakdown.run,
          lambda r: "min_util=" + _fmt(min(v["utilization"]
                                           for v in r.values())),
          lambda r: {"min_utilization": min(v["utilization"]
                                            for v in r.values())})
    bench("sec6d_scaling", scaling.run,
          lambda r: "aw64to256_speedup=" + _fmt(
              r[("AW", 64)]["geomean_cycles"]
              / r[("AW", 256)]["geomean_cycles"])
          + " mesh8_speedup=" + _fmt(r[("mesh", 8)]["speedup"]),
          lambda r: {f"mesh{n}.{key}": r[("mesh", n)][key]
                     for n in (1, 2, 4, 8)
                     for key in ("traffic_ratio", "speedup",
                                 "load_imbalance", "tokens_per_sec",
                                 "per_array_minisa_bytes")
                     if key in r[("mesh", n)]})
    bench("arch_plans_16x256", arch_plans.run,
          lambda r: "n_cells=" + str(len(r)))
    bench("roofline_from_dryrun", roofline.run,
          lambda r: "cells=" + str(sum(1 for x in r
                                       if x.get("status") == "OK")))
    bench("backend_compare",
          lambda: backend_compare.run(quick=args.quick),
          lambda r: "max_wallclock_speedup=" + _fmt(
              max(v["wallclock_speedup"] for v in r.values())),
          lambda r: {f"{name}.{key}": row[key]
                     for name, row in r.items()
                     for key in ("us_interpreter", "us_pallas",
                                 "us_pallas_cold", "wallclock_speedup",
                                 "cycles_minisa", "macs")})
    bench("serve_runtime",
          lambda: serve_runtime.run(quick=args.quick),
          lambda r: "batched_decode_speedup=" + _fmt(
              r["decode_serving"]["batched_decode_speedup"])
          + " tok_s_pallas=" + _fmt(r["pallas"]["tokens_per_sec"]),
          lambda r: {f"{name}.{key}": row[key]
                     for name, row in r.items()
                     for key in ("tokens_per_sec", "total_tokens",
                                 "decode_tokens_per_sec",
                                 "launches_per_decode_tick",
                                 "ttft_p50_s", "ttft_p95_s",
                                 "latency_p50_s", "latency_p95_s",
                                 "latency_p99_s", "batch_decode",
                                 "cache_hit_rate", "cache_searches",
                                 "cache_compiles",
                                 "minisa_bytes_per_request",
                                 "micro_bytes_per_request",
                                 "stall_minisa", "stall_micro",
                                 "decode_fused",
                                 "decode_fused_segments",
                                 "decode_hbm_elided_bytes",
                                 "batched_decode_speedup",
                                 "decode_tok_s_batched",
                                 "decode_tok_s_per_request")
                     if key in row})
    # fused-vs-per-layer kernels/serving live in benchmarks.fusion_compare;
    # CI runs it as its own perf-smoke step and --merges the results into
    # the BENCH_results.json written here (measuring it twice per CI run
    # would only duplicate the slowest serving benchmarks)

    def mapper_walltime():
        """Mapper-search wall clock, scalar vs vectorized prescore."""
        from repro.configs.feather import feather_config
        from repro.core import mapper, workloads
        cfg = feather_config(16, 256)
        suite = workloads.small_suite()
        if not args.quick:
            suite = suite + workloads.ci_suite()[:12]
        out = {}
        for mode, vec in (("scalar", False), ("vectorized", True)):
            t0 = time.time()
            for g in suite:
                mapper.search(g, cfg, vectorized=vec)
            out[f"us_{mode}"] = (time.time() - t0) / len(suite) * 1e6
        out["speedup"] = out["us_scalar"] / max(out["us_vectorized"], 1e-9)
        return out

    bench("mapper_search", mapper_walltime,
          lambda r: "prescore_speedup=" + _fmt(r["speedup"]),
          lambda r: dict(r))

    print("\nname,us_per_call,derived")
    for name, us, derived, _ in rows:
        print(f"{name},{us:.0f},{derived}")

    if args.json:
        payload = {
            "meta": {
                "quick": args.quick,
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                "platform": platform.platform(),
            },
            "results": {
                name: {"us_per_call": us, "derived": derived, **extra}
                for name, us, derived, extra in rows
            },
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()
