"""Joint mapper + measured autotune vs greedy-then-snap fused geometry.

    PYTHONPATH=src python -m benchmarks.mapper_autotune [--quick]
        [--json PATH] [--merge] [--gate]

For a chained segment per Tab. IV CI family (fhe-bconv / fhe-ntt /
zkp-ntt / gpt-oss shapes in a 3-layer MLP-style chain), compares the
fused-chain wall clock of

  untuned   the pre-frontier pipeline: per-GEMM ``mapper.search``
            winners chained, then ``fuse_segment``'s post-hoc snapping
            picks (bm, per-layer bk)
  tuned     the fusion-aware joint mapper: ``mapper.search_segment``'s
            Pareto frontier over {traffic, cycles, VMEM} measured by
            ``runtime.autotune`` against real launch spans, winner
            persisted in the ProgramCache tuned tier

Both modes run the SAME per-layer Programs -- only the launch geometry
differs -- and both are cross-checked against the einsum oracle before
timing.  After the sweep the whole pipeline re-runs against the warmed
cache and asserts ZERO mapper searches, ZERO joint searches and ZERO
kernel compiles (the serving-process contract: structurally identical
segments never re-tune).

``--gate`` fails unless tuned wall clock <= untuned on every chain
(small tolerance for timer noise) and the warm pass did no work.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import numpy as np


def _time(fn, iters):
    fn()                                  # one extra warm call
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def _family_chains(quick: bool) -> list[tuple[str, tuple[int, int, int]]]:
    """One representative CI-extent GEMM per Tab. IV family; its (m, k,
    n) seeds a wired chain m x k -> n -> k (-> n)."""
    from repro.core import workloads

    fams: dict[str, tuple[int, int, int]] = {}
    for g in workloads.ci_suite():
        fam = g.name.rsplit("-", 2)[0]
        if fam.startswith("conv"):
            continue
        best = fams.get(fam)
        if best is None or g.macs > best[0] * best[1] * best[2]:
            fams[fam] = (g.m, g.k, g.n)
    chains = sorted(fams.items())
    return chains[:2] if quick else chains


def _build_chain(cfg, m, k, n, n_layers, cache):
    """Lower + chain an MLP-style stack over the family's (k, n) ranks."""
    from repro.core import program as programlib
    from repro.core.mapper import Gemm
    from repro.runtime.executable import ACTIVATIONS

    widths = [k] + [n if i % 2 == 0 else k for i in range(n_layers)]
    progs = []
    for i in range(n_layers):
        g = Gemm(m=m, k=widths[i], n=widths[i + 1], name=f"chain-l{i}")
        plan = cache.plan(g, cfg)
        act = "relu" if i < n_layers - 1 else "none"
        progs.append(cache.lower(
            plan.gemm, plan.choice, cfg,
            activation=ACTIVATIONS.get(act), act_name=act,
            out_name=f"O{i}"))
    return programlib.chain(progs, lower_fn=cache.lower), widths


def bench_chain(cfg, fam, shape, cache, be, quick: bool) -> dict:
    from repro.core import program as programlib
    from repro.runtime import autotune

    m, k, n = shape
    n_layers = 2 if quick else 3
    progs, widths = _build_chain(cfg, m, k, n, n_layers, cache)

    untuned = programlib.fuse_segment(progs)
    assert untuned is not None, f"{fam} chain must be fusion-legal"
    report = autotune.autotune_segment(
        progs, be, cache=cache,
        top_k=2 if quick else 4, iters=2 if quick else 3)
    assert report is not None, f"{fam} autotune found no frontier"
    w = report.winner
    tuned = programlib.fuse_segment(progs, bm=w.bm, layer_bks=w.layer_bks)
    assert tuned is not None

    rng = np.random.default_rng(1)
    x = rng.standard_normal((m, widths[0])).astype(np.float32)
    ws = [rng.standard_normal((widths[i], widths[i + 1]))
          .astype(np.float32) / np.sqrt(widths[i])
          for i in range(n_layers)]
    t = {"I": x, **{f"W{i}": w_ for i, w_ in enumerate(ws)}}

    # correctness before timing: both geometries == the einsum oracle
    ref = x.copy()
    for i, w_ in enumerate(ws):
        ref = ref @ w_
        if i < n_layers - 1:
            ref = np.maximum(ref, 0)
    out_u = be.run_segment(untuned, t)[untuned.out_name]
    out_t = be.run_segment(tuned, t)[tuned.out_name]
    np.testing.assert_allclose(out_u, ref, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(out_t, ref, rtol=2e-4, atol=2e-3)

    iters = 3 if quick else 10
    same_geometry = (untuned.bm, untuned.layer_bks) == (tuned.bm,
                                                       tuned.layer_bks)
    us_untuned = _time(lambda: be.run_segment(untuned, t), iters)
    us_tuned = (us_untuned if same_geometry
                else _time(lambda: be.run_segment(tuned, t), iters))
    grid = lambda seg: seg.m_steps * sum(  # noqa: E731
        -(-p.gemm.k // bk) for p, bk in zip(seg.programs, seg.layer_bks))
    return {
        "family": fam,
        "m": m, "widths": widths, "n_layers": n_layers,
        "us_untuned": us_untuned,
        "us_tuned": us_tuned,
        "speedup": us_untuned / max(us_tuned, 1e-9),
        "bm_untuned": untuned.bm, "bm_tuned": tuned.bm,
        "bks_untuned": list(untuned.layer_bks),
        "bks_tuned": list(tuned.layer_bks),
        "grid_steps_untuned": grid(untuned),
        "grid_steps_tuned": grid(tuned),
        "vmem_untuned": untuned.vmem_highwater_bytes(),
        "vmem_tuned": tuned.vmem_highwater_bytes(),
        "hbm_untuned": untuned.kernel_hbm_bytes(),
        "hbm_tuned": tuned.kernel_hbm_bytes(),
        "kernel_frac_tuned": w.kernel_frac,
        "n_points_measured": w.n_points_measured,
        "autotune_cached": report.cached,
    }


def run(quick: bool = False) -> dict:
    from repro import backends
    from repro.configs.feather import feather_config
    from repro.runtime import ProgramCache, autotune

    cfg = feather_config(4, 16)
    cache = ProgramCache()
    be = backends.PallasBackend(cfg, compile_cache=cache)

    chains = []
    for fam, shape in _family_chains(quick):
        chains.append(bench_chain(cfg, fam, shape, cache, be, quick))

    # warm-cache contract: rebuilding + re-tuning every chain against
    # the same cache does zero searches, zero joint searches and zero
    # kernel compiles -- structurally identical segments never re-tune
    before = cache.stats.snapshot()
    for fam, shape in _family_chains(quick):
        m, k, n = shape
        progs, _ = _build_chain(cfg, m, k, n, 2 if quick else 3, cache)
        rep = autotune.autotune_segment(progs, be, cache=cache)
        assert rep is not None and rep.cached, \
            f"{fam}: warm autotune must serve the tuned tier"
    delta = cache.stats.delta(before)
    warm = {"searches": delta["plan_misses"],
            "joint_searches": delta["frontier_misses"],
            "compiles": delta["compile_misses"] + delta["fused_misses"],
            "tuned_hits": delta["tuned_hits"]}

    speedups = [c["speedup"] for c in chains]
    geomean = math.exp(sum(math.log(s) for s in speedups) / len(speedups))
    out = {"chains": chains, "geomean_speedup": geomean, "warm": warm,
           "cache": cache.summary()}

    print(f"{'family':>12} {'us untuned':>11} {'us tuned':>9} "
          f"{'speedup':>8} {'grid u/t':>9} {'bm u/t':>9}")
    for c in chains:
        print(f"{c['family']:>12} {c['us_untuned']:11.0f} "
              f"{c['us_tuned']:9.0f} {c['speedup']:8.2f} "
              f"{c['grid_steps_untuned']:>4}/{c['grid_steps_tuned']:<4} "
              f"{c['bm_untuned']:>4}/{c['bm_tuned']:<4}")
    print(f"geomean_speedup={geomean:.2f}x  warm: "
          f"searches={warm['searches']} "
          f"joint_searches={warm['joint_searches']} "
          f"compiles={warm['compiles']}")
    return out


def flat_metrics(result: dict) -> dict:
    """JSON-friendly flat view (merged into BENCH_results.json)."""
    out = {"geomean_speedup": result["geomean_speedup"],
           "warm_searches": result["warm"]["searches"],
           "warm_joint_searches": result["warm"]["joint_searches"],
           "warm_compiles": result["warm"]["compiles"]}
    for c in result["chains"]:
        fam = c["family"]
        for key in ("us_untuned", "us_tuned", "speedup",
                    "grid_steps_untuned", "grid_steps_tuned",
                    "vmem_untuned", "vmem_tuned"):
            out[f"{fam}.{key}"] = c[key]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI sizes")
    ap.add_argument("--json", default="", help="write results to PATH")
    ap.add_argument("--merge", action="store_true",
                    help="merge into an existing BENCH_results.json "
                         "instead of overwriting")
    ap.add_argument("--gate", action="store_true",
                    help="fail unless tuned <= untuned wall clock per "
                         "chain and the warm pass did zero work")
    args = ap.parse_args()
    result = run(quick=args.quick)
    if args.gate:
        for c in result["chains"]:
            assert c["us_tuned"] <= c["us_untuned"] * 1.05, \
                f"{c['family']}: tuned {c['us_tuned']:.0f}us > untuned " \
                f"{c['us_untuned']:.0f}us"
        w = result["warm"]
        assert w["searches"] == 0 and w["joint_searches"] == 0 \
            and w["compiles"] == 0, w
        print(f"gate ok: tuned <= untuned on every chain, warm pass "
              f"did zero searches/compiles "
              f"(geomean {result['geomean_speedup']:.2f}x)")
    if args.json:
        payload = {}
        if args.merge and os.path.exists(args.json):
            with open(args.json) as f:
                payload = json.load(f)
        payload.setdefault("results", {})["mapper_autotune"] = {
            "derived": f"geomean_speedup="
                       f"{result['geomean_speedup']:.3g}",
            **flat_metrics(result),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()


