"""Chaos-serving benchmark: seeded fault injection against the runtime.

    PYTHONPATH=src python -m benchmarks.chaos_serving [--quick]
        [--json PATH] [--merge] [--gate] [--fault-events PATH]

Runs the continuous-batching :class:`repro.runtime.Scheduler` through a
seeded :class:`repro.faults.FaultPlan` and holds it to the resilience
contract:

  * every injected fault kind (transient launch failure, NaN-poisoned
    launch output, KV page-pool exhaustion, corrupt disk-cache entry,
    and -- on a meshed run -- an array dropping out) is recovered:
    ``unrecovered == 0``;
  * every request finishes ``ok`` (no crashes, no unhandled faults);
  * every request's ``state_checksum`` is bit-identical to the same
    submission sequence served with faults off -- retries replay from
    the paged KV state, so chaos may cost time but never correctness.

Three legs, each paired with its own fault-free baseline: interpreter,
pallas (cross-request batched decode), and a 2-array mesh leg whose
``array_down`` event degrades the mesh mid-run (the stream re-lowers
onto the surviving array and keeps serving).  The chaos legs run under
the ``obs`` tracer; the fault swimlane events (injections, recoveries,
breaker transitions) become the ``--fault-events`` artifact CI uploads.

``--gate`` exits non-zero unless every leg recovered every fault with
fault-free-equal checksums; ``--merge`` folds the ``chaos_serving``
headline into an existing ``BENCH_results.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

#: (leg name, mesh arrays, Scheduler kwargs) -- chaos legs pair with a
#: fault-free baseline fed the identical submission sequence
#: breaker_threshold: at full concurrency one bad tick records one
#: failure per in-flight request; the default threshold (4) would trip
#: on the first launch window and eclipse the later ones, so the chaos
#: legs give the breaker headroom to exercise EVERY planned fault kind
LEGS = (
    ("interpreter", 1, dict(backend="interpreter", breaker_threshold=16)),
    ("pallas", 1, dict(backend="pallas", breaker_threshold=16)),
    ("interpreter_mesh2", 2, dict(backend="interpreter",
                                  breaker_threshold=16)),
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
        n_requests: int = 6, decode_steps: int = 4,
        max_concurrent: int = 4, seed: int = 0) -> dict:
    from repro.configs.feather import feather_config
    from repro.dist import ArrayMesh
    from repro.faults import FaultInjector, FaultPlan
    from repro.obs import metrics as obs_metrics
    from repro.obs.export import fault_events
    from repro.obs.trace import trace
    from repro.runtime import ModelExecutable, ProgramCache

    if quick:
        n_requests, decode_steps = 4, 3
    cfg = feather_config(4, 16)
    out: dict = {"legs": {}, "fault_events": []}
    print(f"{'leg':>18} {'status':>8} {'injected':>9} {'recovered':>10} "
          f"{'retries':>8} {'decode tok/s':>13} {'checksums':>10}")
    with tempfile.TemporaryDirectory(prefix="chaos_cache.") as tmp:
        cache = ProgramCache(path=os.path.join(tmp, "cache.bin"))
        for leg, n_arrays, kw in LEGS:
            mesh = ArrayMesh(n_arrays) if n_arrays > 1 else None
            prefill = ModelExecutable.for_cell(arch, "prefill_tiny", cfg,
                                               cache=cache, mesh=mesh)
            decode = ModelExecutable.for_cell(arch, "decode_tiny", cfg,
                                              cache=cache, mesh=mesh)
            base = _serve(prefill, decode, n_requests, decode_steps,
                          max_concurrent, **kw)
            injector = FaultInjector(
                FaultPlan.standard(seed, n_arrays=n_arrays))
            obs_metrics.reset()
            trace.clear().enable()
            try:
                chaos = _serve(prefill, decode, n_requests, decode_steps,
                               max_concurrent, faults=injector, **kw)
            finally:
                trace.disable()
            out["fault_events"].extend(fault_events())

            ref = {r.rid: r.state_checksum for r in base.requests}
            got = {r.rid: r.state_checksum for r in chaos.requests
                   if r.status not in ("timed_out",)}
            expected = {k for k, n in injector.plan.counts().items()
                        if n > 0}
            ok = (injector.unrecovered() == 0
                  and expected <= set(injector.injected)
                  and all(r.status == "ok" for r in chaos.requests)
                  and got == ref)
            s = chaos.summary()
            out["legs"][leg] = {
                "arch": arch,
                "n_requests": n_requests,
                "decode_steps": decode_steps,
                "plan": injector.plan.name,
                "injected": dict(injector.injected),
                "recovered": dict(injector.recovered),
                "skipped": dict(injector.skipped),
                "unrecovered": injector.unrecovered(),
                "retries_total": s["retries_total"],
                "requests_ok": s["requests_ok"],
                "requests_timed_out": s["requests_timed_out"],
                "requests_failed": s["requests_failed"],
                "mesh_degraded": s["resilience"].get("mesh_degraded", 0),
                "breaker_opens": s["resilience"]["breaker"]["opens"],
                "decode_tok_s_chaos": s["decode_tokens_per_sec"],
                "decode_tok_s_fault_free":
                    base.summary()["decode_tokens_per_sec"],
                "checksums_match": got == ref,
                "recovered_all": ok,
            }
            n_inj = sum(injector.injected.values())
            n_rec = sum(injector.recovered.values())
            print(f"{leg:>18} {'PASS' if ok else 'FAIL':>8} "
                  f"{n_inj:>9} {n_rec:>10} {s['retries_total']:>8} "
                  f"{s['decode_tokens_per_sec']:>13.1f} "
                  f"{'equal' if got == ref else 'DIVERGED':>10}")
            assert ok, (f"chaos leg {leg!r} failed: "
                        f"{out['legs'][leg]}")
        cache.save()

    legs = out["legs"]
    all_injected: dict[str, int] = {}
    for leg in legs.values():
        for kind, n in leg["injected"].items():
            all_injected[kind] = all_injected.get(kind, 0) + n
    out["chaos_serving"] = {
        "arch": arch,
        "seed": seed,
        "n_requests": n_requests,
        "decode_steps": decode_steps,
        "legs": sorted(legs),
        "faults_injected_total": sum(all_injected.values()),
        "fault_kinds_injected": sorted(all_injected),
        "unrecovered_total": sum(g["unrecovered"] for g in legs.values()),
        "retries_total": sum(g["retries_total"] for g in legs.values()),
        "requests_failed": sum(g["requests_failed"]
                               for g in legs.values()),
        "mesh_degraded": sum(g["mesh_degraded"] for g in legs.values()),
        "checksums_match": all(g["checksums_match"]
                               for g in legs.values()),
        "recovered_all": all(g["recovered_all"] for g in legs.values()),
        "n_fault_events": len(out["fault_events"]),
    }
    head = out["chaos_serving"]
    print(f"chaos gate: {head['faults_injected_total']} faults over "
          f"{len(legs)} legs ({', '.join(head['fault_kinds_injected'])}), "
          f"{head['unrecovered_total']} unrecovered, checksums "
          f"{'equal' if head['checksums_match'] else 'DIVERGED'}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI sizes")
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--decode-steps", type=int, default=4)
    ap.add_argument("--concurrent", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="fault plan seed (the chaos run replays "
                         "deterministically for one seed)")
    ap.add_argument("--json", default="", help="write results to PATH")
    ap.add_argument("--merge", action="store_true",
                    help="merge into an existing BENCH_results.json "
                         "instead of overwriting")
    ap.add_argument("--gate", action="store_true",
                    help="exit non-zero unless every fault recovered "
                         "and every checksum matched fault-free")
    ap.add_argument("--fault-events", default="", metavar="PATH",
                    help="write the chaos legs' fault swimlane events "
                         "(injections/recoveries/breaker) as JSON")
    args = ap.parse_args()
    result = run(quick=args.quick, arch=args.arch,
                 n_requests=args.requests,
                 decode_steps=args.decode_steps,
                 max_concurrent=args.concurrent, seed=args.seed)
    head = result["chaos_serving"]
    if args.fault_events:
        with open(args.fault_events, "w") as f:
            json.dump({"fault_events": result["fault_events"]}, f,
                      indent=1)
        print(f"wrote {args.fault_events}")
    if args.json:
        payload = {}
        if args.merge and os.path.exists(args.json):
            with open(args.json) as f:
                payload = json.load(f)
        payload.setdefault("results", {})["chaos_serving"] = {
            "derived": f"unrecovered={head['unrecovered_total']} "
                       f"checksums_match={head['checksums_match']}",
            **head,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if args.gate and not (head["recovered_all"]
                          and head["checksums_match"]
                          and head["unrecovered_total"] == 0):
        print("FAIL: chaos run left unrecovered faults or diverged "
              "from the fault-free checksums")
        sys.exit(1)


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()
