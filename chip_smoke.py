"""Bring-up smoke: serve minitron-4b at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the ArrayMesh(4) phase only

It drives the serving path through the entry points a user calls --
``ModelExecutable.for_cell(..., reduce_model=False)`` and
``Scheduler(backend="pallas")`` -- with random weights from a fixed seed,
at minitron-4b's published widths: d_model 3072, 24/8 heads of 128,
d_ff 9216, vocab 256000.  Each distinct layer runs once, as the runtime
always does (``reps`` only scales the traffic accounting).  Outputs are
checked against the runtime's host NumPy stream oracle at its own
tolerances.

One chip: a checked prefill (256 tokens) and decode, a batched decode
tick checked against per-request decodes, and 4 requests x 8 decode
steps served with ``max_concurrent=4``.  ``--four-chips``: the same cell
on ``ArrayMesh(4)`` through the Scheduler, each array's work on its own
chip, checked against the oracle and against the one-array outputs on
the same seeds.

Wall times are host clock around work whose results come back as host
NumPy arrays, so every launch inside has completed.  The script exits
non-zero with no result line when JAX finds no TPU or any phase fails;
otherwise the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "minitron-4b"
PUBLISHED = {"d_model": 3072, "d_ff": 9216, "vocab": 256000}
PROMPT = 256          # prefill tokens (one chunk)
DECODE_STEPS = 8      # per served request
REQUESTS = 4
MESH_REQUESTS, MESH_STEPS = 2, 4   # the four-chip phase serves per request
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform "
            f"{devices[0].platform!r}, {len(devices)} device(s))")
    return devices


def clock(name, fn, *args, **kwargs):
    """Run one named phase and print its wall time."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    log(f"  wall {name}: {time.perf_counter() - t0:.3f} s")
    return out


def build(*, mesh=None, decode_kv=PROMPT + DECODE_STEPS,
          cache=None):
    from repro.configs.base import ShapeConfig
    from repro.configs.feather import feather_config
    from repro.runtime import ModelExecutable, ProgramCache

    cfg = feather_config(4, 16)
    cache = cache if cache is not None else ProgramCache()

    def cell(kind, seq_len):
        return ModelExecutable.for_cell(
            ARCH, ShapeConfig(f"{kind}_{seq_len}", seq_len=seq_len,
                              global_batch=1, kind=kind),
            cfg, cache=cache, reduce_model=False, mesh=mesh)

    tag = "" if mesh is None else f" ({mesh})"
    prefill = clock(f"build prefill{tag}", cell, "prefill", PROMPT)
    decode = clock(f"build decode{tag}", cell, "decode", decode_kv)
    return prefill, decode


def check_widths(ex) -> None:
    by_name = {s.op.gemm.name: s.op.gemm for s in ex.steps}
    widths = {"d_model": by_name["wq"].k, "d_ff": by_name["mlp.up"].n,
              "vocab": by_name["lm_head"].n}
    log(f"  {ex.name}: widths {widths}, {len(ex.steps)} steps, "
        f"{len(ex.segments)} segments "
        f"({sum(s.fused is not None for s in ex.segments)} fused)")
    if widths != PUBLISHED:
        raise SystemExit(f"chip_smoke: {ex.name} runs at {widths}, not the "
                         f"published {PUBLISHED}")


def final_tol(ex) -> dict:
    """The stream oracle's own tolerance at the stream's last step."""
    k = ex.steps[-1].op.gemm.k
    return {"rtol": 2e-3, "atol": 2e-3 + 2e-3 * k}


def checked_runs(be, ex, label, tensors):
    """Compile (first call), oracle check, then a warm timed run."""
    import numpy as np

    clock(f"{label} first call (compiles)", ex.run, be, tensors=tensors,
          fused=True)
    clock(f"{label} oracle check", ex.run, be, tensors=tensors, check=True,
          fused=True)
    warm = clock(f"{label} warm", ex.run, be, tensors=tensors, fused=True)
    if not np.all(np.isfinite(warm.final)):
        raise SystemExit(f"chip_smoke: non-finite {label} output")
    log(f"  {label}: stream-oracle check passed, output "
        f"{warm.final.shape}, {warm.fused_segments} fused launches")
    return warm


def require_compiled(be) -> None:
    from repro.backends.pallas_backend import PallasBackend
    log(f"  PallasBackend.interpret = {be.interpret}")
    if not isinstance(be, PallasBackend) or be.interpret:
        raise SystemExit("chip_smoke: the Pallas backend is interpreting")


def launch_counts() -> dict:
    from repro.obs import metrics as obs_metrics
    return dict(obs_metrics.snapshot().get("backend_launches_total", {}))


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def batch_plan_kinds(decode, n) -> list:
    plan = decode.batch_plan(n)
    kinds = []
    for seg in plan.segments:
        names = [decode.steps[i].op.gemm.name for i in seg.indices]
        kinds.append(f"{seg.kind}{'+fused' if seg.fused else ''}:"
                     f"{'/'.join(names)}")
    perreq = [k for k in kinds if k.startswith("perreq")]
    log(f"  batch plan (bucket {plan.bucket}): {kinds}")
    log(f"  perreq segments: {perreq or 'none'}; "
        f"launches_per_tick: {plan.launches_per_tick}")


def check_batched_decode(be, decode, tensors) -> None:
    """One batched decode tick (bucket 4) against per-request decodes:
    shared weights, per-request KV operands and inputs."""
    import numpy as np

    envs = []
    for r in range(REQUESTS):
        rng = np.random.default_rng(SEED + 1 + r)
        env = dict(tensors)
        for name, (shape, kind) in decode.tensor_specs().items():
            if kind != "weight":
                env[name] = rng.standard_normal(shape).astype(np.float32)
        envs.append(env)
    batched = decode.run_batch(be, envs)
    tol = final_tol(decode)
    for r, env in enumerate(envs):
        ref = decode.run(be, tensors=env, fused=True).final
        np.testing.assert_allclose(
            batched[r], ref, **tol,
            err_msg=f"batched decode row {r} diverged from its request")
    log(f"  batched decode tick: {REQUESTS} rows match per-request decodes")


def serve(prefill, decode, *, requests, steps, max_concurrent):
    from repro.runtime import Scheduler

    sched = clock("scheduler setup (weights)", Scheduler, prefill, decode,
                  backend="pallas", max_concurrent=max_concurrent,
                  weight_seed=SEED, seed=SEED, finite_check=True)
    for _ in range(requests):
        sched.submit(decode_steps=steps)
    report = clock("serve", sched.run)
    s = report.summary()
    bad = [r.rid for r in report.requests
           if r.status != "ok" or r.decode_tokens != steps]
    if len(report.requests) != requests or bad or s["retries_total"]:
        raise SystemExit(f"chip_smoke: serving failed: requests {bad} "
                         f"not ok, retries {s['retries_total']}")
    log(f"  served {s['n_requests']} requests, {s['total_tokens']} tokens, "
        f"{s['decode_ticks']} decode ticks, batch_decode "
        f"{s['batch_decode']}, launches_per_decode_tick "
        f"{s['launches_per_decode_tick']:.2f}")
    log(f"  state checksums: {[r.state_checksum[:12] for r in report.requests]}")
    return sched, report


def one_chip(devices) -> None:
    prefill, decode = build()
    for ex in (prefill, decode):
        check_widths(ex)
    be = prefill.make_backend("pallas")
    require_compiled(be)
    t_pre = clock("prefill weights+inputs", prefill.make_tensors, SEED)
    checked_runs(be, prefill, "prefill", t_pre)
    del t_pre
    t_dec = clock("decode weights+inputs", decode.make_tensors, SEED)
    checked_runs(be, decode, "decode", t_dec)
    batch_plan_kinds(decode, REQUESTS)
    clock("batched decode check", check_batched_decode, be, decode, t_dec)
    del t_dec
    sched, _ = serve(prefill, decode, requests=REQUESTS,
                     steps=DECODE_STEPS, max_concurrent=REQUESTS)
    stats = prefill.cache.stats.summary()
    log(f"  mapper searches {stats.get('searches')}, program compiles "
        f"{stats.get('compiles')}, backend compiles "
        f"{be.n_compiles + sched.backend.n_compiles}")
    log(f"  launches by kernel/device: {launch_counts()}")
    log(f"  peak_bytes_in_use: {peak_bytes(devices[:1])}")


def four_chips(devices) -> None:
    import numpy as np

    from repro.dist import ArrayMesh
    from repro.runtime import ProgramCache

    mesh = ArrayMesh(4)
    if len(devices) < 4 or mesh.jax_mesh() is None:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 TPU devices "
                         f"backing ArrayMesh(4), found {len(devices)}")
    cache = ProgramCache()
    kv = PROMPT + MESH_STEPS
    prefill, decode = build(mesh=mesh, decode_kv=kv, cache=cache)
    one_pre, one_dec = build(decode_kv=kv, cache=cache)
    for ex in (prefill, decode):
        check_widths(ex)
    for ex, one, label in ((prefill, one_pre, "prefill"),
                           (decode, one_dec, "decode")):
        tensors = clock(f"{label} weights+inputs", ex.make_tensors, SEED)
        be = ex.make_backend("pallas")
        require_compiled(be)
        got = clock(f"{label} on ArrayMesh(4), oracle check", ex.run, be,
                    tensors=tensors, check=True, fused=True)
        ref = clock(f"{label} on one array", one.run,
                    one.make_backend("pallas"), tensors=tensors,
                    fused=True)
        np.testing.assert_allclose(
            got.final, ref.final, **final_tol(ex),
            err_msg=f"{label} on ArrayMesh(4) diverged from one array")
        log(f"  {label}: ArrayMesh(4) passes the stream oracle and matches "
            f"one array, output {got.final.shape}")
        del tensors
    serve(prefill, decode, requests=MESH_REQUESTS, steps=MESH_STEPS,
          max_concurrent=MESH_REQUESTS)
    log(f"  launches by kernel/device: {launch_counts()}")
    log(f"  peak_bytes_in_use per device: {peak_bytes(devices[:4])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ArrayMesh(4) phase on four chips")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    sys.path.insert(0, str(ROOT / "src"))
    from repro import jax_cache

    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    log(f"compile cache: {jax_cache.enable()}")
    (four_chips if args.four_chips else one_chip)(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
