import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production meshes and extract memory/cost/collective analyses for the
# roofline (EXPERIMENTS.md §Dry-run / §Roofline).
#
# MUST be run as its own process (jax locks the device count on first init):
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
#       --shape train_4k [--multi-pod] [--all] [--json out.json]
#
# (The XLA_FLAGS lines above must stay the first statements in the file,
# which is why this header is a comment rather than a docstring.)

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, get_config, get_shape
from repro.dist import sharding as shlib
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.train import optimizer as optlib
from repro.train.trainer import TrainConfig, make_train_step, shardings_for

# TPU v5e constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s /link /chip (~)

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2,
}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-operand bytes of every collective op in the HLO."""
    out: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind, dtype, dims = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:
                    numel *= int(d)
        out[kind] = out.get(kind, 0.0) + numel * nbytes
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg)
    spec = {"batch": model.batch_spec(shape)}
    if shape.kind == "decode":
        spec["cache"] = model.cache_spec(shape.global_batch, shape.seq_len)
    return spec


def _abstract_like(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def lower_cell(arch: str, shape_name: str, mesh, verbose: bool = True):
    """Lower + compile one cell; returns the result record."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name, "status":
                "SKIP(full-attention)"}
    model = build_model(cfg)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tstep = make_train_step(model, TrainConfig())
            batch_spec = model.batch_spec(shape)
            (p_sh, o_sh, b_sh), (p_shapes, o_shapes) = shardings_for(
                model, mesh, batch_spec)
            fn = jax.jit(tstep, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_shapes, o_shapes, batch_spec)
        else:
            batch_spec = model.batch_spec(shape)
            params_axes = model.axes()
            p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            # serving: replicate weights over 'data' (no FSDP re-gathers)
            p_sh = shlib.tree_shardings(params_axes, p_shapes, mesh,
                                        inference=True)
            b_sh = shlib.batch_sharding(mesh, batch_spec)
            if shape.kind == "prefill":
                def prefill_fn(params, batch):
                    return model.prefill(
                        params, batch["tokens"],
                        prefix_embeds=batch.get("patches"),
                        frames=batch.get("frames"))
                cache_shapes = jax.eval_shape(
                    prefill_fn, p_shapes, batch_spec)[1]
                c_sh = shlib.tree_shardings(
                    model.cache_axes(), cache_shapes, mesh)
                fn = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh),
                             out_shardings=(None, c_sh))
                lowered = fn.lower(p_shapes, batch_spec)
            else:  # decode
                cache_spec = model.cache_spec(shape.global_batch,
                                              shape.seq_len)
                c_sh = shlib.tree_shardings(
                    model.cache_axes(), cache_spec, mesh)

                def decode_fn(params, tokens, cache, position):
                    return model.decode_step(params, tokens, cache, position)

                fn = jax.jit(decode_fn,
                             in_shardings=(p_sh, b_sh["tokens"], c_sh,
                                           b_sh["position"]),
                             out_shardings=(None, c_sh),
                             donate_argnums=(2,))
                lowered = fn.lower(
                    p_shapes, batch_spec["tokens"], cache_spec,
                    batch_spec["position"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    n_dev = mesh.devices.size
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # cost_analysis reports the PARTITIONED module, i.e. per-device values
    # (verified against a sharded matmul: flops scale as 1/n_dev).  The HLO
    # text is likewise one device's program, so parsed collective operand
    # sizes are per-device shard bytes.
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    hlo_text = compiled.as_text()
    coll_raw = collective_bytes(hlo_text)
    # Trip-count-aware structural analysis: cost_analysis counts while
    # (lax.scan) bodies ONCE, so scanned-layer models undercount by the
    # layer count -- hlo_analysis re-derives dot FLOPs, a memory-traffic
    # proxy, and collective bytes with loop multipliers applied.
    from repro.launch import hlo_analysis
    struct = hlo_analysis.analyze(hlo_text)
    c_flops = max(flops, struct["dot_flops"])
    c_bytes = max(bytes_accessed, struct["tensor_bytes"])
    c_coll = max(coll_raw["total"], struct["collective_bytes"])

    rec = {
        "arch": arch, "shape": shape_name, "status": "OK",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # memory_analysis is per-device on the host backend
        "bytes_per_device": {
            "argument": getattr(mem, "argument_size_in_bytes", None),
            "output": getattr(mem, "output_size_in_bytes", None),
            "temp": getattr(mem, "temp_size_in_bytes", None),
            "peak": getattr(mem, "peak_memory_in_bytes", None),
        },
        # raw cost_analysis (single loop iteration) -- kept for reference
        "hlo_flops_raw": flops,
        "hlo_bytes_raw": bytes_accessed,
        "collective_bytes_raw": coll_raw,
        # loop-corrected per-device quantities (primary)
        "hlo_flops_per_device": c_flops,
        "hlo_flops_global": c_flops * n_dev,
        "hlo_bytes_per_device": c_bytes,
        "collective_bytes_per_device": {**struct["collectives"],
                                        "total": c_coll},
        "while_trips": struct["while_trips"][:8],
        # roofline terms, seconds per executed step (per-chip quantities
        # over per-chip bandwidths == mesh-level step time bounds)
        "t_compute": c_flops / PEAK_FLOPS,
        "t_memory": c_bytes / HBM_BW,
        "t_collective": c_coll / ICI_BW,
    }
    terms = {k: rec[k] for k in ("t_compute", "t_memory", "t_collective")}
    rec["bottleneck"] = max(terms, key=terms.get)
    if verbose:
        print(json.dumps(rec, indent=1, default=str))
        sys.stdout.flush()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--json", help="append records to this JSONL file")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    cells = ([(a, s) for a in ARCH_IDS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    records = []
    for arch, shape in cells:
        try:
            rec = lower_cell(arch, shape, mesh)
        except Exception as e:  # noqa: BLE001 - report, keep sweeping
            rec = {"arch": arch, "shape": shape, "status":
                   f"FAIL {type(e).__name__}: {e}"}
            print(json.dumps(rec), file=sys.stderr)
        records.append(rec)
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec, default=str) + "\n")
    ok = sum(1 for r in records if r.get("status") == "OK")
    skip = sum(1 for r in records
               if str(r.get("status", "")).startswith("SKIP"))
    print(f"\n== dry-run: {ok} OK, {skip} SKIP, "
          f"{len(records) - ok - skip} FAIL / {len(records)} cells "
          f"on mesh {mesh.devices.shape} ==")
    return 0 if ok + skip == len(records) else 1


if __name__ == "__main__":
    sys.exit(main())
