"""Interpreter vs compiled-Pallas wall clock, next to the analytical model.

    PYTHONPATH=src python -m benchmarks.backend_compare [--quick]

For each GEMM the mapper picks its winning (mapping, layout) Plan once;
the same lowered Program then runs on both execution backends:

  interpreter  FEATHER+ functional machine, tile-by-tile MINISA replay
  pallas       one pl.pallas_call whose grid/BlockSpecs derive from the
               Program's tiling (interpret-mode on CPU, Mosaic on TPU)

Both outputs are checked against the einsum oracle before any number is
reported, and the analytical 5-engine cycle count for the identical tile
stream is printed alongside -- what the mapper's winning plan *costs* on
(real or interpret-mode) hardware vs what the model *predicts*.

The compiled backend is timed twice: cold (includes compile/trace time)
and warm (steady state, the number that matters for serving).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

QUICK_SIZES = ((256, 256, 256), (512, 512, 512))
FULL_SIZES = ((1024, 1024, 1024), (4096, 4096, 4096))


def compare_gemm(m: int, k: int, n: int, cfg=None, seed: int = 0) -> dict:
    """Search, lower once, execute on both backends, report wall clocks."""
    from repro import backends
    from repro.configs.feather import feather_config
    from repro.core import mapper

    cfg = cfg or feather_config(16, 256)
    g = mapper.Gemm(m=m, k=k, n=n, name=f"gemm-{m}x{k}x{n}")
    plan = mapper.search(g, cfg)
    prog = plan.program
    rng = np.random.default_rng(seed)
    i = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32)
    oracle = i.astype(np.float64) @ w.astype(np.float64)
    tol = 1e-3 + 1e-5 * k

    def _timed(backend_name):
        be = backends.get_backend(backend_name, cfg)
        t0 = time.perf_counter()
        out = be.run_program(prog, {"I": i, "W": w})[prog.out_name]
        cold = (time.perf_counter() - t0) * 1e6
        np.testing.assert_allclose(np.asarray(out, np.float64), oracle,
                                   rtol=tol, atol=tol,
                                   err_msg=f"{backend_name} diverged")
        t0 = time.perf_counter()
        be.run_program(prog, {"I": i, "W": w})
        warm = (time.perf_counter() - t0) * 1e6
        return cold, warm

    us_pl_cold, us_pl_warm = _timed("pallas")
    us_it_cold, us_it_warm = _timed("interpreter")
    comp = backends.compile_program(prog)
    return {
        "name": g.name,
        "m": m, "k": k, "n": n, "macs": g.macs,
        "df": plan.choice.df.name,
        "tile": [prog.n_m, prog.n_n, prog.n_k],
        "kernel_grid": list(comp.grid),
        "kernel_blocks": [comp.bm, comp.bk, comp.bn],
        "us_interpreter": us_it_warm,
        "us_interpreter_cold": us_it_cold,
        "us_pallas": us_pl_warm,
        "us_pallas_cold": us_pl_cold,
        "wallclock_speedup": us_it_warm / max(us_pl_warm, 1e-9),
        "cycles_minisa": plan.perf_minisa.cycles,
        "cycles_micro": plan.perf_micro.cycles,
    }


def run(quick: bool = False, sizes=None) -> dict[str, dict]:
    sizes = sizes if sizes is not None else (QUICK_SIZES if quick
                                             else QUICK_SIZES + FULL_SIZES)
    print(f"{'gemm':>20} {'grid':>12} {'interp us':>12} {'pallas us':>12} "
          f"{'speedup':>8} {'model cyc':>12}")
    out = {}
    for m, k, n in sizes:
        row = compare_gemm(m, k, n)
        out[row["name"]] = row
        print(f"{row['name']:>20} {str(tuple(row['kernel_grid'])):>12} "
              f"{row['us_interpreter']:12.0f} {row['us_pallas']:12.0f} "
              f"{row['wallclock_speedup']:8.1f} "
              f"{row['cycles_minisa']:12.3g}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes only (CI)")
    ap.add_argument("--size", type=int, nargs="*", default=None,
                    help="cubic GEMM sizes, e.g. --size 1024 4096")
    args = ap.parse_args()
    sizes = ([(s, s, s) for s in args.size] if args.size
             else None if not args.quick else QUICK_SIZES)
    run(quick=args.quick, sizes=sizes)


if __name__ == "__main__":
    from repro import jax_cache
    jax_cache.enable()
    main()
