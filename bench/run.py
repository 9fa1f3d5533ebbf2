"""The benchmark command: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
under ``bench/configs`` and a traffic mix under ``bench/traffic``.  The
run builds the served path at the configuration's widths, warms every
shape the mix reaches, measures for ``--seconds``, checks the window's
answers against the plain reference, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, ``breakdown`` when traced, and last ``compared``, each number
checked beside its limit (also the last lines on standard error).

It runs on the machine it is started on and exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def require_devices(chips: int):
    """The chips the cell runs on; exits when there is no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, peaks

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = harness.load_cell(bench, args.workload)
    devices = require_devices(spec["chips"])
    peaks.for_device(devices[0].device_kind)
    from repro import jax_cache
    harness.log(f"device: {devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}; compile cache {jax_cache.enable()}")
    result, compared = harness.run(spec, bench, seed=args.seed,
                                   seconds=args.seconds,
                                   trace=bool(args.trace), devices=devices,
                                   t_start=T_START)
    harness.print_result(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
