"""The readings that the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

In one process (set-up is long, and one process holds the chip), for
each seed: the cell is set up as a run sets it up, served for
``--seconds`` at its own load, and the window's sampled answers are read
twice: the program against the reference, and the control (the
reference computed in three bf16 passes, ``Precision.HIGH``) in the
program's place against the same reference.  One JSON line per seed,
then the largest program reading and the smallest control reading of
each number.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import correctness, harness
    from bench.run import require_devices

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = harness.load_cell(bench, args.workload)
    require_devices(spec["chips"])
    from repro import jax_cache
    jax_cache.enable()
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        sched, _, window, rec = harness.prepare(spec, seed, False, t0)
        rec.armed = True
        window.run(args.seconds)
        rec.armed = False
        calls, served = harness.sampled_calls(rec)
        del sched, window, rec
        gc.collect()
        prog, ctrl = correctness.readings(
            spec["config"], calls, served, weight_seed=seed,
            kv_extent=spec["mix"]["kv_extent"], control=True)
        line = {"seed": seed, "program": prog, "control": ctrl,
                "sampled": {k: len(v) for k, v in calls.items()},
                "seconds": time.perf_counter() - t0}
        lines.append(line)
        print(json.dumps(line), flush=True)
    names = sorted({k for ln in lines for k in ln["program"]})
    print(json.dumps({
        "program_max": {k: max(ln["program"][k] for ln in lines
                               if k in ln["program"]) for k in names},
        "control_min": {k: min(ln["control"][k] for ln in lines
                               if k in ln["control"]) for k in names}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
