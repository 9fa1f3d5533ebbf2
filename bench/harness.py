"""One run of one cell: set-up, the measured window, the check.

``run.py`` is the command; this module is the run, so that a test can
drive it without a chip.  What it prints before the result line:
set-up phases, compiles inside the window (should be 0), the backlog,
``peak_bytes_in_use`` and, on a traced run, what
``Scheduler.run(max_ticks=1)`` costs outside its tick spans.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import pathlib
import shutil
import sys
import time

from bench import adapter, cell, correctness, stats
from bench.traffic.generate import generate

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench_out" / "trace"
#: sampled calls of each kind whose answers are checked
SAMPLED_CALLS = {"prefill": 2, "decode": 8}


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    return {"name": name, "chips": w["chips"], "config": config, "mix": mix}


class CompileCounter:
    """Counts JAX lowerings and backend compiles while ``open``."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax
        self.open = False
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.open and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


class Window:
    """Drives the scheduler for ``seconds``, one tick at a time, and
    notes on its own clock when each output token becomes visible.  A
    session that finishes is replaced at once by the next of the plan's
    replacements (a closed loop)."""

    def __init__(self, sched, plan, tracing: bool):
        self.sched = sched
        self.logs: dict[int, stats.RequestLog] = {}
        self.plan = plan
        self.replaced: set[int] = set()
        self.ticks: list[tuple[float, float]] = []
        self.tracing = tracing
        self.attempted = 0

    def annotate(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, spec):
        req = self.sched.submit(decode_steps=spec.output_tokens,
                                seed=spec.seed,
                                prompt_tokens=spec.prompt_tokens)
        self.logs[req.rid] = stats.RequestLog(req.rid, spec.output_tokens)
        self.attempted += 1
        return req

    def observe(self, report, now):
        retired = {r.rid for r in report.requests}
        for rid, (tokens, status) in adapter.progress(self.sched,
                                                      report).items():
            rec = self.logs.get(rid)
            if rec is None:
                continue
            rec.token_s += [now] * (tokens - len(rec.token_s))
            rec.status = status
            if rid in retired and rid not in self.replaced:
                self.replaced.add(rid)
                self.submit(self.plan.replacement(len(self.replaced) - 1))

    def run(self, seconds: float):
        clock = time.perf_counter
        self.t0 = clock()
        end = self.t0 + seconds
        with self.annotate("bench.window"):
            while clock() < end and adapter.has_work(self.sched):
                a = clock()
                with self.annotate("bench.tick"):
                    report = self.sched.run(max_ticks=1)
                b = clock()
                self.ticks.append((a, b))
                self.observe(report, b)
        self.t_end = clock()


def prepare(spec: dict, seed: int, trace: bool, t_start: float):
    """Set-up: the cell built from the seed, every shape it reaches
    warmed, the sessions admitted.  Returns the scheduler, the traffic
    plan, the window and the (unarmed) recorder, which has seen every
    request from its admission on."""
    config, mix = spec["config"], spec["mix"]
    sched = cell.build(config, mix, seed)
    log(f"set-up: built, {time.perf_counter() - t_start:.3f} s")
    warmed = cell.warm_up(sched, mix)
    log(f"set-up: warmed decode batches {warmed}, "
        f"{time.perf_counter() - t_start:.3f} s")
    plan = generate(mix, seed)
    recorder = adapter.Recorder(sched, seed, SAMPLED_CALLS,
                                correctness.served_lead(config, mix))
    window = Window(sched, plan, trace)
    for s in plan.sessions:
        window.submit(s)
    while adapter.backlog(sched):
        sched.run(max_ticks=1)
    return sched, plan, window, recorder


def sampled_calls(recorder) -> tuple[dict, dict]:
    """The recorded calls, decode ticks mapped to their steps, and every
    request's served outputs; drops the recorder's hold on the
    scheduler."""
    calls = recorder.kept
    for c in calls["decode"]:
        c.step_rows = adapter.decode_step_rows(recorder.sched.decode, c)
    recorder.sched = None
    return calls, recorder.served


def run(spec: dict, bench: dict, *, seed: int, seconds: float,
        trace: bool, devices, t_start: float) -> tuple[dict, dict]:
    """One run of the cell ``spec`` (see :func:`load_cell`); returns the
    result line and the numbers compared with their limits."""
    import jax

    config, mix = spec["config"], spec["mix"]
    compiles = CompileCounter()
    sched, plan, window, recorder = prepare(spec, seed, trace, t_start)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {len(plan.sessions)} sessions admitted; setup_s "
        f"{setup_s:.3f}")

    tracer = None
    if trace:
        from repro.obs.trace import trace as tracer
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer.clear()
        tracer.enable()
        # no Python tracer: it would slow every host step it watches
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            window.anchor_perf = time.perf_counter()
            tracer.instant("bench.anchor")
    recorder.armed = True
    compiles.open = True
    window.run(seconds)
    compiles.open = False
    recorder.armed = False
    if trace:
        jax.profiler.stop_trace()
        tracer.disable()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"window: {window.t_end - window.t0:.3f} s, {len(window.ticks)} "
        f"ticks, {window.attempted} requests submitted, backlog "
        f"{adapter.backlog(sched)} at the close")
    log(f"window: compiles inside: {dict(compiles.counts) or 0} "
        f"(lowered/compiled events)")
    log(f"device: peak_bytes_in_use {peak}")

    logs = list(window.logs.values())
    failed = sum(1 for r in logs if r.status != "ok")
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": window.attempted,
              "failed": failed}
    if trace:
        from bench import layers
        values, breakdown, extra = layers.measure(
            bench, sched, window, tracer.events(), devices, TRACE_DIR)
        result["metrics"] = _metrics(bench["per_layer"], spec["name"],
                                     values)
        device.update(extra)
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        values = stats.end_to_end(logs, window.t0, window.t_end)
        values["setup_s"] = setup_s
        result["metrics"] = _metrics(bench["end_to_end"], spec["name"],
                                     values)
        result["device"] = device

    # the check: program state freed first, then the reference
    calls, served = sampled_calls(recorder)
    del recorder, sched, window
    gc.collect()
    numbers = correctness.readings(config, calls, served, weight_seed=seed,
                                   kv_extent=mix["kv_extent"])
    limits = correctness.required(config["correct_limits"], calls)
    result["correct"] = correctness.verdict(numbers, limits)
    compared = {k: {"value": numbers.get(k), "limit": v}
                for k, v in limits.items()}
    result["compared"] = compared
    return result, compared


def _metrics(entries, cell_name, values) -> dict:
    out = {}
    for m in entries:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        if values.get(m["name"]) is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def print_result(result: dict, compared: dict) -> None:
    for name, c in compared.items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
