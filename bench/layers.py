"""The per-layer half of a traced run: the program's spans and counters
from the window, the profiler's device trace reduced, and each
per-layer metric's reader run over them."""

from __future__ import annotations

from bench import peaks, trace_reduce
from bench.metrics import Context, read


def measure(bench, model, sched, window, spans, devices, trace_dir):
    """(per-layer values by name, breakdown, device extras) of the
    traced window; a reader that finds nothing gives no value.  The
    launches and their work come from the configuration's ``model``
    module."""
    chip = peaks.for_device(devices[0].device_kind)
    tr = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
    win = next(e for e in tr.host if e.name == "bench.window")
    t0, t1 = win.start_ns, win.end_ns
    anchor = next(e for e in tr.host if e.name == "bench.anchor")
    obs_anchor = next(e for e in spans if e.name == "bench.anchor")
    offset = anchor.start_ns - obs_anchor.t0_s * 1e9

    busy = trace_reduce.busy_ns(tr, t0, t1) * 1e-9
    launches = model.launches(sched, spans)
    kernels = {k: trace_reduce.kernel_ns(tr, k, t0, t1) * 1e-9
               for k in launches}
    ctx = Context(spans=spans, window_s=window.t_end - window.t0,
                  trace_window_s=(t1 - t0) * 1e-9, busy_s=busy,
                  kernel_s=kernels, launches=launches,
                  peaks=chip, chips=len(devices))
    values = {m["name"]: read(m["name"], ctx) for m in bench["per_layer"]}

    host = [(e.name, e.start_ns, e.end_ns) for e in tr.host
            if e.name != "bench.anchor"]
    for e in spans:
        if e.instant:
            continue
        label = e.name + (f":{e.attrs['kernel']}" if "kernel" in e.attrs
                          else "")
        s = offset + e.t0_s * 1e9
        host.append((label, s, s + e.dur_s * 1e9))
    breakdown = {
        "device_ops": [[k, v] for k, v in trace_reduce.top_ops(tr, t0, t1)],
        "idle_gaps": [[k, v] for k, v in trace_reduce.label_gaps(
            trace_reduce.gaps(tr, t0, t1), host)],
    }
    _log_run_overhead(window, spans)
    return values, breakdown, {"busy_s": busy,
                                "window_s": (t1 - t0) * 1e-9}


def _log_run_overhead(window, spans) -> None:
    """Print what ``Scheduler.run(max_ticks=1)`` costs outside its
    ``decode_tick`` / ``prefill_phase`` spans, per call."""
    anchor = next(e for e in spans if e.name == "bench.anchor")
    shift = window.anchor_perf - anchor.t0_s     # span clock -> harness
    inner = [(shift + e.t0_s + 0.5 * e.dur_s, e.dur_s) for e in spans
             if not e.instant and e.name in ("decode_tick", "prefill_phase")]
    over = [(b - a) - sum(d for mid, d in inner if a <= mid <= b)
            for a, b in window.ticks]
    if over:
        print(f"traced: run(max_ticks=1) outside its tick spans: "
              f"{1e3 * sum(over) / len(over):.3f} ms per call over "
              f"{len(over)} calls", flush=True)
