"""Observability spine: tracer semantics, the metrics registry, the
exporters, and the no-perturbation guarantee -- a traced serving run's
per-request ``state_checksum``s are bit-identical to an untraced one on
both backends, and the disabled-mode instrumentation overhead is bounded
against a measured decode tick."""

import json
import threading
import time

import pytest

from repro.configs.feather import feather_config
from repro.obs import export, metrics
from repro.obs.metrics import Registry
from repro.obs.trace import NULL_SPAN, Tracer, trace
from repro.runtime import ModelExecutable, ProgramCache, Scheduler

CFG = feather_config(4, 16)

#: mixed decode lengths + one chunked prompt: retire-mid-batch and
#: multi-tick prefill both appear in the trace
SUBMISSIONS = [(3, None), (1, None), (2, 64)]


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _scheduler(backend, **kw):
    cache = ProgramCache()
    prefill = ModelExecutable.for_cell("gemma-7b", "prefill_tiny", CFG,
                                       cache=cache)
    decode = ModelExecutable.for_cell("gemma-7b", "decode_tiny", CFG,
                                      cache=cache)
    sched = Scheduler(prefill, decode, backend=backend,
                      max_concurrent=3, seed=0, **kw)
    for steps, prompt in SUBMISSIONS:
        sched.submit(decode_steps=steps, prompt_tokens=prompt)
    return sched


def _serve(backend, **kw):
    return _scheduler(backend, **kw).run()


def _checksums(rep):
    return [r.state_checksum for r in rep.requests]


@pytest.fixture(scope="module")
def traced_serving():
    """One traced batched-pallas serving run: (report, events, metrics
    snapshot) -- shared by the exporter/timeline/overhead tests."""
    metrics.reset()
    trace.clear()
    trace.enable()
    try:
        rep = _serve("pallas")
    finally:
        trace.disable()
    events = trace.events()
    snap = metrics.snapshot()
    trace.clear()
    return rep, events, snap


# ---------------------------------------------------------------------------
# Tracer semantics
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_null_singleton():
    t = Tracer()
    sp = t.span("x", a=1)
    assert sp is NULL_SPAN and not sp
    with sp as inner:
        inner.set(b=2)
    t.instant("marker")
    t.record("r", ("host", "x"), 0.0, 1.0)
    assert t.events() == []


def test_nesting_depth_and_track_inheritance():
    t = Tracer()
    t.enable()
    with t.span("outer"):
        with t.span("mid", ("request", 7)):
            with t.span("inner"):
                pass
    evs = {e.name: e for e in t.events()}
    assert evs["outer"].depth == 0
    assert evs["mid"].depth == 1
    assert evs["inner"].depth == 2
    # inner completes first (exit order), outer last
    assert [e.name for e in t.events()] == ["inner", "mid", "outer"]
    assert [e.seq for e in t.events()] == [0, 1, 2]
    # explicit track pins; children inherit the enclosing lane
    assert evs["outer"].track[0] == "host"
    assert evs["mid"].track == ("request", 7)
    assert evs["inner"].track == ("request", 7)
    # timing sanity: containment
    assert evs["outer"].t0_s <= evs["inner"].t0_s
    assert evs["inner"].t1_s <= evs["outer"].t1_s + 1e-9


def test_span_set_attrs_and_instants_and_record():
    t = Tracer()
    t.enable()
    with t.span("work", n=3) as sp:
        sp.set(launches=5)
    t.instant("mark", ("request", 0), rid=0)
    t.record("window", ("request", 0), 10.0, 10.5, step=1)
    work, mark, window = t.events()
    assert work.attrs == {"n": 3, "launches": 5}
    assert mark.instant and mark.dur_s == 0.0
    assert window.dur_s == pytest.approx(0.5)
    assert not window.instant


def test_threads_get_separate_lanes():
    t = Tracer()
    t.enable()

    def worker():
        with t.span("w"):
            pass

    th = threading.Thread(target=worker, name="side")
    with t.span("main_side"):
        th.start()
        th.join()
    tracks = {e.name: e.track for e in t.events()}
    assert tracks["w"] == ("host", "side")
    assert tracks["w"] != tracks["main_side"]


# ---------------------------------------------------------------------------
# Determinism: seeded scheduler -> identical span key sequences
# ---------------------------------------------------------------------------

def test_span_keys_deterministic_across_seeded_runs():
    """Two identically-seeded serving runs must emit the identical
    (name, track, depth) sequence -- the timing-free trace identity."""
    keys = []
    for _ in range(2):
        trace.clear()
        trace.enable()
        try:
            _serve("interpreter", batch_decode=False, use_fused=False)
        finally:
            trace.disable()
        keys.append(trace.keys())
    assert keys[0] == keys[1]
    assert len(keys[0]) > 0


# ---------------------------------------------------------------------------
# No perturbation: checksums identical tracing on vs off, both backends
# ---------------------------------------------------------------------------

def test_tracing_does_not_perturb_interpreter_serving():
    ref = _checksums(_serve("interpreter", batch_decode=False,
                            use_fused=False))
    trace.clear()
    trace.enable()
    try:
        traced = _checksums(_serve("interpreter", batch_decode=False,
                                   use_fused=False))
    finally:
        trace.disable()
    assert traced == ref


def test_tracing_does_not_perturb_pallas_serving(traced_serving):
    rep, _, _ = traced_serving
    assert _checksums(_serve("pallas")) == _checksums(rep)


# ---------------------------------------------------------------------------
# Chrome trace export: schema + per-request swimlanes
# ---------------------------------------------------------------------------

def test_chrome_trace_schema(tmp_path, traced_serving):
    _, events, _ = traced_serving
    path = export.write_chrome_trace(str(tmp_path / "trace.json"), events)
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert evs, "traced serving produced no events"
    for rec in evs:
        assert rec["ph"] in ("X", "i", "M")
        if rec["ph"] == "M":
            assert rec["name"] in ("process_name", "thread_name")
            assert "name" in rec["args"]
        else:
            assert isinstance(rec["pid"], int)
            assert isinstance(rec["tid"], int)
            assert rec["ts"] >= 0
        if rec["ph"] == "X":
            assert rec["dur"] >= 0
        # args must be JSON-clean scalars/lists (Perfetto requirement)
        for v in rec.get("args", {}).values():
            assert isinstance(v, (str, int, float, bool, list)) or v is None


def test_chrome_trace_request_swimlanes(traced_serving):
    rep, events, _ = traced_serving
    doc = export.chrome_trace(events)
    evs = doc["traceEvents"]
    procs = {r["pid"]: r["args"]["name"] for r in evs
             if r["ph"] == "M" and r["name"] == "process_name"}
    assert "request" in procs.values() and "host" in procs.values()
    req_pid = next(p for p, n in procs.items() if n == "request")
    lanes = {r["tid"] for r in evs
             if r["ph"] == "M" and r["name"] == "thread_name"
             and r["pid"] == req_pid}
    assert len(lanes) == len(rep.requests)    # one swimlane per request
    # every request lane carries the full lifecycle
    by_name = {}
    for r in evs:
        if r["ph"] in ("X", "i") and r["pid"] == req_pid:
            by_name.setdefault(r["tid"], set()).add(r["name"])
    for lane_names in by_name.values():
        assert {"submit", "first_token", "retire",
                "decode_step", "request"} <= lane_names


def test_timeline_joins_spans_to_requests(traced_serving):
    rep, events, _ = traced_serving
    tl = rep.timeline(events)
    assert [t["rid"] for t in tl] == [r.rid for r in rep.requests]
    for entry, r in zip(tl, rep.requests):
        assert entry["state_checksum"] == r.state_checksum
        names = [s["name"] for s in entry["spans"]]
        assert "submit" in names and "retire" in names
        assert names.index("submit") < names.index("retire")
        assert any(n == "decode_step" for n in names)
        # spans are in time order
        t0s = [s["t0_s"] for s in entry["spans"]]
        assert t0s == sorted(t0s)
    # tracing off -> empty swimlanes, not an error
    assert all(t["spans"] == [] for t in rep.timeline([]))


def test_span_breakdown_decode_tick(traced_serving):
    rep, events, _ = traced_serving
    bd = export.span_breakdown("decode_tick", {"launch"}, events)
    assert bd["n_parents"] == rep.decode_ticks
    assert bd["n_children"] > 0
    assert 0.0 < bd["child_frac"] <= 1.0
    assert bd["child_frac"] + bd["host_frac"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Inside the decode tick: parent links, uploads, the KV gather
# ---------------------------------------------------------------------------

def _by_tick(events, name):
    """decode_tick id -> the ``name`` spans below it (through parents)."""
    by_id = {e.id: e for e in events}
    out = {e.id: [] for e in events if e.name == "decode_tick"}
    for e in events:
        if e.name != name:
            continue
        p = e.parent
        while p is not None and by_id[p].name != "decode_tick":
            p = by_id[p].parent
        if p is not None:
            out[p].append(e)
    return out


def _plan_uploads(decode, n):
    """(operand, bytes) of each upload one batched tick of ``n`` requests
    makes before any weight is resident, from the batch plan's shapes:
    each launch's fp32 input and weight (K^T and V for attention); a
    chained layer's input is its producer's output."""
    ups = []
    for seg in decode.batch_plan(n).segments:
        if seg.kind == "perreq":
            for s in [decode.steps[i] for i in seg.indices] * n:
                g = s.program.gemm
                ups += [("input", g.m * g.k), (s.weight_kind, g.k * g.n)]
        elif seg.kind == "attention":
            qk, pv = (p.gemm for p in seg.programs)
            ups += [("input", n * qk.m * qk.k), ("kv", n * qk.k * qk.n),
                    ("kv", n * pv.k * pv.n)]
        elif seg.fused is not None:
            gs = [p.gemm for p in seg.fused.programs]
            ups += [("input", gs[0].m * gs[0].k)]
            ups += [("weight", g.k * g.n) for g in gs]
        else:
            for p in seg.programs:
                ups += [("input", p.gemm.m * p.gemm.k),
                        ("weight", p.gemm.k * p.gemm.n)]
    return [(op, 4 * n_values) for op, n_values in ups]


def test_span_parents_are_enclosing_spans(traced_serving):
    _, events, _ = traced_serving
    by_id = {e.id: e for e in events}
    assert len(by_id) == len(events)
    children = [e for e in events if e.parent is not None]
    assert children
    for e in children:
        p = by_id[e.parent]
        assert not p.instant
        assert p.t0_s <= e.t0_s + 1e-9 and e.t1_s <= p.t1_s + 1e-9, \
            (e.name, p.name)
    names = {(by_id[e.parent].name, e.name) for e in children}
    assert {("decode_tick", "decode_step"), ("launch", "backend.put"),
            ("launch", "backend.wait"), ("launch", "backend.fetch"),
            ("scheduler.run", "scheduler.report")} <= names


def test_decode_tick_bytes_are_its_puts_and_the_plans(traced_serving):
    """Every tick uploads what its ``backend.put``s say; the first tick
    uploads the plan's every operand, and each later one only its inputs
    and K/V, its weights already resident (one hit each)."""
    _, events, _ = traced_serving
    decode = ModelExecutable.for_cell("gemma-7b", "decode_tiny", CFG,
                                      cache=ProgramCache())
    puts = _by_tick(events, "backend.put")
    fetches = _by_tick(events, "backend.fetch")
    ticks = [e for e in events if e.name == "decode_tick"]
    assert len(ticks) > 1
    for i, t in enumerate(ticks):
        plan = _plan_uploads(decode, t.attrs["n_ready"])
        n_weights = sum(op == "weight" for op, _ in plan)
        assert t.attrs["h2d_bytes"] == sum(e.attrs["bytes"]
                                           for e in puts[t.id])
        assert t.attrs["d2h_bytes"] == sum(e.attrs["bytes"]
                                           for e in fetches[t.id]) > 0
        operands = {e.attrs["operand"] for e in puts[t.id]}
        if i == 0:
            assert t.attrs["h2d_bytes"] == sum(b for _, b in plan)
            assert t.attrs["weight_hits"] == 0
            assert operands == {"input", "weight", "kv"}
        else:
            assert t.attrs["h2d_bytes"] == \
                sum(b for op, b in plan if op != "weight")
            assert t.attrs["weight_hits"] == n_weights > 0
            assert operands == {"input", "kv"}


def test_each_tick_gathers_kv_once_per_ready_request(traced_serving):
    _, events, _ = traced_serving
    gathers = _by_tick(events, "kv.gather")
    for t in (e for e in events if e.name == "decode_tick"):
        rids = [e.attrs["rid"] for e in gathers[t.id]]
        assert len(rids) == len(set(rids)) == t.attrs["n_ready"]
        assert all(e.attrs["bytes"] > 0 for e in gathers[t.id])


def test_repeated_short_runs_count_each_request_once():
    """``run(max_ticks=1)`` until every request retires: the counters
    count each retired request once, as one ``run()`` does."""
    metrics.reset()
    sched = _scheduler("interpreter")
    calls = 0
    rep = None
    while rep is None or len(rep.requests) < len(SUBMISSIONS):
        rep = sched.run(max_ticks=1)
        calls += 1
    assert calls > 1
    snap = metrics.snapshot()
    label = '{backend="interpreter"}'
    assert snap["tokens_total"][label] == rep.total_tokens
    assert snap["requests_total"][label] == len(SUBMISSIONS)
    assert snap["minisa_bytes_total"][label] == \
        pytest.approx(sum(r.minisa_bytes for r in rep.requests))
    assert snap["micro_bytes_total"][label] == \
        pytest.approx(sum(r.micro_bytes for r in rep.requests))
    assert rep.total_tokens == _serve("interpreter").total_tokens


# ---------------------------------------------------------------------------
# Disabled-mode overhead: bounded against a measured decode tick
# ---------------------------------------------------------------------------

def test_disabled_overhead_under_two_percent_of_decode_tick(traced_serving):
    """events-per-tick x measured disabled per-call cost must stay under
    2% of the measured decode-tick wall clock (robust formulation: no
    differencing of two noisy end-to-end timings)."""
    rep, events, _ = traced_serving
    ticks = [e for e in events if e.name == "decode_tick"]
    assert ticks
    mean_tick_s = sum(e.dur_s for e in ticks) / len(ticks)
    # spans emitted inside one tick window, averaged
    per_tick = sum(
        1 for e in events
        if any(t.t0_s <= e.t0_s and e.t1_s <= t.t1_s + 1e-9
               for t in ticks)) / len(ticks)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("x", a=1):
            pass
    per_call_s = (time.perf_counter() - t0) / n
    overhead = per_tick * per_call_s
    assert overhead < 0.02 * mean_tick_s, (
        f"disabled tracing overhead {overhead * 1e6:.1f}us/tick vs "
        f"tick {mean_tick_s * 1e6:.1f}us ({per_tick:.0f} spans/tick at "
        f"{per_call_s * 1e9:.0f}ns/span)")


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_labels():
    reg = Registry()
    c = reg.counter("events_total", "help text")
    c.inc(1, tier="plan", kind="hit")
    c.inc(2, tier="plan", kind="hit")
    c.inc(5, tier="plan", kind="miss")
    c.inc(7)
    assert c.value(tier="plan", kind="hit") == 3
    assert c.value(tier="plan", kind="miss") == 5
    assert c.value() == 7
    g = reg.gauge("depth")
    g.set(4, pool="kv")
    g.set(2, pool="kv")
    assert g.value(pool="kv") == 2
    g.high(9, pool="kv")
    g.high(3, pool="kv")
    assert g.value(pool="kv") == 9


def test_registry_kind_mismatch_raises():
    reg = Registry()
    reg.counter("x_total")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")


def test_set_many_skips_non_numeric():
    reg = Registry()
    reg.set_many({"a": 1, "b": 2.5, "flag": True, "name": "str",
                  "lst": [1, 2]}, prefix="p_")
    snap = reg.snapshot()
    assert snap["p_a"][""] == 1.0 and snap["p_b"][""] == 2.5
    assert "p_flag" not in snap and "p_name" not in snap
    assert "p_lst" not in snap


def test_prometheus_rendering_deterministic():
    reg = Registry()
    reg.counter("b_total", "bees").inc(2, kind="x")
    reg.gauge("a_gauge").set(1.5)
    text = reg.render_prometheus()
    assert text == reg.render_prometheus()
    lines = text.strip().splitlines()
    assert lines[0] == "# TYPE a_gauge gauge"
    assert "a_gauge 1.5" in lines
    assert "# HELP b_total bees" in lines
    assert "# TYPE b_total counter" in lines
    assert 'b_total{kind="x"} 2' in lines


def test_reset_keeps_registered_handles():
    reg = Registry()
    handle = reg.counter("launches_total")
    handle.inc(3)
    reg.reset()
    assert handle.value() == 0
    handle.inc(1)    # module-level handles must stay attached
    assert reg.snapshot()["launches_total"][""] == 1.0


# ---------------------------------------------------------------------------
# Scheduler -> registry bridge + report surfaces
# ---------------------------------------------------------------------------

def test_serving_publishes_unified_metrics(traced_serving):
    rep, _, snap = traced_serving
    # MINISA vs micro instruction byte counters, labelled by backend
    assert snap["minisa_bytes_total"]['{backend="pallas"}'] == \
        pytest.approx(sum(r.minisa_bytes for r in rep.requests))
    assert snap["micro_bytes_total"]['{backend="pallas"}'] == \
        pytest.approx(sum(r.micro_bytes for r in rep.requests))
    # per-kernel launch counter sums to the scheduler's launch count
    assert sum(snap["backend_launches_total"].values()) >= \
        rep.decode_launches
    # cache tiers (disk stats included) and KV pool stats
    assert snap["cache_hits"]['{tier="plan"}'] >= 0
    assert "cache_disk_bytes" in snap and "cache_disk_evictions" in snap
    assert snap["kv_high_water_pages"][""] == \
        rep.kv["high_water_pages"]
    assert snap["kv_admit_stalls"][""] == rep.kv["admit_stalls"]
    # scheduler summary gauges
    assert snap["sched_tokens_per_sec"][""] > 0
    assert snap["sched_latency_p99_s"][""] > 0


def test_report_to_dict_carries_cache_disk_and_kv(traced_serving):
    rep, _, _ = traced_serving
    d = rep.to_dict()
    assert len(d["requests"]) == len(rep.requests)
    assert "disk_bytes" in d["cache"] and "disk_evictions" in d["cache"]
    assert "admit_stalls" in d["kv"] and "high_water_pages" in d["kv"]
    assert d["latency_p99_s"] == rep.summary()["latency_p99_s"]


def test_latency_and_ttft_percentile_sets(traced_serving):
    """The report carries the full p50/p95/p99 set for both end-to-end
    latency and TTFT, ordered and bounded by the observed walls."""
    rep, _, _ = traced_serving
    s = rep.summary()
    walls = [r.wall_s for r in rep.requests]
    ttfts = [r.ttft_s for r in rep.requests]
    for prefix, vals in (("latency", walls), ("ttft", ttfts)):
        p50, p95, p99 = (s[f"{prefix}_p50_s"], s[f"{prefix}_p95_s"],
                         s[f"{prefix}_p99_s"])
        assert 0.0 < p50 <= p95 <= p99
        assert p99 <= max(vals) + 1e-9
        assert min(vals) - 1e-9 <= p50
