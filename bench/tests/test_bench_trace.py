"""``trace_reduce`` against a small trace recorded on the chip
(``bench/data/trace_small.xplane.pb``, a short traced granite3moe
serving run with prefill passes and decode ticks): the busy union, per-kernel time and the idle
gaps, each recomputed here by a different route."""

import pathlib

import pytest

from bench import trace_reduce

TRACE = pathlib.Path(__file__).parents[1] / "data" / "trace_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(str(TRACE))


@pytest.fixture(scope="module")
def window(trace):
    win = next(e for e in trace.host if e.name == "bench.window")
    return win.start_ns, win.end_ns


def _sweep_busy(events, t0, t1):
    """Busy time by an event sweep: +1 at each start, -1 at each end."""
    marks = sorted([(max(e.start_ns, t0), 1) for e in events
                    if e.end_ns > t0 and e.start_ns < t1]
                   + [(min(e.end_ns, t1), -1) for e in events
                      if e.end_ns > t0 and e.start_ns < t1])
    busy, depth, last = 0.0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_the_recorded_trace_has_device_ops_and_host_marks(trace, window):
    assert len(trace.device_ops) == 1
    assert len(trace.ops()) > 10
    names = {e.name for e in trace.host}
    assert {"bench.window", "bench.anchor", "bench.tick"} <= names
    t0, t1 = window
    assert t1 - t0 > 1e9


def test_busy_union_matches_an_event_sweep(trace, window):
    t0, t1 = window
    busy = trace_reduce.busy_ns(trace, t0, t1)
    assert busy == pytest.approx(_sweep_busy(trace.ops(), t0, t1), rel=1e-9)
    assert 0 < busy < t1 - t0
    # overlapping ops count once: the union is at most the plain sum
    plain = sum(min(e.end_ns, t1) - max(e.start_ns, t0)
                for e in trace.ops() if e.end_ns > t0 and e.start_ns < t1)
    assert busy <= plain + 1e-6


def test_idle_gaps_and_busy_fill_the_window(trace, window):
    t0, t1 = window
    gaps = trace_reduce.gaps(trace, t0, t1)
    idle = sum(b - a for a, b in gaps)
    assert idle + trace_reduce.busy_ns(trace, t0, t1) == \
        pytest.approx(t1 - t0, rel=1e-9)
    lengths = [b - a for a, b in gaps]
    assert lengths == sorted(lengths, reverse=True)
    for a, b in gaps[:20]:       # no op runs inside a gap
        assert not any(e.start_ns < b and e.end_ns > a
                       for e in trace.ops())


def test_kernel_time_is_the_sum_of_its_ops(trace, window):
    t0, t1 = window
    for kernel in ("nest_gemm", "fused_chain"):
        mine = sum(min(e.end_ns, t1) - max(e.start_ns, t0)
                   for e in trace.ops()
                   if kernel in e.name and e.end_ns > t0 and e.start_ns < t1)
        assert trace_reduce.kernel_ns(trace, kernel, t0, t1) == \
            pytest.approx(mine)
    assert trace_reduce.kernel_ns(trace, "nest_gemm", t0, t1) > 0
    top = trace_reduce.top_ops(trace, t0, t1)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])


def test_gap_labels_take_the_innermost_host_interval():
    idle = [(10.0, 20.0), (40.0, 41.0)]
    host = [("bench.window", 0.0, 100.0), ("bench.tick", 5.0, 30.0),
            ("decode_tick", 12.0, 18.0)]
    got = trace_reduce.label_gaps(idle, host)
    assert [g[0] for g in got] == ["decode_tick", "bench.window"]
    assert [g[1] for g in got] == pytest.approx([10e-9, 1e-9])
    (label, _), = trace_reduce.label_gaps([(200.0, 300.0)], host)
    assert label == "untraced host"


def test_ops_are_named_by_hlo_op_inside_their_module(trace):
    names = {(e.module, e.name) for e in trace.ops()}
    assert ("jit_nest_gemm", "nest_gemm") in names
    assert ("jit__fused_chain_jit", "_fused_chain_jit") in names
    assert all(e.module for e in trace.ops())
    assert trace_reduce.op_name(
        "%copy.3 = f32[8,128]{1,0} copy(f32[8,128]{0,1} %x)") == "copy"
    assert trace_reduce.module_name("jit_nest_gemm(123)") == "jit_nest_gemm"


def test_union_clips_and_merges():
    assert trace_reduce.union([(0, 5), (3, 8), (10, 12), (-5, 1)], 0, 11) \
        == [(0, 8), (10, 11)]
