"""The readers of the spans inside a decode tick, on synthetic windows:
two ticks of different composition, spans outside any tick, a window
with no tick, and the spans of a program that records no parent links."""

import dataclasses

import pytest

from bench.metrics import Context, read
from repro.obs.trace import SpanEvent

READERS = ("h2d_ms_per_tick", "h2d_mb_per_tick", "kv_gather_ms_per_tick",
           "host_glue_ms_per_tick")


def _ev(name, id, parent, dur_ms, **attrs):
    return SpanEvent(name=name, track=("host", "main"), t0_s=0.0,
                     dur_s=dur_ms * 1e-3, depth=0, seq=0, attrs=attrs,
                     id=id, parent=parent)


def _ctx(spans):
    return Context(spans=spans, window_s=1.0, trace_window_s=1.0,
                   busy_s=0.5, kernel_s={}, launches={}, peaks=None,
                   chips=1)


#: tick 1: one launch with two uploads and a host activation, one
#: request's gather, one staging; tick 10: one upload, two gathers
TICKS = [
    _ev("decode_tick", 1, None, 20.0, n_ready=1, h2d_bytes=3_000_000),
    _ev("launch", 2, 1, 9.0, kernel="nest_gemm"),
    _ev("backend.put", 3, 2, 2.0, bytes=1_000_000, operand="input"),
    _ev("backend.put", 4, 2, 3.0, bytes=2_000_000, operand="weight"),
    _ev("host.act", 7, 2, 0.25),
    _ev("kv.gather", 5, 1, 1.0, rid=0, bytes=8),
    _ev("host.stage", 6, 1, 0.5, kind="static"),
    _ev("decode_step", 8, 1, 15.0),
    _ev("decode_tick", 10, None, 30.0, n_ready=2, h2d_bytes=1_000_000),
    _ev("launch", 11, 10, 4.0, kernel="nest_gemm"),
    _ev("backend.put", 12, 11, 1.0, bytes=1_000_000, operand="kv"),
    _ev("kv.gather", 13, 10, 2.0, rid=0, bytes=8),
    _ev("kv.gather", 14, 10, 4.0, rid=1, bytes=8),
    _ev("host.stage", 15, 10, 1.0, kind="attention"),
]

#: a prefill pass and a retire's gather: below no tick
OUTSIDE = [
    _ev("prefill_chunk", 20, None, 50.0),
    _ev("launch", 21, 20, 40.0, kernel="fused_chain"),
    _ev("backend.put", 22, 21, 30.0, bytes=9_000_000, operand="weight"),
    _ev("host.act", 23, 20, 5.0),
    _ev("kv.gather", 24, None, 7.0, rid=3, bytes=8),
]


def test_two_ticks_of_different_composition():
    ctx = _ctx(TICKS)
    assert read("h2d_ms_per_tick", ctx) == pytest.approx((2 + 3 + 1) / 2)
    assert read("h2d_mb_per_tick", ctx) == pytest.approx((3 + 1) / 2)
    assert read("kv_gather_ms_per_tick", ctx) == \
        pytest.approx((1 + 2 + 4) / 2)
    assert read("host_glue_ms_per_tick", ctx) == \
        pytest.approx((0.5 + 0.25 + 1) / 2)


def test_spans_outside_any_tick_are_ignored():
    inside = {name: read(name, _ctx(TICKS)) for name in READERS}
    for spans in (OUTSIDE + TICKS, TICKS[:8] + OUTSIDE + TICKS[8:]):
        assert {name: read(name, _ctx(spans)) for name in READERS} == \
            pytest.approx(inside)


@pytest.mark.parametrize("name", READERS)
def test_no_tick_reads_none(name):
    assert read(name, _ctx(OUTSIDE)) is None
    assert read(name, _ctx([])) is None


@dataclasses.dataclass(frozen=True)
class _OlderEvent:
    """A span event as a program without parent links records it."""
    name: str
    dur_s: float
    attrs: dict
    instant: bool = False


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_these_spans_reads_none(name):
    spans = [_OlderEvent("decode_tick", 0.02, {"n_ready": 1,
                                               "launches": 3}),
             _OlderEvent("launch", 0.01, {"kernel": "nest_gemm"})]
    assert read(name, _ctx(spans)) is None
