"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``,
each with ``read(ctx) -> float | None``.  A reader that finds nothing to
read returns None and the metric is left out of the result line; no
share of a roofline or a peak is ever reported as 0 for want of data."""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Context:
    """What one traced window left behind."""
    spans: list                 # the program's span events in the window
    window_s: float             # the window on the harness clock
    trace_window_s: float       # the same window on the profiler clock
    busy_s: float               # device seconds with an op running
    kernel_s: dict              # kernel name -> device seconds
    launches: dict              # kernel name -> [(flops, bytes)] run
    peaks: object               # bench.peaks.Peaks of the device
    chips: int

    def spans_named(self, name: str) -> list:
        return [e for e in self.spans if e.name == name and not e.instant]


def read(name: str, ctx: Context):
    mod = importlib.import_module(f"bench.metrics.{name}")
    return mod.read(ctx)


def mean_ms(spans) -> float | None:
    return 1e3 * sum(e.dur_s for e in spans) / len(spans) if spans else None


def roofline(ctx: Context, kernel: str) -> float | None:
    """Least time the chip needs for the kernel's launches in the window
    (the larger of operations over the bf16 peak and bytes over HBM
    bandwidth, per launch), as a share of its device time."""
    calls = ctx.launches.get(kernel)
    dev = ctx.kernel_s.get(kernel, 0.0)
    if not calls or dev <= 0.0:
        return None
    least = sum(max(f / ctx.peaks.bf16_flops, b / ctx.peaks.hbm_bytes_per_s)
                for f, b in calls)
    return 100.0 * least / dev
