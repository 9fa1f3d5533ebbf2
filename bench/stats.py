"""End-to-end arithmetic over one measured window, on the harness's
clock, over every request and every token of the window."""

from __future__ import annotations

import dataclasses
import math


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class RequestLog:
    """One request as the client sees it: when each output token became
    visible (seconds on the harness clock)."""
    rid: int
    output_tokens: int
    token_s: list[float] = dataclasses.field(default_factory=list)
    status: str = "ok"


def token_gaps(logs: list[RequestLog]) -> list[float]:
    """Every gap between consecutive output tokens of one request."""
    return [b - a for r in logs for a, b in zip(r.token_s, r.token_s[1:])]


def output_tokens(logs: list[RequestLog], t0: float, t_end: float) -> int:
    return sum(1 for r in logs for t in r.token_s if t0 <= t <= t_end)


def end_to_end(logs: list[RequestLog], t0: float, t_end: float) -> dict:
    """The window's end-to-end numbers (seconds / tokens); a metric with
    no sample is left out."""
    out = {"output_tokens_per_s":
           output_tokens(logs, t0, t_end) / (t_end - t0)}
    gaps = token_gaps(logs)
    if gaps:
        out["tpot_p95_ms"] = 1e3 * percentile(gaps, 95)
    return out
