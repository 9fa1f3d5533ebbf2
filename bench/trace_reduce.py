"""From a profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX).  Device operations are
the events on the TPU planes' ``XLA Ops`` line, each named by its HLO
op (``%nest_gemm.1 = f32[...] custom-call(...)`` is ``nest_gemm``) and
placed in the XLA module (``jit_nest_gemm``) of the ``XLA Modules`` line
that holds it; busy time is the union of their intervals.
Host-to-device and device-to-host copies are not operations on that
line, so they do not count as busy: the device is busy when it
computes.  Host intervals are the harness's own
``bench.*`` annotations on the profiler clock, to which the program's
spans are moved by one anchor annotation.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """``%nest_gemm.1 = f32[..] custom-call(..)`` -> ``nest_gemm``."""
    if text.startswith("%") and " = " in text:
        text = text[1:text.index(" = ")]
    return _SUFFIX.sub("", text)


def module_name(text: str) -> str:
    """``jit_nest_gemm(1234)`` -> ``jit_nest_gemm``."""
    return text.split("(", 1)[0]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str                 # HLO op name, or the annotation's name
    start_ns: float
    dur_ns: float
    module: str = ""          # the XLA module (jit) holding the op

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Event]]   # plane name -> ops, by start
    host: list[Event]                    # bench.* annotations

    def ops(self) -> list[Event]:
        return sorted((e for evs in self.device_ops.values() for e in evs),
                      key=lambda e: e.start_ns)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           module_name(e.name))
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            evs = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else ""
                evs.append(Event(op_name(e.name), e.start_ns,
                                 e.duration_ns, mod))
            device[plane.name] = sorted(evs, key=lambda e: e.start_ns)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns,
                                          e.duration_ns))
    host.sort(key=lambda e: e.start_ns)
    return Trace(device, host)


def union(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[t0, t1]``."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, t0: float, t1: float) -> float:
    """Seconds (in ns) in which some operation ran, averaged over the
    device planes."""
    if not trace.device_ops:
        return 0.0
    tot = 0.0
    for evs in trace.device_ops.values():
        tot += sum(b - a for a, b in union(
            [(e.start_ns, e.end_ns) for e in evs], t0, t1))
    return tot / len(trace.device_ops)


def gaps(trace: Trace, t0: float, t1: float) -> list[tuple[float, float]]:
    """Idle intervals of the device (all planes idle), longest first."""
    busy = union([(e.start_ns, e.end_ns) for e in trace.ops()], t0, t1)
    out, t = [], t0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < t1:
        out.append((t, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def kernel_ns(trace: Trace, kernel: str, t0: float, t1: float) -> float:
    """Device time of the kernel's own ops (the custom call whose HLO
    name carries ``kernel``; copies and pads around it are not the
    kernel)."""
    return sum(min(e.end_ns, t1) - max(e.start_ns, t0)
               for e in trace.ops()
               if kernel in e.name and e.end_ns > t0 and e.start_ns < t1)


def top_ops(trace: Trace, t0: float, t1: float,
            n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` op groups (``module/op``) with most device seconds."""
    tot: dict[str, float] = {}
    for e in trace.ops():
        if e.end_ns <= t0 or e.start_ns >= t1:
            continue
        key = f"{e.module}/{e.name}" if e.module else e.name
        tot[key] = tot.get(key, 0.0) + (min(e.end_ns, t1)
                                         - max(e.start_ns, t0)) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def label_gaps(idle, host: list[tuple[str, float, float]],
               n: int = 10) -> list[tuple[str, float]]:
    """Name each of the ``n`` longest idle gaps by the innermost host
    interval ``(label, start_ns, end_ns)`` covering its midpoint."""
    out = []
    for a, b in idle[:n]:
        mid = 0.5 * (a + b)
        inside = [(e - s, lab) for lab, s, e in host if s <= mid <= e]
        out.append((min(inside)[1] if inside else "untraced host",
                    (b - a) * 1e-9))
    return out
