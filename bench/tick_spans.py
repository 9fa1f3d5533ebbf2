"""The program's spans below each ``decode_tick``, found through the
``parent`` link every span event carries (the enclosing span's ``id``).
A span outside any tick is left out.  Where the program records no such
links or spans, every function here returns None."""

from __future__ import annotations


def below_ticks(spans, names) -> dict[int, list] | None:
    """decode_tick id -> the spans named in ``names`` below it; None
    when the window holds no tick, or no such span below any tick."""
    by_id = {getattr(e, "id", 0): e for e in spans}
    by_id.pop(0, None)
    ticks = {i: [] for i, e in by_id.items()
             if e.name == "decode_tick" and not e.instant}
    found = False
    for e in spans:
        if e.name not in names or e.instant:
            continue
        p = getattr(e, "parent", None)
        while p is not None and p not in ticks:
            p = getattr(by_id.get(p), "parent", None)
        if p is not None:
            ticks[p].append(e)
            found = True
    return ticks if found else None


def ms_per_tick(spans, names) -> float | None:
    """Milliseconds in the ``names`` spans per tick, mean over ticks."""
    ticks = below_ticks(spans, names)
    if ticks is None:
        return None
    return 1e3 * sum(e.dur_s for below in ticks.values()
                     for e in below) / len(ticks)
