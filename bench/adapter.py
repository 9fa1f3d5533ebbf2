"""Where the shared harness reaches into the serving program.

The window drives the program only through its public entry points
(``Scheduler.submit`` and ``run``).  What those do not expose, the
harness reads here, and only here:

* ``Scheduler._active`` (each ``_Active``'s ``req.rid``, ``decoded`` and
  ``status``) and ``Scheduler._pending``: per-request progress after each
  tick, which ``run`` reports only for retired requests.

What a configuration's own module reads to build, record and check its
cell is listed in that module (``bench/models/<model>``).
"""

from __future__ import annotations


def progress(sched, report) -> dict[int, tuple[int, str]]:
    """rid -> (output tokens so far, status), retired and in flight."""
    out = {r.rid: (r.decode_tokens, r.status) for r in report.requests}
    for a in sched._active:
        out[a.req.rid] = (a.decoded, a.status)
    return out


def has_work(sched) -> bool:
    return bool(sched._pending or sched._active)


def backlog(sched) -> int:
    """Requests submitted but not yet admitted."""
    return len(sched._pending)
