"""What the window produced, kept for the check after it.

The recorder wraps, on the instances, what the serving program does not
expose (it changes no argument):

* ``Scheduler._prefill_chunk`` (its ``_Active``'s ``req.rid``,
  ``req.seed`` and ``chunks_done``): which request and chunk a prefill
  pass serves;
* ``Scheduler._decode_env`` (its ``_Active``'s ``req.rid``, ``req.seed``,
  ``prefill_chunks`` and ``decoded``): which request each row of a
  batched tick serves, and how many outputs it had been served;
* ``Scheduler._after_decode``: each output served to a request;
* ``ModelExecutable.run`` and ``run_batch`` and the backend's
  ``run_program`` (two arguments) / ``run_segment`` /
  ``run_batched_attention``: every served output's leading values, and
  references to what every launch of a sampled call returned;
* ``ModelExecutable.steps`` / ``segments`` / ``batch_plan`` and
  ``Step.host_act``: the order of a batched tick's launches and which
  outputs the runtime activates on the host.
"""

from __future__ import annotations

import dataclasses
import random

import numpy as np


@dataclasses.dataclass
class Call:
    """One sampled call: the requests it served, each backend launch's
    output in order, and the results."""
    kind: str                          # "prefill" | "decode"
    n: int                             # requests in the call
    launches: list[np.ndarray]
    finals: list[np.ndarray]
    outputs: list | None = None        # prefill: RunResult.outputs
    # prefill: (rid, request seed, chunk); decode, per request:
    # (rid, request seed, prompt chunks, outputs served before this tick)
    reqs: list[tuple] = dataclasses.field(default_factory=list)


class Recorder:
    """Keeps every request's served outputs from its first pass on (the
    leading ``keep`` values of each, all that its later passes and its
    K/V read), and while ``armed`` a seeded reservoir of
    ``per_kind[kind]`` calls of each kind.  Create it before the first
    request is submitted."""

    def __init__(self, sched, seed: int, per_kind: dict[str, int],
                 keep: int):
        self.sched = sched
        self.rng = random.Random(seed)
        self.per_kind = per_kind
        self.keep = keep
        self.seen = {"prefill": 0, "decode": 0}
        self.kept: dict[str, list[Call]] = {"prefill": [], "decode": []}
        # rid -> {"prefill": [chunk outputs], "decode": [step outputs]}
        self.served: dict[int, dict[str, list]] = {}
        self.current: Call | None = None
        self.chunk: tuple[int, int, int] | None = None
        self.env_reqs: list[tuple] = []
        self.armed = False
        self._wrap()

    def _take(self, kind) -> int | None:
        """Reservoir slot for the next call of ``kind`` (None: skip)."""
        if not self.armed:
            return None
        self.seen[kind] += 1
        c, k = self.seen[kind], self.per_kind[kind]
        if c <= k:
            return c - 1
        j = self.rng.randrange(c)
        return j if j < k else None

    def _keep(self, kind, slot, call):
        kept = self.kept[kind]
        if slot < len(kept):
            kept[slot] = call
        else:
            kept.append(call)

    def _serve(self, rid, kind, out):
        lead = np.array(np.asarray(out, np.float32).ravel()[:self.keep])
        self.served.setdefault(rid, {"prefill": [], "decode": []})[
            kind].append(lead)

    def _wrap(self):
        sched = self.sched
        pre, dec, be = sched.prefill, sched.decode, sched.backend
        pre_run, dec_batch = pre.run, dec.run_batch
        prefill_chunk = sched._prefill_chunk
        decode_env = sched._decode_env
        after_decode = sched._after_decode

        def run(backend="interpreter", **kw):
            chunk = self.chunk
            slot = self._take("prefill") if chunk else None
            call = None
            if slot is not None:
                call = Call("prefill", 1, [], [], reqs=[chunk])
            self.current = call
            try:
                res = pre_run(backend, **kw)
            finally:
                self.current = None
            if chunk:
                self._serve(chunk[0], "prefill", res.final)
            if call is not None:
                call.finals = [res.final]
                call.outputs = list(res.outputs)
                self._keep("prefill", slot, call)
            return res

        def env(a):
            self.env_reqs.append((a.req.rid, a.req.seed, a.prefill_chunks,
                                  a.decoded))
            return decode_env(a)

        def run_batch(backend, envs, **kw):
            reqs, self.env_reqs = self.env_reqs, []
            slot = self._take("decode")
            if slot is None:
                return dec_batch(backend, envs, **kw)
            call = Call("decode", len(envs), [], [], reqs=reqs)
            self.current = call
            try:
                finals = dec_batch(backend, envs, **kw)
            finally:
                self.current = None
            call.finals = list(finals)
            self._keep("decode", slot, call)
            return finals

        def served(a, final):
            self._serve(a.req.rid, "decode", final)
            return after_decode(a, final)

        def launch(fn, output_of):
            def wrapped(program, *args, **kw):
                out = fn(program, *args, **kw)
                if self.current is not None:
                    self.current.launches.append(output_of(program, out))
                return out
            return wrapped

        def chunk(a):
            self.chunk = (a.req.rid, a.req.seed, a.chunks_done)
            try:
                return prefill_chunk(a)
            finally:
                self.chunk = None

        sched._prefill_chunk = chunk
        sched._decode_env = env
        sched._after_decode = served
        pre.run = run
        dec.run_batch = run_batch
        be.run_program = launch(be.run_program,
                                lambda p, out: out[p.out_name])
        be.run_segment = launch(be.run_segment,
                                lambda s, out: out[s.out_name])
        be.run_batched_attention = launch(be.run_batched_attention,
                                          lambda p, out: out)

    def collect(self) -> tuple[dict, dict]:
        """The sampled calls, decode ticks mapped to their steps, and
        every request's served outputs; drops the hold on the
        scheduler."""
        for c in self.kept["decode"]:
            c.step_rows = decode_step_rows(self.sched.decode, c)
        self.sched = None
        return self.kept, self.served


def decode_step_rows(decode, call: Call) -> dict | None:
    """step index -> (per-request output arrays, pre_host_act) for every
    launch of a recorded batched tick, mapped by the batch plan's launch
    order; None when the tick's launches do not follow its plan.
    ``pre_host_act`` is True where the runtime applies that step's
    activation on the host after the launch returned."""
    try:
        return _step_rows(decode, call)
    except (StopIteration, IndexError, ValueError):
        return None


def _step_rows(decode, call: Call) -> dict[int, tuple[list, bool]]:
    plan = decode.batch_plan(call.n)
    it = iter(call.launches)
    rows: dict[int, tuple[list, bool]] = {}
    for seg in plan.segments:
        steps = [decode.steps[i] for i in seg.indices]
        if seg.kind == "perreq":
            for i in seg.indices:
                rows[i] = ([None] * call.n,
                           decode.steps[i].host_act is not None)
            for r in range(call.n):
                for i in seg.indices:
                    rows[i][0][r] = next(it)
            continue
        m = seg.m_rows
        if seg.kind == "attention":
            out = next(it)
            rows[seg.indices[-1]] = ([out[r] for r in range(call.n)],
                                     steps[-1].host_act is not None)
            continue
        if seg.fused is not None:
            outs = [(seg.indices[-1], next(it))]
        else:
            outs = [(i, next(it)) for i in seg.indices]
        for i, out in outs:
            rows[i] = ([out[r * m:(r + 1) * m] for r in range(call.n)],
                       decode.steps[i].host_act is not None)
    if next(it, None) is not None:
        raise ValueError("a batched tick launched more than its plan")
    return rows
