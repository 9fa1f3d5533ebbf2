"""The comparison that decides ``correct``.

The window's own answers are compared with the plain reference
(``bench/reference.py``): a seeded sample of the prefill passes and of
the batched decode ticks that the window ran, every launch in them and
their final outputs.  The reference draws the weights itself from the
run's seed, and a first prompt chunk's inputs from the request's seed.
A decode row's operands are the reference's own: it keeps each request's
K/V from the request seed and folds in every output the request was
served before that tick, and feeds the tick the last of them, as a
served model's check runs the reference over the served tokens.  So a
tick that reads state the program failed to update, or a row served to
the wrong request, computes from the wrong operands and fails.

Each number is the worst relative error, ``||out - ref|| / ||ref||``,
over every compared array of its kind.  ``control`` reads the same
numbers with the reference computed in three bf16 passes
(``precision="high"``) in the program's place.
"""

from __future__ import annotations

import numpy as np

from bench import reference

#: number compared -> the kind of call it reads
NUMBERS = {"prefill_rel_err": "prefill", "decode_rel_err": "decode"}


def rel_err(out, ref) -> float:
    """Infinite when there is no answer or it has the wrong shape."""
    if out is None:
        return float("inf")
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        return float("inf")
    den = float(np.linalg.norm(ref))
    return float(np.linalg.norm(out - ref)) / max(den, 1e-30)


def _worst_of(errs: list[float]) -> float:
    """The largest error; NaN (a non-finite answer) wins."""
    return float("nan") if any(e != e for e in errs) else max(errs)


def _worst(pairs) -> float:
    return _worst_of([rel_err(out, ref) for out, ref in pairs])


def served_lead(config: dict, mix: dict) -> int:
    """How many leading values of a served output a later decode pass
    reads: its fresh inputs and one K/V slot."""
    steps = reference.stream(config, "decode", mix["kv_extent"])
    return max(min(shape) if kind == "dynamic" else shape[0] * shape[1]
               for _, shape, kind in reference.tensor_specs(steps)
               if kind != "weight")


def _prefill_pairs(steps, weights, calls, served, precision):
    call, = calls
    _, req_seed, chunk = call.reqs[0]
    if chunk:     # no mix sends a prompt longer than one chunk
        return [(None, None)]
    rows = reference.draw(steps, req_seed, ("dynamic", "input"))
    rows = {k: v[None] for k, v in rows.items()}
    refs = {i: np.asarray(post[0])
            for i, _, post in reference.run(steps, weights, rows,
                                            precision)}
    pairs = [(out, refs[i]) for i, out in enumerate(call.outputs)
             if out is not None]
    pairs.append((call.finals[0], refs[len(steps) - 1]))
    return pairs


def _decode_operands(steps, req, served, kv0):
    """The reference's operands for one request's row of a tick: its K/V
    from the request seed with every earlier output folded in, and its
    fresh inputs from the last output.  None when the outputs it was
    served were not all recorded."""
    rid, seed, chunks, decoded = req
    got = served.get(rid, {"prefill": [], "decode": []})
    pre, dec = got["prefill"], got["decode"]
    if len(pre) < chunks or len(dec) < decoded:
        return None
    if seed not in kv0:
        kv0[seed] = reference.initial_kv(steps, seed)
    kv = {k: v.copy() for k, v in kv0[seed].items()}
    for c in range(chunks):
        reference.commit(kv, pre[c], c)
    for j in range(decoded):
        reference.commit(kv, dec[j], chunks + j)
    last = dec[decoded - 1] if decoded else pre[chunks - 1]
    return {**reference.fresh_inputs(steps, last), **kv}


def _decode_pairs(steps, weights, calls, served, precision):
    kv0 = {}
    ops = [_decode_operands(steps, req, served, kv0)
           for c in calls for req in c.reqs]
    if any(op is None for op in ops) or \
            len(ops) != sum(c.n for c in calls):
        return [(None, None)]
    rows = {k: np.stack([op[k] for op in ops]) for k in ops[0]}
    refs = {i: (np.asarray(pre), np.asarray(post))
            for i, pre, post in reference.run(steps, weights, rows,
                                              precision)}
    pairs, b = [], 0
    for c in calls:
        if c.step_rows is None:       # launches that do not follow the plan
            pairs.append((None, None))
            b += c.n
            continue
        for i, (outs, pre_act) in c.step_rows.items():
            ref = refs[i][0 if pre_act else 1]
            pairs += [(outs[r], ref[b + r]) for r in range(c.n)]
        last = refs[len(steps) - 1][1]
        pairs += [(c.finals[r], last[b + r]) for r in range(c.n)]
        b += c.n
    return pairs


def readings(config: dict, calls: dict, served: dict, *, weight_seed: int,
             kv_extent: int, control: bool = False):
    """name -> worst relative error of the program over the sampled
    calls; a kind with no sampled call gives no number.  ``served``:
    rid -> the leading values of every output each request was served.
    With ``control``, also the same numbers for the reference at
    ``high`` in the program's place: returns ``(program, control)``."""
    prog, ctrl = {}, {}
    seq_of = {"prefill": config["prefill_chunk"], "decode": kv_extent}
    for name, kind in NUMBERS.items():
        if not calls.get(kind):
            continue
        steps = reference.stream(config, kind, seq_of[kind])
        weights = reference.draw(steps, weight_seed, ("weight",))
        if kind == "prefill":
            batches = [[c] for c in calls[kind]]
            pairs_of = _prefill_pairs
        else:
            batches = [calls[kind]]
            pairs_of = _decode_pairs
        p_errs, c_errs = [], []
        for batch in batches:
            pairs = pairs_of(steps, weights, batch, served, "highest")
            p_errs.append(_worst(pairs))
            if control:
                low = pairs_of(steps, weights, batch, served, "high")
                c_errs.append(_worst([(lo, ref) for (_, lo), (_, ref)
                                      in zip(low, pairs)]))
            del pairs
        prog[name] = _worst_of(p_errs)
        if control:
            ctrl[name] = _worst_of(c_errs)
        del weights
    return (prog, ctrl) if control else prog


def required(limits: dict, calls: dict) -> dict:
    """The limits a run is held to: the decode number always, another
    where the window ran a call of its kind."""
    return {k: v for k, v in limits.items()
            if NUMBERS[k] == "decode" or calls.get(NUMBERS[k])}


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number the run is held to was read and is
    within its limit (a NaN never is)."""
    return all(k in numbers and numbers[k] <= v for k, v in limits.items())
