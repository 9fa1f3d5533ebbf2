"""The comparison that decides ``correct``.

The window's own answers are compared with the plain reference
(``reference.py`` beside this file): a seeded sample of the prefill
passes and of the batched decode ticks that the window ran, every launch
in them and their final outputs.  The reference draws the weights itself
from the run's seed, and a first prompt chunk's inputs from the
request's seed.  A decode row's operands are the reference's own: it
keeps each request's K/V from the request seed and folds in every
output the request was served before that tick, and feeds the tick the
last of them, as a served model's check runs the reference over the
served tokens.  So a tick that reads state the program failed to
update, or a row served to the wrong request, computes from the wrong
operands and fails.

Each number is the worst over every compared array of its kind of
``||out - ref|| / ||ref||`` (``*_rel_err``), or of ``||out - ref|| /
||bound||`` (``decode_err_over_bound``), ``bound`` being the array's
first-order rounding-error bound in units of the unit roundoff
(``reference.run(..., bound=True)``): a decode state that grows until
the attention's softmax is sharp swings the relative error from seed to
seed, the error over its bound does not.  A configuration is held to the
numbers its ``correct_limits`` names.  ``control`` reads the same numbers
with the reference computed in three bf16 passes (``precision="high"``)
in the program's place.
"""

from __future__ import annotations

import numpy as np

from bench.models.gemm_stream import reference

#: number compared -> the kind of call it reads
NUMBERS = {"prefill_rel_err": "prefill", "decode_rel_err": "decode",
           "decode_err_over_bound": "decode"}


def rel_err(out, ref, scale=None) -> float:
    """``||out - ref||`` over ``||scale||`` (by default ``||ref||``);
    infinite when there is no answer or it has the wrong shape."""
    if out is None:
        return float("inf")
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        return float("inf")
    den = float(np.linalg.norm(ref if scale is None else scale))
    return float(np.linalg.norm(out - ref)) / max(den, 1e-30)


def _worst_of(errs: list[float]) -> float:
    """The largest error; NaN (a non-finite answer) wins."""
    return float("nan") if any(e != e for e in errs) else max(errs)


def _worst(name, triples) -> float:
    if name.endswith("_over_bound"):
        return _worst_of([rel_err(*t) for t in triples])
    return _worst_of([rel_err(out, ref) for out, ref, _ in triples])


def served_lead(config: dict, mix: dict) -> int:
    """How many leading values of a served output a later decode pass
    reads: its fresh inputs and one K/V slot."""
    steps = reference.stream(config, "decode", mix["kv_extent"])
    return max(min(shape) if kind == "dynamic" else shape[0] * shape[1]
               for _, shape, kind in reference.tensor_specs(steps)
               if kind != "weight")


def _prefill_pairs(steps, weights, calls, served, precision, bound):
    """(program output, reference, None) for every launch of one
    prefill pass (no bound: no number reads one)."""
    call, = calls
    _, req_seed, chunk = call.reqs[0]
    if chunk:     # no mix sends a prompt longer than one chunk
        return [(None, None, None)]
    rows = reference.draw(steps, req_seed, ("dynamic", "input"))
    rows = {k: v[None] for k, v in rows.items()}
    refs = {i: np.asarray(post[0])
            for i, _, post in reference.run(steps, weights, rows,
                                            precision)}
    pairs = [(out, refs[i], None) for i, out in enumerate(call.outputs)
             if out is not None]
    pairs.append((call.finals[0], refs[len(steps) - 1], None))
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


def _decode_pairs(steps, weights, calls, served, precision, bound):
    """(program output, reference, its bound or None) for every launch
    of the sampled decode ticks and their results."""
    kv0 = {}
    ops = [_decode_operands(steps, req, served, kv0)
           for c in calls for req in c.reqs]
    if any(op is None for op in ops) or \
            len(ops) != sum(c.n for c in calls):
        return [(None, None, None)]
    rows = {k: np.stack([op[k] for op in ops]) for k in ops[0]}
    refs = {}
    for i, *arrs in reference.run(steps, weights, rows, precision, bound):
        pre, post, *bounds = (np.asarray(a) for a in arrs)
        refs[i] = ((pre, bounds[0] if bound else None),
                   (post, bounds[1] if bound else None))
    triples, b = [], 0
    for c in calls:
        if c.step_rows is None:       # launches that do not follow the plan
            triples.append((None, None, None))
            b += c.n
            continue
        for i, (outs, pre_act) in c.step_rows.items():
            ref, bnd = refs[i][0 if pre_act else 1]
            triples += [(outs[r], ref[b + r],
                         None if bnd is None else bnd[b + r])
                        for r in range(c.n)]
        ref, bnd = refs[len(steps) - 1][1]
        triples += [(c.finals[r], ref[b + r],
                     None if bnd is None else bnd[b + r])
                    for r in range(c.n)]
        b += c.n
    return triples


def readings(config: dict, mix: dict, collected, *, weight_seed: int,
             control: bool = False):
    """name -> worst error of the program over the sampled calls, for
    each number the configuration's ``correct_limits`` names; a kind with
    no sampled call gives no number.  ``collected``: the recorder's
    ``(calls, served)``, ``served`` being rid -> the leading values of
    every output each request was served.  With ``control``, also the
    same numbers for the reference at ``high`` in the program's place:
    returns ``(program, control)``."""
    calls, served = collected
    prog, ctrl = {}, {}
    seq_of = {"prefill": config["prefill_chunk"],
              "decode": mix["kv_extent"]}
    pairs_of = {"prefill": _prefill_pairs, "decode": _decode_pairs}
    for kind in ("prefill", "decode"):
        names = [n for n in config["correct_limits"] if NUMBERS[n] == kind]
        if not names or not calls.get(kind):
            continue
        bound = any(n.endswith("_over_bound") for n in names)
        steps = reference.stream(config, kind, seq_of[kind])
        weights = reference.draw(steps, weight_seed, ("weight",))
        batches = ([[c] for c in calls[kind]] if kind == "prefill"
                   else [calls[kind]])
        p_errs = {n: [] for n in names}
        c_errs = {n: [] for n in names}
        for batch in batches:
            triples = pairs_of[kind](steps, weights, batch, served,
                                     "highest", bound)
            for n in names:
                p_errs[n].append(_worst(n, triples))
            if control:
                low = pairs_of[kind](steps, weights, batch, served, "high",
                                     False)
                low = [(lo, ref, bnd) for (_, lo, _), (_, ref, bnd)
                       in zip(low, triples)]
                for n in names:
                    c_errs[n].append(_worst(n, low))
            del triples
        for n in names:
            prog[n] = _worst_of(p_errs[n])
            if control:
                ctrl[n] = _worst_of(c_errs[n])
        del weights
    return (prog, ctrl) if control else prog


def required(limits: dict, collected) -> dict:
    """The limits a run is held to: the decode number always, another
    where the window ran a call of its kind."""
    calls, _ = collected
    return {k: v for k, v in limits.items()
            if NUMBERS[k] == "decode" or calls.get(NUMBERS[k])}

