import json
import pathlib

import pytest

from bench.traffic.generate import generate

MIXES = sorted(p.stem for p in (pathlib.Path(__file__).parents[1]
                                / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**31 + 11, 2**33 + 5]


def _mix(name):
    return json.loads((pathlib.Path(__file__).parents[1] / "traffic"
                       / f"{name}.json").read_text())


def _requests(plan, replacements=8):
    return plan.sessions + [plan.replacement(k)
                            for k in range(replacements)]


def _shape(plan):
    return [(s.index, s.prompt_tokens, s.output_tokens, s.seed)
            for s in _requests(plan)]


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(mix, seed):
    m = _mix(mix)
    assert _shape(generate(m, seed)) == _shape(generate(m, seed))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    m = _mix(mix)
    plans = [generate(m, s) for s in SEEDS]
    work = {tuple((s.prompt_tokens, s.output_tokens)
                  for s in _requests(p)) for p in plans}
    assert len(work) == 1
    # the seed draws the requests' tensors
    assert len({tuple(_shape(p)) for p in plans}) == len(SEEDS)
    for p in plans:
        for s in _requests(p):
            assert 0 <= s.seed < 2**63
            assert s.prompt_tokens + s.output_tokens <= m["kv_extent"]


@pytest.mark.parametrize("mix", MIXES)
def test_closed_loop_sessions_and_replacements(mix):
    m = _mix(mix)
    plan = generate(m, 9)
    assert len(plan.sessions) == m["sessions"] <= m["max_concurrent"]
    reqs = plan.sessions + [plan.replacement(k) for k in range(200)]
    assert [s.index for s in reqs] == list(range(len(reqs)))
    assert len({s.seed for s in reqs}) == len(reqs)
    lo, hi = m["output_tokens"]["min"], m["output_tokens"]["max"]
    assert all(lo <= s.output_tokens <= hi for s in reqs)
    # replacements cycle through the distribution's quantiles
    assert plan.replacement(3).output_tokens == \
        plan.replacement(3 + 64).output_tokens


def test_unknown_loop_or_length_is_refused():
    m = _mix("long_decode")
    with pytest.raises(ValueError):
        generate(dict(m, loop="open"), 1)
    with pytest.raises(ValueError):
        generate(dict(m, output_tokens={"dist": "lognormal"}), 1)
