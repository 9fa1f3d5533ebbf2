"""Resident weights: the Pallas backend uploads a static weight once and
keeps it on the device.  A weight is static when the host array is fp32,
read-only and owns its data; every other operand is uploaded on every
launch.  The cache is bounded in bytes, least recently used first out,
and leaves the numbers unchanged: a Scheduler whose weight sets stay
writeable serves bit-identical states."""

import numpy as np
import pytest

from repro import backends
from repro.configs.feather import feather_config
from repro.core import isa, mapper, program
from repro.dist import ArrayMesh
from repro.obs import metrics
from repro.runtime import ModelExecutable, ProgramCache, Scheduler

CFG = feather_config(4, 4)
G = mapper.Gemm(m=10, k=12, n=8)
RNG = np.random.default_rng(15)


def _frozen(a):
    a.setflags(write=False)
    return a


def _weight():
    return _frozen(RNG.standard_normal((G.k, G.n)).astype(np.float32))


@pytest.fixture(scope="module")
def prog():
    choice = mapper.MappingChoice(df=isa.Dataflow.WOS, vn=4, m_t=8, k_t=8,
                                  n_t=8, n_kg=1, n_nb=1, dup=4)
    return program.lower(G, choice, CFG)


@pytest.fixture(scope="module")
def x():
    return RNG.standard_normal((G.m, G.k)).astype(np.float32)


def _run(be, prog, x, w):
    out = be.run_program(prog, {"I": x, "W": w})["O"]
    np.testing.assert_allclose(out, x @ w, rtol=2e-4, atol=2e-4)
    return out


def test_read_only_weight_is_uploaded_once(prog, x):
    be = backends.PallasBackend(CFG)
    w = _weight()
    outs = [_run(be, prog, x, w) for _ in range(3)]
    assert be.n_h2d_bytes == 3 * x.nbytes + w.nbytes
    assert be.n_weight_hits == 2
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])


@pytest.mark.parametrize("kind", ["writeable", "float64", "view"])
def test_other_weights_are_uploaded_every_call(prog, x, kind):
    """Writeable arrays, arrays that need an fp32 copy, and read-only
    views of a writeable base take the upload path each time."""
    w = RNG.standard_normal((G.k, G.n)).astype(np.float32)
    if kind == "float64":
        w = _frozen(w.astype(np.float64))
    elif kind == "view":
        w = _frozen(w[:])
    be = backends.PallasBackend(CFG)
    for _ in range(3):
        _run(be, prog, x, w)
    assert be.n_h2d_bytes == 3 * (x.nbytes + G.k * G.n * 4)
    assert be.n_weight_hits == 0 and not be._resident


def test_kv_operands_are_never_resident(prog, x):
    be = backends.PallasBackend(CFG)
    w = _weight()
    with be.weight_kind("kv"):
        for _ in range(2):
            _run(be, prog, x, w)
    assert be.n_weight_hits == 0 and not be._resident


def test_equal_shaped_weights_never_alias(prog, x):
    be = backends.PallasBackend(CFG)
    a, b = _weight(), _weight()
    for w in (a, b, a, b):
        _run(be, prog, x, w)
    assert be.n_weight_hits == 2
    assert [h for h, _ in be._resident.values()] == [a, b]


def test_reset_empties_the_cache(prog, x):
    be = backends.PallasBackend(CFG)
    w = _weight()
    _run(be, prog, x, w)
    assert be._resident_bytes == w.nbytes
    be.reset()
    assert not be._resident and be._resident_bytes == 0
    _run(be, prog, x, w)
    assert be.n_weight_hits == 0
    assert be.n_h2d_bytes == 2 * (x.nbytes + w.nbytes)


def test_byte_bound_evicts_the_least_recently_used(prog, x):
    be = backends.PallasBackend(CFG)
    a, b, c = _weight(), _weight(), _weight()
    be._resident_limit = 2 * a.nbytes
    for w in (a, b, a, c):       # a is used after b, so b goes first
        _run(be, prog, x, w)
    assert [h for h, _ in be._resident.values()] == [a, c]
    assert be._resident_bytes == 2 * a.nbytes and be.n_weight_hits == 1
    _run(be, prog, x, b)         # an evicted weight is uploaded again
    assert be.n_weight_hits == 1
    assert [h for h, _ in be._resident.values()] == [c, b]


def test_weight_larger_than_the_bound_is_not_kept(prog, x):
    be = backends.PallasBackend(CFG)
    be._resident_limit = 16
    w = _weight()
    for _ in range(2):
        _run(be, prog, x, w)
    assert not be._resident and be.n_weight_hits == 0


def test_hits_and_resident_bytes_reach_the_registry(prog, x):
    metrics.reset()
    be = backends.PallasBackend(CFG)
    w = _weight()
    for _ in range(3):
        _run(be, prog, x, w)
    snap = metrics.snapshot()
    assert sum(snap["backend_resident_weight_hits_total"].values()) == 2
    assert sum(snap["backend_resident_weight_bytes"].values()) == w.nbytes


def _serve(mesh=None, writeable=False):
    cache = ProgramCache()
    cfg = feather_config(4, 16)
    prefill = ModelExecutable.for_cell("gemma-7b", "prefill_tiny", cfg,
                                       cache=cache, mesh=mesh)
    decode = ModelExecutable.for_cell("gemma-7b", "decode_tiny", cfg,
                                      cache=cache, mesh=mesh)
    sched = Scheduler(prefill, decode, backend="pallas", max_concurrent=2,
                      seed=3)
    if writeable:
        sched.prefill_weights = {k: v.copy()
                                 for k, v in sched.prefill_weights.items()}
        sched.decode_weights = {k: v.copy()
                                for k, v in sched.decode_weights.items()}
    for steps in (3, 2, 2):
        sched.submit(decode_steps=steps)
    rep = sched.run()
    return [r.state_checksum for r in rep.requests], sched.backend


@pytest.mark.parametrize("mesh", [None, 2], ids=["single", "mesh2"])
def test_scheduler_states_match_with_and_without_residency(mesh):
    """The Scheduler's read-only weight sets become resident; the
    states it serves are bit-identical to a run whose weights stay
    writeable, which uploads every weight on every launch."""
    mesh = ArrayMesh(mesh) if mesh else None
    resident, be = _serve(mesh)
    plain, be_plain = _serve(mesh, writeable=True)
    assert resident == plain and all(resident)
    assert be.n_weight_hits > 0 and be_plain.n_weight_hits == 0
    assert be.n_h2d_bytes < be_plain.n_h2d_bytes
