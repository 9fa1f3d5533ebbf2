"""Multi-array sharding: ShardedProgram execution == unsharded Program ==
einsum oracle across mesh shapes and both backends, traffic conservation,
axis policy, activation hoisting, the mesh-aware runtime (executable +
scheduler determinism) and the ProgramCache sharded tier."""

import dataclasses

import numpy as np
import pytest

from repro import backends
from repro.configs.feather import feather_config
from repro.core import isa, mapper, perf, program, workloads
from repro.core.planner import GemmOp, plan_model
from repro.dist import ArrayMesh
from repro.dist.sharding import gemm_shard_axis
from repro.runtime import ModelExecutable, ProgramCache, Scheduler

RNG = np.random.default_rng(11)
CFG = feather_config(4, 16)


def _tensors(g):
    return {
        "I": RNG.standard_normal((g.m, g.k)).astype(np.float32),
        "W": RNG.standard_normal((g.k, g.n)).astype(np.float32),
    }


def _choice(df=isa.Dataflow.WOS, vn=4):
    return mapper.MappingChoice(df=df, vn=vn, m_t=8, k_t=8, n_t=8,
                                n_kg=1, n_nb=1, dup=4)


# ---------------------------------------------------------------------------
# Acceptance sweep: every ci_suite GEMM, 4-array mesh, both backends
# ---------------------------------------------------------------------------

_SWEEP_CACHE = ProgramCache(max_plans=1 << 20)


@pytest.mark.parametrize("gemm", workloads.ci_suite(),
                         ids=lambda g: g.name)
def test_sharded_equivalence_workload_sweep(gemm):
    """Sharded execution on a 4-array mesh matches the unsharded einsum
    oracle on both backends, for every Tab. IV (CI extents) workload."""
    plan = _SWEEP_CACHE.plan(gemm, CFG)
    backends.cross_check(plan.program, _tensors(gemm), mesh=ArrayMesh(4))


# ---------------------------------------------------------------------------
# Property sweep: random GEMMs x mesh {1, 2, 4} x every axis x backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("n_arrays", [1, 2, 4])
def test_sharded_matches_unsharded_and_oracle(seed, n_arrays):
    rng = np.random.default_rng(seed)
    g = mapper.Gemm(m=int(rng.integers(5, 40)),
                    k=int(rng.integers(5, 40)),
                    n=int(rng.integers(5, 40)))
    prog = mapper.search(g, CFG).program
    t = _tensors(g)
    mesh = ArrayMesh(n_arrays)
    unsharded = backends.run(prog, t)[prog.out_name]
    for axis in (None, "m", "n", "k"):
        for name in ("interpreter", "pallas"):
            out = backends.run_sharded(prog, t, mesh, backend=name,
                                       axis=axis)[prog.out_name]
            np.testing.assert_allclose(
                out, t["I"] @ t["W"], rtol=2e-4, atol=2e-4 + 2e-4 * g.k,
                err_msg=f"{name} axis={axis} n={n_arrays} on {g}")
            np.testing.assert_allclose(
                out, unsharded, rtol=2e-4, atol=2e-4 + 2e-4 * g.k)


def test_traffic_sums_to_single_array_total():
    """Per-array MINISA traffic is conserved: the sum over arrays equals
    the single-array total within tiling overhead (tight at scale where
    the Execute stream dominates, bounded on small problems)."""
    cfg = feather_config(16, 64)
    g = mapper.Gemm(m=65536, k=40, n=88, name="bconv-full")
    plan = _SWEEP_CACHE.plan(g, cfg)
    base = plan.program.minisa_bytes()
    for n_arrays in (2, 4, 8):
        sh = program.shard_program(plan.program, ArrayMesh(n_arrays))
        per = sh.per_array_minisa_bytes()
        assert len(per) == n_arrays and all(b > 0 for b in per)
        assert sum(per) == sh.minisa_bytes()
        ratio = sh.minisa_bytes() / base
        assert 0.95 <= ratio <= 1.25, (n_arrays, sh.axis, ratio)


def test_mesh_perf_parallel_speedup_and_imbalance():
    cfg = feather_config(16, 64)
    g = mapper.Gemm(m=65536, k=40, n=88)
    plan = _SWEEP_CACHE.plan(g, cfg)
    base_cycles = plan.perf_minisa.cycles
    sh = program.shard_program(plan.program, ArrayMesh(4))
    mp = perf.simulate_sharded(sh, cfg)
    assert len(mp.per_array) == 4
    assert 1.0 <= mp.load_imbalance <= 1.5
    # arrays run in parallel: the mesh makespan beats one array clearly
    assert base_cycles / mp.cycles > 2.0
    assert mp.macs == pytest.approx(plan.perf_minisa.macs)


# ---------------------------------------------------------------------------
# Axis policy + partition structure
# ---------------------------------------------------------------------------

def test_axis_policy_prefers_divisible_tensor_parallel():
    # N divisible -> tensor parallelism first
    assert gemm_shard_axis(64, 64, 64, 4) == "n"
    # N indivisible/narrow -> fall through to M
    assert gemm_shard_axis(64, 64, 3, 4) == "m"
    # only K can host the arrays
    assert gemm_shard_axis(2, 64, 3, 4) == "k"
    # tile counts gate replication-prone ranks: N fits one tile ->
    # splitting it would replicate the M-loop traffic on every array
    assert gemm_shard_axis(64, 64, 64, 4,
                           tiles={"m": 8, "n": 1, "k": 1}) == "m"
    assert gemm_shard_axis(64, 64, 64, 2) == "n"


def test_shard_slices_partition_the_problem():
    g = mapper.Gemm(m=20, k=12, n=18)
    prog = program.lower(g, _choice(), CFG)
    for axis, dim in (("m", g.m), ("n", g.n), ("k", g.k)):
        sh = program.shard_program(prog, ArrayMesh(4), axis=axis)
        spans = [(s.m1 - s.m0) * (s.n1 - s.n0) * (s.k1 - s.k0)
                 for s in sh.shards]
        assert sum(spans) == g.m * g.k * g.n   # disjoint cover
        assert sh.reduce == (axis == "k")
        assert sh.macs == g.macs


def test_single_array_mesh_is_the_program_itself():
    g = mapper.Gemm(m=10, k=8, n=6)
    prog = program.lower(g, _choice(), CFG)
    sh = program.shard_program(prog, ArrayMesh(1))
    assert sh.n_shards == 1
    assert sh.shards[0].program is prog
    assert sh.minisa_bytes() == prog.minisa_bytes()


def test_chained_programs_refuse_to_shard():
    g1 = mapper.Gemm(m=10, k=12, n=8)
    g2 = mapper.Gemm(m=10, k=8, n=6)
    p1 = program.lower(g1, _choice(), CFG, out_name="O0")
    p2 = program.lower(g2, _choice(), CFG, out_name="O1")
    chained = program.chain([p1, p2])
    with pytest.raises(ValueError, match="commit"):
        program.shard_program(chained[0], ArrayMesh(2))
    with pytest.raises(ValueError, match="elided"):
        program.shard_program(chained[1], ArrayMesh(2))


# ---------------------------------------------------------------------------
# Activation hoisting across the mesh boundary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("axis", ["m", "n", "k"])
def test_elementwise_activation_sharded(axis):
    g = mapper.Gemm(m=12, k=10, n=14)
    act = lambda x: np.maximum(x, 0)  # noqa: E731
    prog = program.lower(g, _choice(), CFG, activation=act,
                         act_name="relu")
    sh = program.shard_program(prog, ArrayMesh(2), axis=axis)
    # K split hoists (partial sums are pre-activation); M/N keep it local
    assert (sh.epilogue_act is not None) == (axis == "k")
    backends.cross_check(prog, _tensors(g), mesh=ArrayMesh(2), axis=axis)


@pytest.mark.parametrize("axis", ["m", "n", "k"])
def test_row_wise_activation_sharded(axis):
    """softmax needs full output rows: only a WO-S M split keeps rows
    shard-local; N/K splits hoist it to the assembled output."""
    g = mapper.Gemm(m=8, k=10, n=12)
    from repro.runtime.executable import ACTIVATIONS
    prog = program.lower(g, mapper.MappingChoice(
        df=isa.Dataflow.WOS, vn=4, m_t=8, k_t=12, n_t=12,
        n_kg=1, n_nb=1, dup=4), CFG,
        activation=ACTIVATIONS["softmax"], act_name="softmax")
    sh = program.shard_program(prog, ArrayMesh(2), axis=axis)
    assert (sh.epilogue_act is None) == (axis == "m")
    backends.cross_check(prog, _tensors(g), mesh=ArrayMesh(2), axis=axis)


# ---------------------------------------------------------------------------
# Plan.execute / cache tier / planner mesh plumbing
# ---------------------------------------------------------------------------

def test_plan_execute_with_mesh():
    g = mapper.Gemm(m=17, k=24, n=21)
    plan = mapper.search(g, CFG)
    t = _tensors(g)
    for name in ("interpreter", "pallas"):
        out = plan.execute(t, backend=name, mesh=ArrayMesh(3))["O"]
        np.testing.assert_allclose(out, t["I"] @ t["W"],
                                   rtol=2e-4, atol=2e-4 + 2e-4 * g.k)


def test_cache_sharded_tier_memoises_per_mesh_shape():
    cache = ProgramCache()
    g = mapper.Gemm(m=16, k=16, n=16)
    plan = cache.plan(g, CFG)
    s2a = cache.sharded(plan.program, ArrayMesh(2))
    s2b = cache.sharded(plan.program, ArrayMesh(2))
    s4 = cache.sharded(plan.program, ArrayMesh(4))
    assert s2a is s2b and s2a is not s4
    assert cache.stats.sharded_hits == 1
    assert cache.stats.sharded_misses == 2
    # shard sub-lowerings flow through the shared lowered tier
    assert cache.stats.lowered_misses > 0
    assert "sharded" in cache.summary()["entries"]


def test_plan_model_mesh_aggregates():
    cache = ProgramCache()
    ops = [GemmOp(gemm=mapper.Gemm(m=64, k=32, n=48, name="fc1", count=2)),
           GemmOp(gemm=mapper.Gemm(m=64, k=48, n=32, name="fc2"),
                  chained=True)]
    single = plan_model("toy", "cell", ops, CFG, cache=cache)
    meshed = plan_model("toy", "cell", ops, CFG, cache=cache,
                        mesh=ArrayMesh(4))
    assert meshed.n_arrays == 4
    assert len(meshed.per_array_bytes) == 4
    assert sum(meshed.per_array_bytes) == pytest.approx(meshed.minisa_bytes)
    assert meshed.load_imbalance >= 1.0
    # parallel arrays: the meshed cell is faster than the single array
    assert meshed.cycles_minisa < single.cycles_minisa
    assert meshed.summary()["n_arrays"] == 4


# ---------------------------------------------------------------------------
# Mesh-aware runtime: executable + scheduler determinism
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_cache():
    return ProgramCache()


@pytest.mark.parametrize("backend", ["interpreter", "pallas"])
def test_executable_sharded_matches_stream_oracle(mesh_cache, backend):
    ex = ModelExecutable.for_cell("gemma-7b", "decode_tiny", CFG,
                                  cache=mesh_cache, mesh=ArrayMesh(4))
    assert ex.describe()["n_sharded"] == len(ex.steps)
    res = ex.run(backend, check=True)
    assert res.checked and len(res.outputs) == len(ex.steps)
    stats = ex.perf_stats()
    assert stats["n_arrays"] == 4
    assert len(stats["per_array_minisa_bytes"]) == 4
    assert sum(stats["per_array_minisa_bytes"]) == pytest.approx(
        stats["minisa_bytes"])
    assert stats["load_imbalance"] >= 1.0


def _sched_run(cache, backend, mesh=None, seed=0):
    prefill = ModelExecutable.for_cell("gemma-7b", "prefill_tiny", CFG,
                                       cache=cache, mesh=mesh)
    decode = ModelExecutable.for_cell("gemma-7b", "decode_tiny", CFG,
                                      cache=cache, mesh=mesh)
    sched = Scheduler(prefill, decode, backend=backend, max_concurrent=2,
                      seed=seed)
    for _ in range(3):
        sched.submit(decode_steps=2)
    return sched.run()


def test_scheduler_run_bit_reproducible(mesh_cache):
    """Determinism regression: same submissions -> identical per-request
    state checksums run-to-run, across backends, and under a mesh;
    different scheduler seeds diverge."""
    a = _sched_run(mesh_cache, "interpreter")
    b = _sched_run(mesh_cache, "interpreter")
    c = _sched_run(mesh_cache, "pallas")
    assert [r.state_checksum for r in a.requests] \
        == [r.state_checksum for r in b.requests] \
        == [r.state_checksum for r in c.requests]
    assert all(r.state_checksum for r in a.requests)
    other = _sched_run(mesh_cache, "interpreter", seed=7)
    assert [r.state_checksum for r in other.requests] \
        != [r.state_checksum for r in a.requests]
    # traffic accounting is backend-independent byte-for-byte
    assert [r.minisa_bytes for r in a.requests] \
        == [r.minisa_bytes for r in c.requests]


def test_scheduler_mesh_report(mesh_cache):
    rep = _sched_run(mesh_cache, "interpreter", mesh=ArrayMesh(4))
    assert rep.n_arrays == 4
    assert len(rep.per_array_minisa_bytes) == 4
    assert all(b > 0 for b in rep.per_array_minisa_bytes)
    assert rep.load_imbalance >= 1.0
    s = rep.summary()
    assert s["n_arrays"] == 4 and len(s["per_array_cycles"]) == 4
    # sharded and unsharded serving agree on the request state trajectory
    flat = _sched_run(mesh_cache, "interpreter")
    assert [r.state_checksum for r in rep.requests] \
        == [r.state_checksum for r in flat.requests]


def test_scheduler_rejects_mismatched_meshes(mesh_cache):
    prefill = ModelExecutable.for_cell("gemma-7b", "prefill_tiny", CFG,
                                       cache=mesh_cache, mesh=ArrayMesh(4))
    decode = ModelExecutable.for_cell("gemma-7b", "decode_tiny", CFG,
                                      cache=mesh_cache)
    with pytest.raises(ValueError, match="ArrayMesh"):
        Scheduler(prefill, decode)


# ---------------------------------------------------------------------------
# shard_map execution on a real device mesh (runs when devices exist)
# ---------------------------------------------------------------------------

def test_pallas_shard_map_path_when_devices_available():
    """With >= 2 JAX devices (the CI multi-device job fakes 8 via
    XLA_FLAGS), the Pallas backend executes the whole mesh as one
    shard_map-wrapped kernel; with 1 device it must fall back to the
    sequential path -- either way the numbers match the oracle."""
    import jax
    n_dev = len(jax.devices())
    mesh = ArrayMesh(min(max(n_dev, 2), 4))
    assert (mesh.jax_mesh() is not None) == (n_dev >= mesh.n_arrays)
    for df in (isa.Dataflow.WOS, isa.Dataflow.IOS):
        g = mapper.Gemm(m=24, k=16, n=20)
        prog = program.lower(g, _choice(df), CFG)
        t = _tensors(g)
        for axis in ("m", "n", "k"):
            out = backends.run_sharded(prog, t, mesh, backend="pallas",
                                       axis=axis)[prog.out_name]
            np.testing.assert_allclose(out, t["I"] @ t["W"],
                                       rtol=2e-4, atol=2e-4 + 2e-4 * g.k,
                                       err_msg=f"{df} axis={axis}")


def test_pallas_shard_backends_pinned_to_their_devices():
    """Each logical array's executor runs on its own device (wrapping
    when the host has fewer devices than arrays), and its launches are
    counted under that device."""
    import jax
    from repro.obs import metrics as obs_metrics
    devices = jax.devices()
    be = backends.get_backend("pallas", CFG)
    g = mapper.Gemm(m=24, k=16, n=20)
    prog = program.lower(g, _choice(), CFG)
    t = _tensors(g)
    for a in range(4):
        sub = be._shard_backend(a)
        assert sub.device == devices[a % len(devices)]
        before = obs_metrics.snapshot().get("backend_launches_total", {})
        out = sub.run_program(prog, t)[prog.out_name]
        after = obs_metrics.snapshot()["backend_launches_total"]
        key = f'{{device="{sub.device}",kernel="nest_gemm"}}'
        assert after[key] == before.get(key, 0) + 1
        np.testing.assert_allclose(out, t["I"] @ t["W"], rtol=2e-4,
                                   atol=2e-4 + 2e-4 * g.k)


def test_array_mesh_validation():
    with pytest.raises(ValueError):
        ArrayMesh(0)
    assert ArrayMesh(1).jax_mesh() is None
    assert ArrayMesh(2).shape == (2,)
    with pytest.raises(ValueError, match="axis"):
        g = mapper.Gemm(m=8, k=8, n=8)
        prog = program.lower(g, _choice(), CFG)
        program.shard_program(prog, ArrayMesh(2), axis="q")
