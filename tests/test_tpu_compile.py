"""Compile the serving path's Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a described (not
attached) ``v5e:2x2`` topology, so these tests catch what interpret mode
cannot: blocks off the (8, 128) tiling, unaligned lane slices, reshapes
Mosaic cannot lower, scoped-VMEM overruns.  Geometry comes from the real
launch path -- ``compile_program`` / ``compile_segment`` over the
minitron-4b executables at published widths (d_model 3072, 24/8 heads of
128, d_ff 9216, vocab 256000) under ``feather_config(4, 16)`` -- and each
kernel is compiled with ``interpret=False`` from shapes alone.

The topology is described inside a fixture (never at import time): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends.pallas_backend import compile_program, compile_segment
from repro.configs.base import ShapeConfig
from repro.configs.feather import feather_config
from repro.kernels import ops
from repro.runtime import ModelExecutable, ProgramCache

ARCH = "minitron-4b"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def executables():
    cfg = feather_config(4, 16)
    cache = ProgramCache()

    def build(seq_len, kind):
        return ModelExecutable.for_cell(
            ARCH, ShapeConfig(kind, seq_len=seq_len, global_batch=1,
                              kind=kind),
            cfg, cache=cache, reduce_model=False)

    return {"prefill": build(256, "prefill"), "decode": build(16, "decode")}


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _step(ex, name):
    return next(s for s in ex.steps if s.op.gemm.name == name)


def _compile_gemm(program, sharding):
    comp = compile_program(program)
    g = program.gemm
    fn = functools.partial(
        ops.nest_gemm, bm=comp.bm, bk=comp.bk, bn=comp.bn, interpret=False,
        out_block_t=comp.out_block_t, act=comp.fused_act)
    return _compile(fn, sharding, (g.m, g.k), (g.k, g.n))


@pytest.mark.parametrize("phase,name", [
    ("prefill", "wq"), ("prefill", "mlp.down"), ("decode", "lm_head")])
def test_nest_gemm_compiles(executables, one_chip, phase, name):
    program = _step(executables[phase], name).program
    assert _compile_gemm(program, one_chip) is not None


def test_bucketed_decode_gemm_compiles(executables, one_chip):
    """The bucket-4 batch plan's ``wq``: four stacked decode rows."""
    ex = executables["decode"]
    plan = ex.batch_plan(4)
    seg = next(b for b in plan.segments
               if b.kind == "static" and b.indices == [_step(ex, "wq").index])
    program = seg.programs[0]
    assert program.gemm.m == 4
    assert _compile_gemm(program, one_chip) is not None


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_fused_segment_across_adapt_compiles(executables, one_chip, phase):
    """The ``[wv, qk]`` fused segment: its in-kernel adapt (head split)
    and its K-tiled slab reads must lower under Mosaic."""
    ex = executables[phase]
    seg = next(s for s in ex.segments if s.fused is not None
               and any(s.fused.adapts))
    names = [ex.steps[i].op.gemm.name for i in seg.indices]
    assert names == ["wv", "qk"]
    comp = compile_segment(seg.fused)

    def fn(x, *ws):
        return ops.fused_chain(x, list(ws), bm=comp.bm, bks=comp.layer_bks,
                               acts=comp.acts, adapts=comp.adapts,
                               dims=comp.dims, interpret=False,
                               out_dtype=jnp.float32)

    m, k, _ = comp.dims[0]
    shapes = [(m, k)] + [(d[1], d[2]) for d in comp.dims]
    assert _compile(fn, one_chip, *shapes) is not None


def test_flash_decode_proj_compiles(executables, one_chip):
    """Batched decode attention + adapt-cycled output projection at the
    decode widths: 4 requests, one query row of head_dim 128, a KV
    extent of 256, Wo 3072 x 3072."""
    ex = executables["decode"]
    wo = _step(ex, "wo").op.gemm
    b, sq, d, skv = 4, 1, 128, 256

    def fn(q, k, v, w):
        return ops.flash_decode_proj(q, k, v, w, jnp.full((b,), skv),
                                     m_out=wo.m, k_out=wo.k,
                                     interpret=False)

    assert _compile(fn, one_chip, (b, sq, d), (b, skv, d), (b, skv, d),
                    (wo.k, wo.n)) is not None
