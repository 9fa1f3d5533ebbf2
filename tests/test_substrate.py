"""Substrate tests: checkpointing (atomic/async/elastic), optimizer,
data pipeline determinism, sharding rules, gradient compression, serving
engine end-to-end."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.ckpt.manager import CheckpointManager
from repro.configs.base import ShapeConfig, reduced
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import sharding as shlib
from repro.dist.compression import fake_quantize_int8
from repro.models import build_model
from repro.serve.engine import Engine, ServeConfig
from repro.train import optimizer as optlib


# ---------------------------------------------------------------------------
# Checkpoint manager
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.float32),
                  "d": [jnp.zeros((2, 2)), jnp.full((3,), 7.0)]}}


def test_ckpt_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(10, tree)
    assert mgr.latest_step() == 10
    restored = mgr.restore(10, jax.eval_shape(lambda: tree))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b),
                 tree, restored)


def test_ckpt_async_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, {"x": jnp.full((4,), float(s))})
    mgr.wait()
    assert mgr.all_steps() == [3, 4]
    r = mgr.restore(4, jax.eval_shape(lambda: {"x": jnp.zeros((4,))}))
    assert float(r["x"][0]) == 4.0


def test_ckpt_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="shape mismatch"):
        mgr.restore(1, jax.eval_shape(lambda: {"x": jnp.zeros((5,))}))


def test_ckpt_atomicity_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, _tree())
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = optlib.OptimizerConfig(peak_lr=0.1, warmup_steps=5,
                                 total_steps=200, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = optlib.init(params)
    loss = lambda p: jnp.sum(jnp.square(p["w"] - 1.0))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = optlib.update(cfg, params, g, state)
    assert float(loss(params)) < 1e-2


def test_adamw_master_weights_decouple_dtype():
    cfg = optlib.OptimizerConfig(peak_lr=1e-2, warmup_steps=1,
                                 total_steps=10)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = optlib.init(params)
    g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    params, state, _ = optlib.update(cfg, params, g, state)
    assert params["w"].dtype == jnp.bfloat16
    assert state["master"]["w"].dtype == jnp.float32


def test_grad_clip():
    tree = {"a": jnp.full((100,), 10.0)}
    clipped, norm = optlib.clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(100.0)
    assert float(optlib.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_per_step():
    cfg = reduced(get_config("gemma-7b"))
    shape = ShapeConfig("t", 64, 4, "train")
    d1 = SyntheticLM(DataConfig(seed=1), cfg, shape)
    d2 = SyntheticLM(DataConfig(seed=1), cfg, shape)
    np.testing.assert_array_equal(d1.batch(17)["tokens"],
                                  d2.batch(17)["tokens"])
    assert not np.array_equal(d1.batch(17)["tokens"],
                              d1.batch(18)["tokens"])


# ---------------------------------------------------------------------------
# Sharding rules (AbstractMesh: no devices needed)
# ---------------------------------------------------------------------------

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_tp_and_fsdp():
    # (embed, ffn): FSDP on data + TP on model
    assert shlib.spec_for(("embed", "ffn"), (8192, 29568), MESH) == \
        P("data", "model")
    # vocab embedding
    assert shlib.spec_for(("vocab", "embed"), (152064, 8192), MESH) == \
        P("model", "data")


def test_spec_kv_heads_fallback_to_seq():
    # qwen2: 8 kv heads % 16 != 0 -> heads replicated, kvseq sharded
    spec = shlib.spec_for(("layers", "batch", "kvseq", "kv_heads",
                           "head_dim"), (80, 128, 32768, 8, 128), MESH)
    assert spec == P(None, "data", "model")


def test_spec_experts_divisibility():
    # TP-inside-expert policy (§Perf iteration 6b): experts stay unsharded
    # and each expert's ffn dim is TP-sharded -- per-device weight bytes
    # match EP when both divide, and the dispatch/combine stays row-local.
    assert shlib.spec_for(("experts", "embed", "expert_ffn"),
                          (160, 5120, 1536), MESH) == \
        P(None, "data", "model")
    assert shlib.spec_for(("experts", "embed", "expert_ffn"),
                          (40, 1536, 512), MESH) == \
        P(None, "data", "model")


def test_inference_rules_drop_fsdp():
    # serving replicates weights over data (no FSDP gather-at-use)
    assert shlib.spec_for(("embed", "ffn"), (8192, 29568), MESH,
                          shlib.INFERENCE_RULES) == P(None, "model")
    # TP/SP unchanged
    assert shlib.spec_for(("layers", "batch", "kvseq", "kv_heads",
                           "head_dim"), (80, 128, 32768, 8, 128),
                          MESH, shlib.INFERENCE_RULES) == \
        P(None, "data", "model")


def test_spec_pod_axis_batch():
    spec = shlib.spec_for(("batch", "embed"), (512, 1024), POD_MESH)
    assert spec == P(("pod", "data"), None) or spec == P(("pod", "data"))


def test_no_axis_used_twice():
    spec = shlib.spec_for(("ffn", "ssm_inner"), (4096, 4096), MESH)
    flat = [s for s in spec if s is not None]
    assert len(set(flat)) == len(flat)


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

def test_int8_fake_quant_error_bounded():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                    jnp.float32)
    q = fake_quantize_int8(x)
    err = jnp.abs(q - x).max()
    assert float(err) <= float(jnp.abs(x).max()) / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# Serving engine end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["minitron-4b", "falcon-mamba-7b"])
def test_engine_generates(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_len=24))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    toks = engine.generate(prompts, steps=6)
    assert toks.shape == (2, 6)
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()


def test_engine_greedy_matches_rerun():
    """Greedy decode is deterministic."""
    cfg = reduced(get_config("minitron-4b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_len=24))
    prompts = np.full((1, 8), 3, np.int32)
    a = engine.generate(prompts, steps=5)
    b = engine.generate(prompts, steps=5)
    np.testing.assert_array_equal(a, b)
