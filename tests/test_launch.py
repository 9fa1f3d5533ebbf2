"""Launch-layer tests: input_specs, elastic resume (re-shard restore),
multimodal serving, trainer resume via the CLI driver."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, reduced
from repro.configs.registry import ARCH_IDS, get_config
from repro.models import build_model

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# input_specs: abstract stand-ins for every cell (no device allocation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gemma-7b", "whisper-base",
                                  "internvl2-26b", "falcon-mamba-7b",
                                  "deepseek-v2-236b"])
@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k",
                                        "decode_32k"])
def test_input_specs_shapes(arch, shape_name):
    from repro.launch.dryrun import input_specs
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    spec = input_specs(arch, shape_name)
    batch = spec["batch"]
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(batch))
    if shape.kind == "decode":
        assert batch["tokens"].shape == (shape.global_batch, 1)
        assert "cache" in spec
        # cache leaves must be abstract too
        assert all(isinstance(x, jax.ShapeDtypeStruct)
                   for x in jax.tree.leaves(spec["cache"]))
    else:
        text = shape.seq_len
        if cfg.family == "vlm":
            text -= cfg.frontend_len
        assert batch["tokens"].shape == (shape.global_batch, text)
        if cfg.frontend != "none" and cfg.family in ("vlm", "encdec"):
            key = "patches" if cfg.family == "vlm" else "frames"
            assert batch[key].shape == (shape.global_batch,
                                        cfg.frontend_len, cfg.d_model)


def test_all_cells_enumerate():
    from repro.configs.registry import cells
    grid = cells(include_skipped=True)
    assert len(grid) == 40
    skips = [c for c in grid if c[2].startswith("SKIP")]
    assert len(skips) == 8
    assert all(c[1] == "long_500k" for c in skips)


# ---------------------------------------------------------------------------
# Elastic resume: restore onto explicit (different) shardings
# ---------------------------------------------------------------------------

def test_elastic_restore_onto_mesh(tmp_path):
    from repro.ckpt.manager import CheckpointManager
    from repro.dist import elastic

    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(64.0).reshape(8, 8),
            "b": jnp.ones((4,))}
    mgr.save(3, tree)

    mesh = jax.make_mesh((1,), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data")),
                 "b": NamedSharding(mesh, P())}
    restored, step = elastic.resume(mgr, jax.eval_shape(lambda: tree),
                                    shardings)
    assert step == 3
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


def test_elastic_resume_empty_dir(tmp_path):
    from repro.ckpt.manager import CheckpointManager
    from repro.dist import elastic
    mgr = CheckpointManager(str(tmp_path))
    tree, step = elastic.resume(mgr, {}, None)
    assert tree is None and step == 0


# ---------------------------------------------------------------------------
# Multimodal serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["whisper-base", "internvl2-26b"])
def test_engine_multimodal(arch):
    from repro.serve.engine import Engine, ServeConfig
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, ServeConfig(max_len=24))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    kwargs = {}
    if cfg.family == "encdec":
        kwargs["frames"] = np.asarray(rng.standard_normal(
            (2, cfg.frontend_len, cfg.d_model)), np.float32) * 0.02
    else:
        kwargs["prefix_embeds"] = np.asarray(rng.standard_normal(
            (2, cfg.frontend_len, cfg.d_model)), np.float32) * 0.02
    toks = engine.generate(prompts, steps=4, **kwargs)
    assert toks.shape == (2, 4)


# ---------------------------------------------------------------------------
# CLI trainer: checkpoint + resume continues from the saved step
# ---------------------------------------------------------------------------

def test_train_cli_resume(tmp_path):
    # run in subprocesses: the CLI owns donation + mesh state and must not
    # share a process with other jit caches (mirrors real usage)
    import os
    import subprocess
    import sys

    # strip the 512-fake-device flag that importing launch.dryrun (in the
    # input_specs tests above) leaves in this process's environ
    env = dict(os.environ, PYTHONPATH="src", XLA_FLAGS="")
    args = ["--arch", "minitron-4b", "--reduced", "--reduced-layers", "2",
            "--reduced-dmodel", "32", "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
            "--log-every", "100"]

    def run(extra):
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.train"] + args + extra,
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=240)

    r1 = run(["--steps", "4"])
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "step     0" in r1.stdout
    r2 = run(["--steps", "6", "--resume", "auto"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 4" in r2.stdout
    assert "step     0 " not in r2.stdout   # did not restart from scratch
