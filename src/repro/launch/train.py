"""Training launcher (end-to-end driver, deliverable (b)).

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b \
        --steps 300 --reduced --batch 8 --seq 256 --ckpt-dir /tmp/ckpt \
        --resume auto

On this CPU container use --reduced (family-preserving ~100M-and-below
models); on real hardware drop it and the production mesh/shardings apply
unchanged.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.configs.base import ShapeConfig, reduced as reduce_cfg
from repro.configs.registry import ARCH_IDS, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import elastic
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import build_model
from repro.train import optimizer as optlib
from repro.train.trainer import TrainConfig, make_train_step, shardings_for


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--reduced-layers", type=int, default=4)
    ap.add_argument("--reduced-dmodel", type=int, default=256)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None, choices=[None, "auto"])
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, layers=args.reduced_layers,
                         d_model=args.reduced_dmodel, vocab=2048)
    model = build_model(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())

    tcfg = TrainConfig(
        opt=optlib.OptimizerConfig(peak_lr=args.lr,
                                   warmup_steps=min(20, args.steps // 5 + 1),
                                   total_steps=args.steps),
        grad_accum=args.grad_accum)
    step_fn = make_train_step(model, tcfg)

    data = SyntheticLM(DataConfig(vocab_size=min(cfg.vocab_size, 4096)),
                       cfg, shape)
    batch0 = data.batch(0)
    batch_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch0)
    with jax.set_mesh(mesh):
        (p_sh, o_sh, b_sh), _ = shardings_for(model, mesh, batch_spec)
        jit_step = jax.jit(step_fn, in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, None),
                           donate_argnums=(0, 1))
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), p_sh)
        opt_state = jax.device_put(optlib.init(params), o_sh)

        start = 0
        manager = None
        if args.ckpt_dir:
            manager = CheckpointManager(args.ckpt_dir)
            if args.resume == "auto":
                state = {"params": params, "opt": opt_state}
                sh = {"params": p_sh, "opt": o_sh}
                restored, start = elastic.resume(manager, state, sh)
                if restored is not None:
                    params, opt_state = restored["params"], restored["opt"]
                    print(f"resumed from step {start}")

        t0 = time.time()
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.tree.map(float, metrics)
                print(f"step {step:5d} loss {m['loss']:.4f} "
                      f"ppl {m.get('perplexity', float('nan')):.1f} "
                      f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e} "
                      f"({(time.time()-t0)/max(step-start+1,1):.2f}s/step)")
            if manager and args.ckpt_every and step and \
                    step % args.ckpt_every == 0:
                manager.save_async(step, {"params": params,
                                          "opt": opt_state})
        if manager:
            manager.save(args.steps, {"params": params, "opt": opt_state})
            manager.wait()
    print("done")


if __name__ == "__main__":
    main()
