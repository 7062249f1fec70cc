"""End-to-end training driver: data pipeline → train loop → checkpoint →
crash-resume. Defaults to a laptop-sized model; ``--arch`` selects any
assigned architecture's smoke config, ``--prod`` uses the full config
(needs the production mesh).

    PYTHONPATH=src python examples/train_lm.py --steps 60
    PYTHONPATH=src python examples/train_lm.py --steps 60 --resume  # later

The loop demonstrates the fault-tolerance contract (DESIGN.md §8):
deterministic data by step, atomic checkpoints, auto-resume from the
latest complete step.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_smoke, get_config
from repro.models import Model, init_params
from repro.models.config import ModelConfig
from repro.train import (AdamWConfig, SyntheticLM, init_opt_state,
                         latest_step, make_train_step, restore_checkpoint,
                         save_checkpoint)
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="assigned arch id (smoke config); default: custom "
                         "~20M decoder")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    if args.arch:
        cfg = get_smoke(args.arch).scaled(vocab=2048)
    else:
        cfg = ModelConfig(name="demo-20m", kind="decoder", n_layers=4,
                          d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
                          d_ff=1024, vocab=2048).validate()
    model = Model(cfg)
    params = init_params(cfg, seed=0)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params / 1e6:.1f}M params")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          decay_steps=max(args.steps, 100))
    step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    opt = init_opt_state(params)
    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=7)

    start = 0
    if args.resume and latest_step(args.ckpt_dir) is not None:
        (params, opt), start = restore_checkpoint(
            args.ckpt_dir, (params, opt))
        print(f"resumed from step {start}")

    t0 = time.time()
    for step in range(start, args.steps):
        batch = jax.tree.map(jnp.asarray, data.batch_at(step))
        params, opt, metrics = step_fn(params, opt, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({(time.time() - t0):.1f}s)")
        if (step + 1) % args.ckpt_every == 0:
            path = save_checkpoint(args.ckpt_dir, step + 1, (params, opt))
            print(f"checkpoint -> {path}")
    print("done. resume anytime with --resume.")


if __name__ == "__main__":
    main()
