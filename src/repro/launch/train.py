"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 50                       # laptop smoke run
    python -m repro.launch.train --arch qwen2-72b --shape train_4k \
        --multi-pod                              # real pods (or dry-run env)

Wires the full substrate: production mesh + sharding plan, sharded params
/optimizer states, deterministic data pipeline with prefetch, gradient
accumulation, optional gradient compression on the pod axis, atomic
checkpoints with auto-resume, and the straggler watchdog.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from .cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on a 1x1 mesh (CPU-runnable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--compress", choices=["none", "int8", "topk"],
                    default="none")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    from ..configs import get_config, get_smoke
    from ..configs.registry import SHAPES
    from ..models import Model, init_params
    from ..models.model import init_param_specs
    from ..train import (AdamWConfig, SyntheticLM, init_opt_state,
                         latest_step, make_train_step, restore_checkpoint,
                         save_checkpoint)
    from ..train.data import Prefetcher
    from .elastic import StepWatchdog
    from .mesh import make_plan, make_production_mesh

    if args.smoke:
        cfg = get_smoke(args.arch).scaled(vocab=2048)
        mesh = None
        plan = None
        B, S = args.batch, args.seq
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]
        B, S = shape["batch"], shape["seq"]
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        plan = make_plan(cfg, multi_pod=args.multi_pod, shape_kind="train",
                         batch=B)

    model = Model(cfg, plan)
    params = init_params(cfg, seed=0)
    opt = init_opt_state(params)
    if mesh is not None:
        pspecs = init_param_specs(cfg, plan)
        to_sharded = lambda tree, specs: jax.tree.map(
            lambda x, s: jax.device_put(x, jax.NamedSharding(mesh, s)),
            tree, specs)
        params = to_sharded(params, pspecs)
        opt = dict(m=to_sharded(opt["m"], pspecs),
                   v=to_sharded(opt["v"], pspecs), step=opt["step"])

    compressor = None
    if args.compress == "int8":
        from ..dist.compression import int8_quantize
        compressor = int8_quantize
    # (topk needs state threading; exposed via dist.compression API)

    opt_cfg = AdamWConfig(warmup_steps=20, decay_steps=max(args.steps, 100))
    step_fn = jax.jit(make_train_step(model, opt_cfg, accum=args.accum,
                                      compressor=compressor),
                      donate_argnums=(0, 1))
    data = SyntheticLM(cfg.vocab, S, B, seed=11)
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt), start = restore_checkpoint(args.ckpt_dir,
                                                  (params, opt))
        print(f"resumed from step {start}")
    pf = Prefetcher(data, start_step=start)
    wd = StepWatchdog()
    ctx = mesh if mesh is not None else _null()
    with ctx:
        for step in range(start, args.steps):
            wd.start()
            batch = jax.tree.map(jnp.asarray, pf.next())
            params, opt, metrics = step_fn(params, opt, batch)
            dt = wd.stop()
            if wd.is_straggling(dt):
                print(f"WARNING step {step}: straggler ({dt:.2f}s > "
                      f"{wd.budget():.2f}s budget) — launcher may re-slice")
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"({dt:.2f}s/step)")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, (params, opt))
    pf.close()


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
