"""Production serving launcher: batched prefill + decode on the mesh.

    python -m repro.launch.serve --arch granite-3-2b --smoke \
        --batch 4 --prompt-len 32 --tokens 32

On real pods: drop --smoke; the plan switches to the serving layout
(TP-only bf16 params, sequence-sharded KV cache — §Perf cell C).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from .cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=0)
    args = ap.parse_args()

    from ..configs import get_config, get_smoke
    from ..models import Model, init_params
    from ..serve import greedy_generate
    from .mesh import make_plan, make_production_mesh

    if args.smoke:
        cfg = get_smoke(args.arch)
        model = Model(cfg)
    else:
        cfg = get_config(args.arch).scaled(param_dtype="bfloat16")
        mesh = make_production_mesh()
        plan = make_plan(cfg, shape_kind="decode", batch=args.batch,
                         mesh=mesh)
        import dataclasses
        plan = dataclasses.replace(plan, fsdp_axes=())
        model = Model(cfg, plan)
    params = init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab,
                                      (args.batch, args.prompt_len)),
                         jnp.int32)
    max_len = args.max_len or (args.prompt_len + args.tokens + 1)
    t0 = time.time()
    out = greedy_generate(model, params, prompt, max_len, args.tokens)
    dt = time.time() - t0
    print(f"{cfg.name}: {args.batch}×{args.tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s incl compile)")
    print("first sequence:", np.asarray(out[0])[:24])


if __name__ == "__main__":
    main()
