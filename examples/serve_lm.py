"""Serving example: batched prefill + greedy decode with KV caches.

    PYTHONPATH=src python examples/serve_lm.py --arch granite-3-2b
(uses the smoke config of the chosen arch; --tokens controls generation)
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_smoke
from repro.models import Model, init_params
from repro.serve import greedy_generate
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    model = Model(cfg)
    params = init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    max_len = args.prompt_len + args.tokens + 1
    t0 = time.time()
    out = greedy_generate(model, params, prompt, max_len, args.tokens)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} "
          f"generated {args.tokens} tokens/seq in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s incl compile)")
    print("sample token ids:", np.asarray(out[0])[:16])


if __name__ == "__main__":
    main()
