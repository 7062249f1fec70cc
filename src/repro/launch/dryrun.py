import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# initialization. Placeholder host devices exist ONLY in this entrypoint —
# tests/benchmarks keep the real single device.

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) cell on the production meshes and extract the
roofline terms (deliverable g).

    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out-dir results/]

Success = .lower().compile() completes for the 16×16 single-pod mesh and
the 2×16×16 multi-pod mesh; memory_analysis() proves per-device fit and
cost_analysis() + HLO collective walk feed EXPERIMENTS.md §Roofline.
"""
import argparse   # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402
from .cache import use_compile_cache            # noqa: E402


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None, depth: int | None = None,
               unroll: bool = False, model_opts: dict | None = None,
               accum: int = 1, serve_bf16: bool = False):
    """Returns (step_fn, args, in_shardings, meta) ready to lower.

    depth/unroll: shallow UNROLLED probe variants for exact cost analysis
    (see roofline.extrapolate_raw).
    """
    import dataclasses as _dc
    from ..configs.registry import SHAPES, get_config, cell_valid
    from ..launch.mesh import make_plan, make_production_mesh
    from ..models import Model, init_params
    from ..models.config import active_param_count
    from ..models.model import init_param_specs
    from ..train import AdamWConfig, make_train_step
    from ..train.data import make_batch_specs
    from ..serve.decode import make_serve_step

    ok, why = cell_valid(arch, shape_name)
    if not ok:
        raise ValueError(f"cell ({arch}, {shape_name}) skipped: {why}")
    cfg = get_config(arch)
    if depth is not None:
        cfg = _dc.replace(cfg, n_layers=depth)
    shape = SHAPES[shape_name]
    kind = shape["kind"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(cfg, multi_pod=multi_pod, shape_kind=kind,
                     batch=shape["batch"], mesh=mesh)
    if overrides:
        plan = _dc.replace(plan, **overrides)
    if serve_bf16 and kind == "decode":
        cfg = _dc.replace(cfg, param_dtype="bfloat16")
    model = Model(cfg, plan, scan_unroll=unroll, **(model_opts or {}))
    params = init_params(cfg, abstract=True)
    pspecs = init_param_specs(cfg, plan)   # validates every spec against
    # the plan's axes/shapes — a bad plan fails loudly before lowering
    B, S = shape["batch"], shape["seq"]
    dp_total = plan.dp_size
    batch_shardable = B % dp_total == 0

    def batch_sharding(spec_tree):
        def leaf(s):
            nd = len(s.shape)
            if not batch_shardable:
                return NamedSharding(mesh, P(*(None,) * nd))
            if nd >= 1 and s.shape[0] == B:
                return NamedSharding(mesh, P(*(plan.dp(),) +
                                             (None,) * (nd - 1)))
            if nd >= 2 and s.shape[1] == B:      # pos3 (3, B, S)
                return NamedSharding(mesh, P(None, plan.dp(),
                                             *(None,) * (nd - 2)))
            return NamedSharding(mesh, P(*(None,) * nd))
        return jax.tree.map(leaf, spec_tree)

    ns = lambda tree: jax.tree.map(lambda sp: NamedSharding(mesh, sp), tree)
    tokens_per_step = B * S if kind == "train" else \
        (B * S if kind == "prefill" else B)
    n_active = active_param_count(cfg)
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens_per_step
    meta = dict(arch=arch, shape=shape_name, kind=kind, multi_pod=multi_pod,
                batch=B, seq=S, n_devices=mesh.size,
                active_params=n_active, model_flops=model_flops)

    if kind == "train":
        opt_cfg = AdamWConfig()
        step = make_train_step(model, opt_cfg, accum=accum)
        opt_specs = dict(m=pspecs, v=pspecs, step=P())
        opt_abstract = dict(
            m=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape,
                                                          jnp.float32),
                           params),
            v=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape,
                                                          jnp.float32),
                           params),
            step=jax.ShapeDtypeStruct((), jnp.int32))
        batch_specs = make_batch_specs(cfg, shape, plan)
        in_sh = (ns(pspecs), ns(opt_specs), batch_sharding(batch_specs))
        fn = jax.jit(step, in_shardings=in_sh, donate_argnums=(0, 1))
        args = (params, opt_abstract, batch_specs)
        return fn, args, meta, mesh

    if kind == "prefill":
        def prefill_fn(p, batch):
            logits, aux, _ = model.forward(p, batch, remat=False)
            return logits[:, -1, :]
        batch_specs = make_batch_specs(cfg, shape, plan)
        if cfg.kind != "encoder":
            batch_specs.pop("labels", None)
        else:
            batch_specs.pop("targets", None)
        in_sh = (ns(pspecs), batch_sharding(batch_specs))
        fn = jax.jit(prefill_fn, in_shardings=in_sh)
        return fn, (params, batch_specs), meta, mesh

    # decode
    serve = make_serve_step(model)
    caches = model.init_cache(B, S, abstract=True)
    cache_specs = _cache_specs(model, plan, caches)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    off = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_sh = NamedSharding(mesh, P(plan.dp(), None) if batch_shardable
                           else P(None, None))
    off_sh = NamedSharding(mesh, P(plan.dp()) if batch_shardable
                           else P(None))
    in_sh = (ns(pspecs), ns(cache_specs), tok_sh, off_sh)
    fn = jax.jit(serve, in_shardings=in_sh, donate_argnums=(1,))
    return fn, (params, caches, tok, off), meta, mesh


def _cache_specs(model, plan, caches):
    cfg = model.cfg
    specs = {}
    for pos, c in caches.items():
        if "k" in c:            # GQA kv cache (reps, B, S, KVH, hd)
            kv = plan.cache_spec("kv", dict(kvh=cfg.n_kv_heads, hd=cfg.hd))
            specs[pos] = dict(k=P(None, *kv), v=P(None, *kv),
                              offset=P(None))
        elif "c_kv" in c:       # MLA latent cache
            lat = plan.cache_spec("kv_flat", dict(x=cfg.kv_lora_rank))
            rope = plan.cache_spec("kv", dict(kvh=1, hd=cfg.qk_rope_dim))
            specs[pos] = dict(c_kv=P(None, *lat), k_rope=P(None, *rope),
                              offset=P(None))
        else:                   # SSM state
            st = plan.cache_spec("ssm", dict(h=cfg.ssm_heads))
            cv = plan.cache_spec("conv",
                                 dict(c=cfg.d_inner + 2 * cfg.ssm_state))
            specs[pos] = dict(conv=P(None, *cv), state=P(None, *st))
    return specs


# The production meshes the dry-run compiles for (on forced host devices)
# are TPU v5e pods: their roofline terms use that chip's peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch, shape_name, *, multi_pod, out_dir=None, overrides=None,
             verbose=True, probes=True, model_opts=None, accum=1,
             serve_bf16=False, tag_extra=""):
    from .roofline import (extrapolate_raw, raw_metrics, roofline_terms,
                           terms_from_raw)
    from ..configs.registry import get_config
    from ..models.model import period_of
    kw = dict(model_opts=model_opts, accum=accum, serve_bf16=serve_bf16)
    t0 = time.time()
    fn, args, meta, mesh = build_cell(arch, shape_name, multi_pod=multi_pod,
                                      overrides=overrides, **kw)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    print(compiled.memory_analysis())      # proves it fits
    from .roofline import cost_dict
    ca = cost_dict(compiled)               # FLOPs/bytes for §Roofline
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    if probes:
        # XLA counts scan bodies once — lower 2 shallow UNROLLED probes and
        # extrapolate linearly in depth (exact; see roofline.py)
        cfg_full = get_config(arch)
        period = period_of(cfg_full)
        reps = cfg_full.n_layers // period
        raws = []
        for d in (period, 2 * period):
            pf, pargs, _, pmesh = build_cell(
                arch, shape_name, multi_pod=multi_pod, overrides=overrides,
                depth=d, unroll=True, **kw)
            with pmesh:
                pcomp = pf.lower(*pargs).compile()
            raws.append(raw_metrics(pcomp))
        raw = extrapolate_raw(raws[0], raws[1], reps)
        rf = terms_from_raw(raw, n_devices=meta["n_devices"],
                            model_flops=meta["model_flops"],
                            device_kind=TARGET_DEVICE_KIND,
                            memory_stats=compiled.memory_analysis())
        rf["scanned_raw"] = raw_metrics(compiled)
        rf["probe_raws"] = raws
    else:
        rf = roofline_terms(compiled, n_devices=meta["n_devices"],
                            device_kind=TARGET_DEVICE_KIND,
                            model_flops=meta["model_flops"])
    result = dict(meta=meta, lower_s=t_lower, compile_s=t_compile, **rf)
    if verbose:
        ts = rf["terms_seconds"]
        print(f"[{arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}] "
              f"compute={ts['compute']:.4f}s memory={ts['memory']:.4f}s "
              f"collective={ts['collective']:.4f}s "
              f"dominant={rf['dominant']} "
              f"roofline_frac={rf['roofline_fraction']} "
              f"compile={t_compile:.1f}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}"
        if overrides:
            tag += "_" + "_".join(f"{k}={v}" for k, v in overrides.items())
        if tag_extra:
            tag += "_" + tag_extra
        with open(os.path.join(out_dir, f"dryrun_{tag}.json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out-dir", default="benchmarks/results")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--cast-early", action="store_true",
                    help="bf16 param cast before the sharded-use boundary")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over the data axes (serving plan)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="store params in bf16 for decode cells")
    ap.add_argument("--tag", default="", help="extra tag for the result file")
    ap.add_argument("--moe-ep", action="store_true",
                    help="shard_map expert-parallel MoE dispatch")
    args = ap.parse_args()
    from ..configs.registry import valid_cells
    overrides = {}
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.no_fsdp:
        overrides["fsdp_axes"] = ()
    if args.moe_ep:
        overrides["moe_ep"] = True
    overrides = overrides or None
    model_opts = dict(cast_early=True) if args.cast_early else None
    cells = valid_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                # probes (exact roofline) on the single-pod mesh — the
                # §Roofline table is single-pod; multi-pod proves the 'pod'
                # axis shards (compile success + scanned collective pattern)
                run_cell(arch, shape, multi_pod=mp, out_dir=args.out_dir,
                         overrides=overrides, probes=not mp,
                         model_opts=model_opts, accum=args.accum,
                         serve_bf16=args.serve_bf16, tag_extra=args.tag)
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print(f"dry-run OK: {len(cells) * len(meshes)} cells")


if __name__ == "__main__":
    main()
