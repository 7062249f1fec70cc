"""repro/dist/shardings: per-arch spec coverage, plan derivation,
divisibility validation, and reproducible parameter init."""
import os
import subprocess
import sys

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import ARCHS, get_config, get_smoke
from repro.dist.shardings import (ShardingError, ShardingPlan,
                                  spec_for_param, validate_spec,
                                  validate_spec_tree)
from repro.launch.mesh import make_plan
from repro.models.model import init_param_specs, param_shapes

MESH_2D = AbstractMesh((16, 16), ("data", "model"))
MESH_3D = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def walk(shapes, specs, prefix=""):
    assert isinstance(specs, dict) == isinstance(shapes, dict), prefix
    if isinstance(shapes, dict):
        assert set(specs) == set(shapes), (prefix, set(specs) ^ set(shapes))
        for k in shapes:
            yield from walk(shapes[k], specs[k],
                            f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tuple(shapes), specs


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [MESH_2D, MESH_3D],
                         ids=["16x16", "2x16x16"])
def test_specs_congruent_and_divisible(arch, mesh):
    cfg = get_config(arch)
    plan = make_plan(cfg, mesh=mesh)
    sizes = dict(mesh.shape)
    shapes = param_shapes(cfg)
    specs = init_param_specs(cfg, plan)
    n_sharded = 0
    for path, shape, spec in walk(shapes, specs):
        assert isinstance(spec, P), (path, spec)
        assert len(spec) <= len(shape), (path, shape, spec)
        used = []
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                assert ax in sizes, (path, ax)       # axis exists on mesh
                assert ax not in used, (path, spec)  # used at most once
                used.append(ax)
                total *= sizes[ax]
            assert shape[d] % total == 0, (path, shape, spec)
            n_sharded += 1
        if path.startswith("blocks/"):
            assert spec[0] is None, (path, spec)     # scanned reps dim
        assert "pod" not in used, (path, spec)       # pod = pure DP
    assert n_sharded > 0
    # the module's own validator agrees
    validate_spec_tree(specs, shapes, plan)


@pytest.mark.parametrize("arch", ARCHS)
def test_every_weight_matrix_is_sharded(arch):
    """No replicated-fallback: every ≥2-D parameter carries at least one
    mesh axis (1-D norm/bias vectors may stay whole)."""
    cfg = get_config(arch)
    plan = make_plan(cfg, mesh=MESH_2D)
    for path, shape, spec in walk(param_shapes(cfg),
                                  init_param_specs(cfg, plan)):
        base = shape[1:] if path.startswith("blocks/") else shape
        if len(base) >= 2:
            assert any(e is not None for e in spec), (path, shape, spec)


def test_unknown_param_fails_loudly():
    cfg = get_config("qwen2-72b")
    plan = make_plan(cfg, mesh=MESH_2D)
    with pytest.raises(ShardingError, match="no sharding rule"):
        spec_for_param("blocks/pos0/mystery_w", (4, 16, 16), cfg, plan)
    with pytest.raises(ShardingError, match="no sharding rule"):
        spec_for_param("mystery_top", (16, 16), cfg, plan)


def test_indivisible_dim_fails_loudly():
    cfg = get_config("qwen2-72b")
    plan = make_plan(cfg, mesh=MESH_2D)
    with pytest.raises(ShardingError, match="not divisible"):
        spec_for_param("blocks/pos0/wq", (1, 8192, 100), cfg, plan)
    with pytest.raises(ShardingError, match="not divisible"):
        validate_spec(P("model"), (100,), plan, "x")
    with pytest.raises(ShardingError, match="not on this plan's mesh"):
        validate_spec(P("bogus_axis"), (16,), plan, "x")
    with pytest.raises(ShardingError, match="two dims"):
        validate_spec(P("model", "model"), (16, 16), plan, "x")


def test_plan_derived_from_mesh_shape():
    """dp_size / model_size follow the mesh — no hard-coded 32/16/16."""
    cfg = get_config("granite-3-2b")
    small = AbstractMesh((4, 2), ("data", "model"))
    plan = make_plan(cfg, mesh=small)
    assert plan.dp_size == 4 and plan.model_size == 2
    assert plan.dp_axes == ("data",) and plan.fsdp_axes == ("data",)
    tri = AbstractMesh((3, 4, 2), ("pod", "data", "model"))
    plan3 = make_plan(cfg, mesh=tri)
    assert plan3.dp_size == 12 and plan3.dp_axes == ("pod", "data")
    assert plan3.fsdp_axes == ("data",)      # pod stays pure DP
    assert plan3.dp() == ("pod", "data")
    # production fallback without a mesh keeps the paper grids
    assert make_plan(cfg).dp_size == 16
    assert make_plan(cfg, multi_pod=True).dp_size == 32
    with pytest.raises(ValueError, match="model"):
        make_plan(cfg, mesh=AbstractMesh((4, 2), ("a", "b")))


def test_context_parallel_cache_layout():
    cfg = get_config("mamba2-2.7b")
    # decode with batch < dp: sequence-sharded cache, unsharded batch
    plan = make_plan(cfg, shape_kind="decode", batch=1, mesh=MESH_2D)
    assert plan.context_parallel
    assert plan.cache_spec("kv", dict(kvh=8, hd=128)) == \
        (None, "data", None, "model")
    assert plan.cache_spec("ssm", dict(h=80)) == \
        (None, "model", None, None)
    assert plan.act_spec() == P(None, None, None)
    # decode with batch ≥ dp: batch-sharded cache
    plan = make_plan(cfg, shape_kind="decode", batch=128, mesh=MESH_2D)
    assert not plan.context_parallel
    assert plan.cache_spec("kv", dict(kvh=8, hd=128)) == \
        ("data", None, None, "model")
    # GQA head count ≥ model size shards heads, not head_dim
    assert plan.cache_spec("kv", dict(kvh=16, hd=128)) == \
        ("data", None, "model", None)
    assert plan.cache_spec("kv_flat", dict(x=512)) == \
        ("data", None, "model")
    assert plan.cache_spec("conv", dict(c=5376)) == \
        ("data", None, "model")
    with pytest.raises(ShardingError, match="cache kind"):
        plan.cache_spec("bogus", {})


def test_moe_ep_regroups_expert_weights():
    cfg = get_config("qwen2-moe-a2.7b")
    plan = make_plan(cfg, mesh=MESH_2D)
    ep = make_plan(cfg, mesh=MESH_2D, moe_ep=True)
    E = 64                                   # 60 routed padded to 64
    shp = (1, E, cfg.d_model, cfg.d_ff)
    assert spec_for_param("blocks/pos0/we_g", shp, cfg, plan) == \
        P(None, "model", None, "data")
    # EP regrouping: weights stay whole per expert shard (shard_map
    # consumes P('model', None, None))
    assert spec_for_param("blocks/pos0/we_g", shp, cfg, ep) == \
        P(None, "model", None, None)
    assert ep.ep_spec() == P("model", None, None)


def test_serving_layout_drops_fsdp():
    import dataclasses
    cfg = get_config("qwen2-72b")
    plan = dataclasses.replace(make_plan(cfg, mesh=MESH_2D), fsdp_axes=())
    spec = spec_for_param("blocks/pos0/wq", (1, 8192, 8192), cfg, plan)
    assert spec == P(None, None, "model")
    assert spec_for_param("blocks/pos0/ln1", (1, 8192), cfg, plan) == \
        P(None, None)


def test_smoke_configs_shard_on_small_mesh():
    """The same rules hold for the reduced smoke configs on a test-sized
    mesh (every smoke dim divides 2×2)."""
    mesh = AbstractMesh((2, 2), ("data", "model"))
    for arch in ARCHS:
        cfg = get_smoke(arch)
        plan = make_plan(cfg, mesh=mesh)
        validate_spec_tree(init_param_specs(cfg, plan), param_shapes(cfg),
                           plan)


_INIT_DIGEST = r"""
import numpy as np
from repro.configs.registry import get_smoke
from repro.models.model import init_params
params = init_params(get_smoke("jamba-1.5-large-398b"), seed=3)
acc = 0.0
def fold(t, pre=""):
    global acc
    for k in sorted(t):
        v = t[k]
        if isinstance(v, dict):
            fold(v, pre + k + "/")
        else:
            acc += float(np.abs(np.asarray(v, np.float64)).sum())
fold(params)
print(f"{acc:.10e}")
"""


def test_init_reproducible_across_hash_seeds():
    """init_params must not depend on Python's per-process hash salt:
    two processes with different PYTHONHASHSEED get identical params."""
    root = os.path.join(os.path.dirname(__file__), "..")
    outs = []
    for hs in ("0", "424242"):
        env = dict(os.environ, PYTHONHASHSEED=hs,
                   PYTHONPATH=os.path.join(root, "src"))
        proc = subprocess.run([sys.executable, "-c", _INIT_DIGEST],
                              capture_output=True, text=True, env=env,
                              cwd=root, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout.strip())
    assert outs[0] == outs[1], outs
