"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles a kernel for one chip of
a ``v5e:2x2`` topology description, so Mosaic refuses here what it would
refuse on the chip (block shapes against the HBM tiling, dtypes the vector
unit cannot reduce, scoped-memory overruns). The segmented reduce is
compiled at the size chip_smoke.py reaches: a 2^20-entry stream into
32,768 segments, for each monoid tag the engine dispatches to it.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU compiler's library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.coo import COO
from repro.core.local_spgemm import _expand
from repro.core.semiring import ARITHMETIC
from repro.kernels.segreduce import segment_reduce_pallas
from repro.kernels.semiring_matmul import semiring_matmul

STREAM = 1 << 20
SEGMENTS = 32768
# the local expand at the benchmark's largest product buffer
# (tc.urand15's 2^25 slots): a 2^20-entry A against a 2^16-entry B
EXPAND_SLOTS = 1 << 25
EXPAND_A, EXPAND_B = 1 << 20, 1 << 16

# (tag, value dtype, identity): PLUS on f32, MIN_INT on int32, and
# BOOLEAN's LOR, which the engine tags "max" on bool values
SEGREDUCE_TAGS = [("sum", jnp.float32, None),
                  ("min", jnp.int32, 2**31 - 1),
                  ("max", jnp.bool_, False)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n,s", [(STREAM, SEGMENTS), (1000, 300)],
                         ids=["real", "small"])
@pytest.mark.parametrize("tag,dtype,identity", SEGREDUCE_TAGS,
                         ids=[t for t, _, _ in SEGREDUCE_TAGS])
def test_segreduce_compiles(one_chip, tag, dtype, identity, n, s):
    vals = jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda v, i: segment_reduce_pallas(v, i, s, tag, identity=identity,
                                           interpret=False), vals, ids)
    out = compiled.out_info
    assert out.shape == (s,) and out.dtype == dtype


def test_semiring_matmul_compiles(one_chip):
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda x, y: semiring_matmul(x, y, kind="plus_times",
                                     interpret=False), a, a)
    assert compiled.out_info.shape == (512, 512)


def _while_shapes(hlo: str):
    """Element counts of the arrays each ``while`` op of an HLO text
    carries, one list per op."""
    out = []
    for line in hlo.splitlines():
        if " while(" not in line:
            continue
        dims = re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", line.split(" while(")[0])
        out.append([math.prod(int(x) for x in d.split(",") if x)
                    for d in dims])
    return out


def test_expand_has_no_loop_over_slots(one_chip):
    """The slot -> owner map of the local expand is a scatter and a running
    max, not a loop over the product slots: no ``while`` op of the
    compiled expand carries a 2^25-slot array (the operand-side range
    searches over 2^20 queries may loop)."""
    def coo(n):
        return (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))

    def fn(a, b):
        a = COO(*a, shape=(32768, 32768), order="none")
        b = COO(*b, shape=(32768, 32768), order="row")
        return _expand(a, b, ARITHMETIC, EXPAND_SLOTS)

    compiled = jax.jit(fn).lower(coo(EXPAND_A), coo(EXPAND_B)).compile()
    assert compiled.out_info[0].shape == (EXPAND_SLOTS,)
    loops = _while_shapes(compiled.as_text())
    assert not any(EXPAND_SLOTS in sizes for sizes in loops), loops
