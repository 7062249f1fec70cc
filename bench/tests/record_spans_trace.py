"""Record the small v5e trace that ``test_bench_spans.py`` reduces.

    python bench/tests/record_spans_trace.py bench/tests/data/spans.xplane.pb

Run from the checkout's root on one TPU chip. Inside one ``bench.window``
annotation and one ``bench.call:spans`` annotation it opens the program's
own spans (``repro.obs``) the way a planned product does: ``plan.spgemm``
around the ``program.demo`` span of a shard_map program that is re-traced
(its body sleeps as it is traced), read back from the persistent compile
cache and run, then ``dist.fetch`` around a fetch of its output. Each span
holds a 20-ms sleep, so the device idles under each layer, under the call
alone and outside the call. The program's sort runs under the named scope
``exchange``. Prints every event of the main host line and every device
op with its op path. Source paths in the trace are cut to start at
``src/`` or ``bench/``.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.profiler import TraceAnnotation                      # noqa: E402
from jax.sharding import PartitionSpec as P                   # noqa: E402

from repro import obs                                         # noqa: E402
from repro.core import compat                                 # noqa: E402

N, GAP_S = 1 << 16, 0.02


def demo(mesh):
    """A shard_map program made anew on every call, as the engine's are."""
    def body(x):
        time.sleep(GAP_S)               # traced on every call: the program
        with obs.span("exchange"):
            return jnp.sort(x[::-1])
    return compat.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P())


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_spans_trace: needs a TPU")
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r".*/(?=(src|bench)/)")
    cache = tempfile.mkdtemp()
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    mesh = compat.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    x = jnp.arange(N, dtype=jnp.float32)
    jax.block_until_ready(demo(mesh)(x))     # compile outside the trace
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        time.sleep(GAP_S)                                 # outside the call
        with TraceAnnotation("bench.call:spans"):
            time.sleep(GAP_S)                             # the call alone
            with obs.span("plan.spgemm"):
                time.sleep(GAP_S)                         # the planner
                y = jax.block_until_ready(demo(mesh)(x))  # program.demo
                time.sleep(GAP_S)
            with obs.span("dist.fetch"):
                time.sleep(GAP_S)                         # the fetch
                np.asarray(y)
            time.sleep(GAP_S)
        time.sleep(GAP_S)
    jax.profiler.stop_trace()
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copyfile(src, out)
    shutil.rmtree(tmp)
    shutil.rmtree(cache)
    from bench import xplane
    from bench.spans import host_spans
    w0, w1, spans = host_spans(out)
    print(f"window {w0!r} {w1 - w0!r}")
    for s, e, n in spans:
        print(f"host | {s!r} {e - s!r} | {n[:100]}")
    red = xplane.reduce(out, "bench.window")
    for o in red.ops:
        print(f"{o.device} | {o.start_ns!r} {o.dur_ns!r} | "
              f"{xplane._short(o.name)} | {o.stats.get('tf_op', '')}")
    print(f"{Path(out).stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
