"""Record the small v5e trace that ``test_bench_xplane.py`` reduces.

    python bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Run from the checkout's root on one TPU chip. Inside one ``bench.window``
annotation it runs a sort, a segment sum (a scatter) and the program's
Pallas segmented reduce, each in its own ``bench.call:*`` annotation and
separated by 20 ms sleeps, and prints every device op with its times.
Source paths in the trace are cut to start at ``src/`` or ``bench/``.
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

from repro.kernels.segreduce import segment_reduce_pallas     # noqa: E402

N, SEGMENTS, GAP_S = 1 << 16, 1000, 0.02


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r".*/(?=(src|bench)/)")
    ids = jnp.asarray(np.sort(np.random.default_rng(0).integers(
        0, SEGMENTS, N)).astype(np.int32))
    vals = jnp.ones(N, jnp.float32)
    sort = jax.jit(lambda x: jnp.sort(x[::-1]))
    segsum = jax.jit(lambda v, i: jax.ops.segment_sum(v, i, SEGMENTS))
    pallas = lambda: segment_reduce_pallas(vals, ids, SEGMENTS, "sum",
                                           interpret=False)
    calls = [("sort", lambda: sort(ids)), ("segment", lambda: segsum(
        vals, ids)), ("pallas", pallas)]
    for _, f in calls:                       # compile outside the trace
        jax.block_until_ready(f())
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        time.sleep(GAP_S)
        for name, f in calls:
            with TraceAnnotation(f"bench.call:{name}"):
                jax.block_until_ready(f())
            time.sleep(GAP_S)
    jax.profiler.stop_trace()
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copyfile(src, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        for line in plane.lines:
            if line.name in ("XLA Ops",) or (
                    plane.name == "/host:CPU"
                    and any(e.name.startswith("bench.")
                            for e in line.events)):
                for e in line.events:
                    print(f"{plane.name} | {line.name} | {e.start_ns!r} "
                          f"{e.duration_ns!r} | {e.name[:120]}")


if __name__ == "__main__":
    main(sys.argv[1])
