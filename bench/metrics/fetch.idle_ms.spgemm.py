"""Device-idle ms per call under the ``dist.fetch`` host spans: the
product's fetch to the host (core/dist.py)."""
from bench.spans import idle_ms_per_call


def read(run):
    return idle_ms_per_call(run, "fetch")
