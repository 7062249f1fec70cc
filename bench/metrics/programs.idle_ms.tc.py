"""Device-idle ms per call under the program's ``program.*`` host spans:
tracing, lowering, cache reads and dispatch of shard_map programs."""
from bench.spans import idle_ms_per_call


def read(run):
    return idle_ms_per_call(run, "programs")
