"""Device-idle ms per call under the planner's ``plan.*`` host spans, not
under a ``program.*`` or ``dist.fetch`` span inside them."""
from bench.spans import idle_ms_per_call


def read(run):
    return idle_ms_per_call(run, "plan")
