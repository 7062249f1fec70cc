"""Device ms per call in the SUMMA exchange's collectives: the ops under
the ``exchange`` named scope (core/spgemm.py)."""
from bench.spans import scope_device_ms_per_call


def read(run):
    return scope_device_ms_per_call(run, "exchange")
