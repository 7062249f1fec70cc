"""Device ms per call in sort ops (the sort/merge layer, core/merge.py)."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "sort")
