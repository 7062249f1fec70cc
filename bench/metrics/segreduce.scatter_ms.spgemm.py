"""Device ms per call in scatter / segment ops (XLA segment ops)."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "scatter_segment")
