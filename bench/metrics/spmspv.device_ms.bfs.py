"""Device ms per call in SpMSpV (core/spmv.py, core/spmv_local.py)."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "spmspv")
