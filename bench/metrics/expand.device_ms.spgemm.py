"""Device ms per call in the local expand of SpGEMM (core/local_spgemm.py)."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "local_expand")
