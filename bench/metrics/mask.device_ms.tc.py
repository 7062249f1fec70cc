"""Device ms per call in the mask probe (core/mask.py)."""
from bench.readers import device_ms_per_call


def read(run):
    return device_ms_per_call(run, "mask_probe")
