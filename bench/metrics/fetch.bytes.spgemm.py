"""Bytes the ``dist.fetch`` spans move to the host per call, from the
fetched arrays' shapes and dtypes (the ``fetch.bytes`` counter)."""
from bench.spans import obs_per_call


def read(run):
    return obs_per_call(run, "fetch.bytes")


OBS = True      # reads the fetch.bytes counter of repro.obs
