"""Blocking device-to-host reads the program makes per call: the sum of
its ``host.sync.*`` counters."""
from bench.spans import obs_per_call


def read(run):
    return obs_per_call(run, "host.sync")


OBS = True      # reads the host.sync.* counters of repro.obs
