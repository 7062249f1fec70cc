"""Useful multiplications over planned product slots, %."""
from bench.readers import slot_use_pct as read  # noqa: F401

OBS = True      # reads the plan.* events of repro.obs
