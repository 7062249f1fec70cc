"""Bytes a SpGEMM call moves through the 2D exchange (robust/audit.py:
guard_exchange counts each operand's live payload as it enters it)."""
from bench.readers import comm_bytes_per_call as read  # noqa: F401

OBS = True      # reads the comm.bytes.* counters of repro.obs
