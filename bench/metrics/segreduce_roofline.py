"""The Pallas segmented reduce's share of its HBM roofline, %."""
from bench.readers import segreduce_roofline_pct as read  # noqa: F401
