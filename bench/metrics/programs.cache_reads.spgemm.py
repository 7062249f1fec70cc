"""Programs read from the persistent compile cache per call."""
from bench.readers import cache_reads_per_call as read  # noqa: F401
