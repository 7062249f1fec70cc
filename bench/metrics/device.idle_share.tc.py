"""Device idle share of the traced window, %."""
from bench.readers import idle_share_pct as read  # noqa: F401
