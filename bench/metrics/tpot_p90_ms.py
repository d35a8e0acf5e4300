"""Time per output token, 90th percentile over the window's finished requests (host clock)."""
from benchlib.readers import tpot_p90_ms as read  # noqa: F401
