"""Time to first token, 90th percentile over the requests due in the window, from their due time (host clock)."""
from benchlib.readers import ttft_p90_ms as read  # noqa: F401
