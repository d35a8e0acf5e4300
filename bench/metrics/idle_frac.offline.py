"""Device: share of the traced window with no operation running (device trace)."""
from benchlib.readers import idle_frac as read  # noqa: F401
