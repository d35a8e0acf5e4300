"""Engine: host milliseconds per iteration over the window (EngineStats host_time / iterations)."""
from benchlib.readers import host_ms_per_iter as read  # noqa: F401
