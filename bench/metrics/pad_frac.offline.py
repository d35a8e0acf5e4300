"""Scheduler: padding tokens launched over all tokens launched in the window (EngineStats counters)."""
from benchlib.readers import pad_frac as read  # noqa: F401
