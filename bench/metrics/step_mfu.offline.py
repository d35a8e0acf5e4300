"""Model step: needed model FLOPs of the traced iterations over device busy time x chips x peak (device trace)."""
from benchlib.readers import step_mfu as read  # noqa: F401
