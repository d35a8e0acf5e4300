"""Prompt tokens prefilled plus output tokens committed per second of the window (host clock)."""
from benchlib.readers import tokens_per_s as read  # noqa: F401
