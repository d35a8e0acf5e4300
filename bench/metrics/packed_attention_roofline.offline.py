"""Kernel: least time for the needed packed-attention work over its summed event time (device trace)."""
from benchlib.readers import attn_roofline as read  # noqa: F401
