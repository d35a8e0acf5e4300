"""TP collectives: collective device time no other operation overlaps, over the traced window, averaged over devices (device trace)."""
from benchlib.readers import collective_exposed_frac as read  # noqa: F401
