"""Bytes the decode program needs (weights, live KV or state) over the peak HBM bandwidth, against its device time, in %."""
from chip.layer import decode_roofline as read  # noqa: F401
