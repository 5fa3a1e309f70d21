"""Prefill FLOPs of the admissions over their device time times the chip's bf16 peak, in %."""
from chip.layer import admit_mfu_pct as read  # noqa: F401
