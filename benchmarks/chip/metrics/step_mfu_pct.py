"""FLOPs of every token the traced window processed over window length times the chip's bf16 peak, in %."""
from chip.layer import step_mfu_pct as read  # noqa: F401
