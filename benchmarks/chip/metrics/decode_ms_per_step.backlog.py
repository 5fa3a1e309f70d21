"""Device busy time of a step that admitted nothing (one ``lm.decode_step``), in ms."""
from chip.layer import decode_ms_per_step as read  # noqa: F401
