"""Device time of one admission (prefill, splice, first-token selection): busy time of admitting steps less one decode each, over requests admitted, in ms."""
from chip.layer import admit_ms_per_request as read  # noqa: F401
