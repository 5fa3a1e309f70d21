"""Share of the traced window in which no operation ran on the device, in %."""
from chip.layer import device_idle_pct as read  # noqa: F401
