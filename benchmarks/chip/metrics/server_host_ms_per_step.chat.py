"""Device-idle time inside ``Server.step`` spans, per step, in ms: the serving loop's host work."""
from chip.layer import server_host_ms_per_step as read  # noqa: F401
