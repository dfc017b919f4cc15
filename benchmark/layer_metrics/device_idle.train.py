"""``device_idle.train`` (device): 1 - (union of device-operation intervals over
the traced window), averaged over the cell's chips, from the ``.xplane.pb``."""
from harness.xplane import idle_share as read  # noqa: F401
