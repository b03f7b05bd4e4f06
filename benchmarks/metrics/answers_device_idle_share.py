"""1 - union of device operation intervals over the traced window."""
from benchlib.readers import device_idle_share as read  # noqa: F401
