"""Share of device busy time in the flash-attention custom calls."""
from benchlib.readers import flash_time_share as read  # noqa: F401
