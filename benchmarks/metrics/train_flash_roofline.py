"""Roofline share of the flash-attention custom calls in the device trace."""
from benchlib.readers import flash_roofline as read  # noqa: F401
