"""Roofline share of the decode step (program _decode_fn in the device trace)."""
from benchlib.readers import decode_step_roofline as read  # noqa: F401
