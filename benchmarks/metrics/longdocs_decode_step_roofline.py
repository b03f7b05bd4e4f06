"""Roofline share of the decode step (program _decode_fn in the device trace): the family's least time holds the weights once, the K/V held and each resident's state read and written."""
from benchlib.readers import decode_step_roofline as read  # noqa: F401
