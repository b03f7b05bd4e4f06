"""Roofline share of the decode step (program _decode_fn in the device trace): the family's least time holds the weights outside the routed experts once, each held expert once if a token touches it (in expectation), the latent rows held and each resident's state read and written."""
from benchlib.readers import decode_step_roofline as read  # noqa: F401
