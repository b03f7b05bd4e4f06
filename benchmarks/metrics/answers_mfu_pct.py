"""Whole-step share of the chip's peak: model FLOPs THIS chip must do for the tokens processed in the window (everything outside the routed experts, the pairs that fall on held experts in expectation, attention in the latent over the context held, the rule's products) over window x peak."""
from benchlib.readers import serve_mfu_pct as read  # noqa: F401
