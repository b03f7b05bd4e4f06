"""Whole-step share of the chip's peak: model FLOPs of the tokens processed in the window (projections, attention over the context held, the rule's products) over window x peak."""
from benchlib.readers import serve_mfu_pct as read  # noqa: F401
