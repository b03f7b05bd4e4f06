"""Whole-step share of the chip's peak: (6 N + 12 L H S) x tokens/s over peak; recomputation not counted."""
from benchlib.readers import train_mfu_pct as read  # noqa: F401
