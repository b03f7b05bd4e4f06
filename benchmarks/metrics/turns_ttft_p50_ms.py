"""Median of first token minus due time."""
from benchlib.readers import ttft_p50_ms as read  # noqa: F401
