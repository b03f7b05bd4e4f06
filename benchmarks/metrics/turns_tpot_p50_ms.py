"""Median over requests of (last token - first token) / (tokens - 1)."""
from benchlib.readers import tpot_p50_ms as read  # noqa: F401
