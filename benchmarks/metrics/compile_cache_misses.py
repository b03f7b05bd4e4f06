"""Persistent compile cache misses before the window."""
from benchlib.readers import compile_cache_misses as read  # noqa: F401
