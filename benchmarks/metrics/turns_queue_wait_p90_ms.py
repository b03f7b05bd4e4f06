"""Admission minus due time, on one clock, 90th percentile over the requests due in the window."""
from benchlib.readers import queue_wait_p90_ms as read  # noqa: F401
