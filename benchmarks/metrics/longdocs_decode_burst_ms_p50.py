"""Median decode burst, from the engine's StepTimeline: 4 decode steps, and the wait for the prefill calls in flight before them."""
from benchlib.readers import decode_burst_ms_p50 as read  # noqa: F401
