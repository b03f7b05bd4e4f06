"""Median decode burst, from the engine's StepTimeline."""
from benchlib.readers import decode_burst_ms_p50 as read  # noqa: F401
