"""Median time between the completions of consecutive steps (host clock at the blocking fetch)."""
from benchlib.readers import train_step_ms_p50 as read  # noqa: F401
