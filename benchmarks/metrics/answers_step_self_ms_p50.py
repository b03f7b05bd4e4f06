"""Median `serving.step` minus the part its child spans (admit, prefill, decode burst) cover."""
from benchlib import program_spans as P

read = P.step_self_ms_p50
