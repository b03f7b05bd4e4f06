"""Median seconds an engine step spends in its prefill calls: `serving.step.prefill` of the steps that start in the window."""
from benchlib import program_spans as P


def read(obs):
    return P.duration_ms_p50(obs, P.PREFILL)
