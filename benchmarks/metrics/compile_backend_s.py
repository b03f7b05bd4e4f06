"""Seconds of the compile stage (XLA's compile, or a load from the persistent cache) of the programs built before the window, from the program's compile log."""
from metrics.compile_trace_lower_s import before_window


def read(obs):
    t = before_window(obs)
    return None if t is None else t['outer_s']['compile']
