"""Programs jax built or loaded (compile-stage entries) before the window, from the program's compile log."""
from metrics.compile_trace_lower_s import before_window


def read(obs):
    t = before_window(obs)
    return None if t is None else float(t['programs'])
