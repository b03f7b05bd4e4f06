"""Of the set-up's outer trace, lower and compile seconds, those of the programs the cell's engine or TrainStep owns, from the program's compile log."""
from metrics.compile_trace_lower_s import before_window


def read(obs):
    t = before_window(obs)
    return None if t is None else t['owned_s']
