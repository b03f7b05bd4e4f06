"""Mean jitted prefill calls an engine step carries: the `calls` tag of `serving.step.prefill`."""
from benchlib import program_spans as P

read = P.prefill_calls_per_step
