"""1 - device seconds of `_prefill_fn` runs over seconds of `serving.step.prefill` spans, traced window."""
from benchlib import program_spans as P


def read(obs):
    return P.idle_share(obs, P.PREFILL, '_prefill_fn')
