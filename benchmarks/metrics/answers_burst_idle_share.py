"""1 - device seconds of `_decode_fn` runs over seconds of `serving.decode_burst` spans, traced window: the host's share of a burst and the prefill calls it waits out."""
from benchlib import program_spans as P


def read(obs):
    return P.idle_share(obs, P.BURST, '_decode_fn')
