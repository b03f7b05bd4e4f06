"""1 - device seconds of `_decode_fn` runs over seconds of `serving.decode_burst` spans, traced window."""
from benchlib import program_spans as P


def read(obs):
    return P.idle_share(obs, P.BURST, '_decode_fn')
