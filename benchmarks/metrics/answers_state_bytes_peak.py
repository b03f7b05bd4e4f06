"""Peak bytes of per-slot recurrent state (the KDA layers' float32 S and convolution tails) that belonged to a resident: the `state_bytes` tag of the `serving.step` spans that start in the window."""
from benchlib import program_spans as P


def read(obs):
    steps = P.window_spans(obs, P.STEP)
    sizes = [s['tags']['state_bytes'] for s in steps or ()
             if 'state_bytes' in s['tags']]
    return float(max(sizes)) if sizes else None
