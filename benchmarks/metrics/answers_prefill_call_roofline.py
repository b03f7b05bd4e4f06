"""Roofline share of the prefill call (program _prefill_fn in the device trace): the family's least time of the traced window's calls, from the `start` and `tokens` tags of their `serving.prefill_call` spans, over the device time of the program's runs; means of both, as the decode step's share is taken."""
from benchlib import program_spans as P

CALL = 'serving.prefill_call'


def read(obs):
    red = obs.get('reduced')
    runs = red and red['modules'].get('_prefill_fn')
    spans = runs and P.traced_spans(obs, CALL)
    least = getattr(obs.get('family'), 'prefill_call_least_seconds', None)
    # a program whose spans do not say where a call started: nothing to read
    if not spans or least is None or \
            any('start' not in s['tags'] for s in spans):
        return None
    flops, bandwidth = obs['peaks']
    floor = [least(obs['model'], s['tags']['start'], s['tags']['tokens'],
                   flops, bandwidth)[0] for s in spans]
    return 100.0 * (sum(floor) / len(floor)) / (sum(runs) / len(runs))
