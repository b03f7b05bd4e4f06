"""The busiest held expert's tokens in a decode step (`moe_load_max`: the largest over the burst's steps and the expert layers) over the mean tokens a held expert got in that burst (`moe_pairs_held` / (held experts x expert layers x steps)): median over the window's bursts. 1 is an even routing; the dense product over held experts costs the same whatever this reads, a grouped one would not."""
from benchlib import program_spans as P
from benchlib.stats import percentile


def read(obs):
    steps = P.window_spans(obs, P.STEP)
    m = obs['model']
    cells = obs['engine']['decode_block'] * m['num_experts'] \
        * (m['num_hidden_layers'] - m['first_k_dense_replace'])
    ratios = [s['tags']['moe_load_max'] * cells / s['tags']['moe_pairs_held']
              for s in steps or ()
              if s['tags'].get('moe_pairs_held')]
    return percentile(ratios, 50) if ratios else None
