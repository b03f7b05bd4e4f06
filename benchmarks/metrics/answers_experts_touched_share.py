"""Held experts that at least one token of a decode step chose, over held experts x expert layers x the burst's steps: `moe_experts_touched` on the `serving.step` spans of the window. An expert that no token touches need not be read."""
from benchlib import program_spans as P


def read(obs):
    steps = P.window_spans(obs, P.STEP)
    tags = [s['tags'] for s in steps or ()
            if 'moe_experts_touched' in s['tags']]
    m = obs['model']
    per_burst = obs['engine']['decode_block'] * m['num_experts'] \
        * (m['num_hidden_layers'] - m['first_k_dense_replace'])
    return 100.0 * sum(t['moe_experts_touched'] for t in tags) \
        / (len(tags) * per_burst) if tags else None
