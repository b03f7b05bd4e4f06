"""Rows the expert layer's product multiplies, tile padding included, for each (token, expert) pair that fell on a held expert, over the window's decode bursts: `moe_expert_rows` over `moe_pairs_held` on the `serving.step` spans. The dense product multiplies every row of a step by every held expert (64 held x 128 rows for ~256 pairs a step and layer: ~32); the grouped one only the chosen pairs, each touched expert's rows rounded up to the kernel's row tile. None where the program has no such counter."""
from benchlib import program_spans as P


def read(obs):
    steps = P.window_spans(obs, P.STEP)
    tags = [s['tags'] for s in steps or () if 'moe_expert_rows' in s['tags']]
    held = sum(t['moe_pairs_held'] for t in tags)
    return sum(t['moe_expert_rows'] for t in tags) / held if held else None
