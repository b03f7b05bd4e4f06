"""Of the (token, expert) pairs the routers chose in the window's decode bursts, the share that fell on experts this chip holds: `moe_pairs_held` over `moe_pairs`, device counters on the `serving.step` spans (64 of 256 held: 25% where the routing is even)."""
from benchlib import program_spans as P


def read(obs):
    steps = P.window_spans(obs, P.STEP)
    tags = [s['tags'] for s in steps or () if s['tags'].get('moe_pairs')]
    return 100.0 * sum(t['moe_pairs_held'] for t in tags) \
        / sum(t['moe_pairs'] for t in tags) if tags else None
