"""Time the expert layer's two products, dense and grouped, at the shapes
the served configurations trace, on the chip.

    python tools/time_expert_paths.py --out chiprun_out/expert_paths.json

For each shape (the family's widths, `rows` tokens a call: a prefill chunk
of 512, a decode step of every slot) it draws bfloat16 weights of the held
experts and a float32 router from a seed, routes random tokens, and times
the layer's routed part (`SparseMoe._routed`: the router, the counters and
the product) with the product forced to each path, and the grouped one at
each row tile of `--tiles` beside the one `_row_tile` chooses. A timing is
the median over `--repeats` of one program that applies the layer `--reps`
times in a loop (each pass feeds the next, so none is left out), over
`--reps`. The shape rule's thresholds (`text/models/moe.py`) are set from
these numbers. Needs a TPU: elsewhere it exits 1.
"""
import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# (family, rows, hidden, expert width, experts, top-k, held, scale)
SHAPES = (('trinity', 512, 3072, 3072, 256, 4, 16, 2.448),
          ('trinity', 64, 3072, 3072, 256, 4, 16, 2.448),
          ('kimi', 512, 2304, 1024, 256, 8, 64, 2.446),
          ('kimi', 128, 2304, 1024, 256, 8, 64, 2.446))


def _layer(moe, d, f, experts, top_k, held, scale, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    with nn.skip_init():     # shapes only: the weights are drawn below
        layer = moe.SparseMoe(d, f, experts, top_k, scale, (0, held))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = lambda k, shape, dt: (0.02 * jax.random.normal(k, shape)) \
        .astype(dt)
    weights = (normal(keys[0], (d, experts), jnp.float32),
               jnp.zeros((experts,), jnp.float32),
               *(normal(k, (held, f, d), jnp.bfloat16) for k in keys[1:4]))
    return layer, weights


def time_one(moe, layer, weights, rows, d, reps, repeats, seed):
    import jax
    import jax.numpy as jnp
    x0 = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (rows, 1, d)).astype(jnp.bfloat16)
    real = jnp.ones((rows, 1), bool)

    @jax.jit
    def loop(x, *ws):
        def body(_, carry):
            x, rows_done = carry
            y, counted = layer._routed(x, real, *ws)
            return (x + (1e-3 * y).astype(x.dtype),
                    rows_done + counted['moe_expert_rows'])
        return jax.lax.fori_loop(0, reps, body, (x, jnp.int32(0)))

    out = loop(x0, *weights)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(x0, *weights))
        times.append((time.perf_counter() - t0) / reps)
    _, counted = jax.jit(lambda x, *ws: layer._routed(x, real, *ws))(
        x0, *weights)
    return statistics.median(times), {k: int(v) for k, v in counted.items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--out', default=os.path.join(REPO, 'chiprun_out',
                                                  'expert_paths.json'))
    ap.add_argument('--reps', type=int, default=20)
    ap.add_argument('--repeats', type=int, default=5)
    ap.add_argument('--tiles', default='8,16,32,64,128')
    ap.add_argument('--seed', type=int, default=3000000419)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != 'tpu':
        print('needs a TPU; the devices here: %s' % jax.devices())
        return 1
    from paddle_tpu.text.models import moe
    rule, tile_rule = moe.expert_path, moe._row_tile
    results = []
    try:
        for fam, rows, d, f, experts, top_k, held, scale in SHAPES:
            layer, weights = _layer(moe, d, f, experts, top_k, held, scale,
                                    args.seed)
            chosen = tile_rule(rows, top_k, experts)
            tiles = sorted({chosen, *(int(t) for t in args.tiles.split(','))})
            runs = [('dense', None)] + [('grouped', t) for t in tiles]
            for path, tile in runs:
                moe.expert_path = lambda *a, p=path: p
                moe._row_tile = (lambda *a, t=tile: t) if tile else tile_rule
                s, counted = time_one(moe, layer, weights, rows, d,
                                      args.reps, args.repeats, args.seed)
                line = dict(family=fam, rows=rows, path=path, row_tile=tile,
                            chosen_tile=tile == chosen if tile else None,
                            rule=rule(rows, top_k, experts),
                            ms=round(1e3 * s, 4), counters=counted)
                print(json.dumps(line), flush=True)
                results.append(line)
    finally:
        moe.expert_path, moe._row_tile = rule, tile_rule
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, 'w') as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
