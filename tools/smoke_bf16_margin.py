"""Where chip_smoke.py's bf16 check `gateway == paged` fails: how close
was the call? Runs the smoke's bf16 serve drive (the engine direct, then
behind the gateway) over some seeds with the failing check recorded
instead of raised, and for every request whose two drives part reads, at
the first token they part on, the logits of the same model over the
direct drive's sequence so far — in bf16 as served and in f32 at highest
precision: the two tokens' ranks, their margin, and the bf16 spacing at
that magnitude. A margin of a spacing or two between ranks 1 and 2 is a
tie that the order of a sum decides; anything wider is a bug.

    chiprun -- python tools/smoke_bf16_margin.py --seeds 0,1,2,3

Last stdout line: one JSON object. `--toy` runs the rehearsal's widths
(CPU); the smoke's own seed is 0.
"""
import argparse
import json
import math
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def last_logits(model, ids):
    import paddle_tpu as paddle
    out = model(paddle.to_tensor(np.asarray([ids], np.int32)))
    return np.asarray(out.numpy()[0, -1], np.float32)


def reading(lg, direct, gateway):
    """One row of logits against the two tokens the drives picked."""
    order = np.argsort(-lg, kind='stable')
    rank = {int(t): int(np.nonzero(order == t)[0][0]) + 1
            for t in (direct, gateway)}
    top = float(lg[order[0]])
    return {'top2': [int(order[0]), int(order[1])],
            'top2_margin': float(lg[order[0]] - lg[order[1]]),
            'rank_direct': rank[direct], 'rank_gateway': rank[gateway],
            'margin_direct_gateway': float(lg[direct] - lg[gateway]),
            'bf16_spacing_at_top': 2.0 ** (math.floor(math.log2(abs(top)))
                                           - 7) if top else None}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--seeds', default='0')
    ap.add_argument('--toy', action='store_true')
    args = ap.parse_args(argv)
    import jax
    import chip_smoke as S
    from paddle_tpu.framework import compile_cache
    compile_cache.configure()
    failed = []
    S.check = lambda ok, what: None if ok else failed.append(what)
    widths, sizes = (S.TOY, S.TOY_SIZES) if args.toy \
        else (S.FULL, S.FULL_SIZES)
    device = jax.devices()[0]
    rows = []
    for seed in [int(s) for s in args.seeds.split(',')]:
        del failed[:]
        prompts = S.make_prompts(widths, sizes, seed)
        exact = S.build_lm(widths, 4 * sizes['max_len'], seed)
        exact.eval()
        model = S.build_lm(widths, 4 * sizes['max_len'], seed)
        model.eval()
        model.bfloat16()
        out = S.run_engine(model, widths, sizes, prompts,
                           device.platform in ('tpu', 'gpu'))
        parted = []
        for i, (d, g) in enumerate(zip(out['paged'], out['gateway'])):
            if d == g:
                continue
            t = next(k for k, (x, y) in enumerate(zip(d, g)) if x != y)
            ids = list(prompts[i]) + d[:t]
            with jax.default_matmul_precision('highest'):
                f32 = reading(last_logits(exact, ids), d[t], g[t])
            parted.append({
                'request': i, 'token': t, 'direct': d[t], 'gateway': g[t],
                'bf16': reading(last_logits(model, ids), d[t], g[t]),
                'f32_highest': f32})
        rows.append({'seed': seed, 'requests': len(prompts),
                     'parted': parted, 'failed_checks': list(failed)})
    print(json.dumps({'device': device.device_kind, 'rows': rows}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
