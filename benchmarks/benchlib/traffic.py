"""The one traffic generator: a traffic file (`traffic/<name>.json`) of
parameters in, a seeded trace out.

Started as a copy of `paddle_tpu/capacity/workload.py` (named RNG streams
so that a new knob never shifts an old draw, lognormal lengths, shared
prefix groups, prompts drawn in request order) and changed where a
benchmark cell has to repeat from seed to seed:

  * lengths are STRATIFIED: n requests take the n quantile mid-points of
    the stated distribution, so every seed has the same multiset of
    lengths; the seed decides their order, the token ids and the weights;
  * the order is stratified by blocks: every run of `order.block`
    consecutive requests holds an evenly spaced sample of the quantiles,
    permuted inside the block by the seed;
  * the number of arrivals is fixed and each is drawn uniformly inside
    its own slot of 1/rate seconds (`slotted`), or all are present at the
    start (`backlog`);
  * prefix groups take exact shares (largest remainder), not draws.

Grammar of a serving traffic file:

  arrival: {"process": "slotted", "rate_per_s": r, "warmup_s": w}
         | {"process": "backlog", "requests": n, "warmup_steps": k}
  prefix:  {"groups": G, "len": P, "weights": [...], "share": s}   optional
  tail:    length dist of the part after a shared prefix
  prompt:  length dist of a prompt with no shared prefix
  output:  length dist of max_new_tokens
  order:   {"block": b, "seeded": true | false}
  vocab_limit: ids are drawn below it

  length dist: {"dist": "lognormal", "median": M, "sigma": s,
                "min": lo, "max": hi}
             | {"dist": "uniform", "min": lo, "max": hi}
             | {"dist": "fixed", "len": L}

Times are seconds relative to the start of the measured window: a slotted
warm-up's arrivals are negative. A backlog is all there at the start and
its window opens after `warmup_steps` engine steps: the window then holds
the same steps of the same job in every run, where a warm-up counted in
seconds let it open a step earlier or later.
"""
import json
import math
import os
import statistics
import zlib

import numpy as np

_NORMAL = statistics.NormalDist()


def load(name, root):
    """The parameters of traffic mix `name` under `<root>/traffic/`."""
    path = os.path.join(root, 'traffic', name + '.json')
    with open(path) as f:
        return json.load(f)


def stream(seed, name):
    """Generator for the named stream of `seed` (any whole number)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
         zlib.crc32(name.encode('utf-8'))])


def quantile_lengths(cfg, n):
    """The n quantile mid-points of a length distribution, ascending."""
    dist = cfg.get('dist', 'fixed')
    if n == 0:
        return np.zeros(0, np.int64)
    if dist == 'fixed':
        return np.full(n, int(cfg['len']), np.int64)
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(cfg['min']), int(cfg['max'])
    if dist == 'uniform':
        vals = lo + q * (hi - lo)
    elif dist == 'lognormal':
        mu, sigma = math.log(float(cfg['median'])), float(cfg['sigma'])
        vals = np.exp([mu + sigma * _NORMAL.inv_cdf(x) for x in q])
    else:
        raise ValueError('unknown length dist %r' % (dist,))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def block_order(sorted_vals, block, rng):
    """`sorted_vals` dealt into consecutive blocks so that each block
    holds an evenly spaced sample, permuted inside the block by `rng`."""
    n = len(sorted_vals)
    if n == 0:
        return np.asarray(sorted_vals)
    nblocks = -(-n // max(int(block), 1))
    out = []
    for b in range(nblocks):
        part = np.asarray(sorted_vals[b::nblocks])
        out.append(part[rng.permutation(len(part))])
    return np.concatenate(out)


def exact_shares(n, weights):
    """n split over the weights by largest remainder: exact counts."""
    w = np.asarray(weights, float)
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind='stable')[:n - counts.sum()]:
        counts[i] += 1
    return counts


class ServeTrace:
    """Columnar request list: due time, prompt ids, output budget, prefix
    group (-1: none) and the prefix length shared."""

    def __init__(self, due, prompts, outputs, group, prefix_len, prefixes,
                 warmup_s, warmup_steps):
        self.due = due
        self.prompts = prompts
        self.outputs = outputs
        self.group = group
        self.prefix_len = prefix_len
        self.prefixes = prefixes
        self.warmup_s = warmup_s
        self.warmup_steps = warmup_steps

    def __len__(self):
        return len(self.due)

    def in_window(self, i, seconds):
        return 0.0 <= self.due[i] < seconds


def serve_trace(cfg, seed, seconds):
    """Traffic parameters + seed + window length -> ServeTrace."""
    arr = cfg['arrival']
    warm = float(arr.get('warmup_s', 0.0))
    if arr['process'] == 'slotted':
        rate = float(arr['rate_per_s'])
        n_warm = int(math.floor(rate * warm))
        n = n_warm + int(math.floor(rate * seconds))
        u = stream(seed, 'arrival').random(n)
        due = (np.arange(n) - n_warm + u) / rate
    elif arr['process'] == 'backlog':
        n = int(arr['requests'])
        due = np.zeros(n)
    else:
        raise ValueError('unknown arrival process %r' % (arr['process'],))

    order = cfg.get('order', {})
    block = int(order.get('block', n))
    # "seeded": false deals the lengths in ONE order for every seed (a
    # batch job is the same job each time; the seed still makes its ids
    # and the weights): where the order decides how requests pack into a
    # full page pool, it is work, not noise
    oseed = seed if order.get('seeded', True) else 0
    limit = int(cfg['vocab_limit'])

    pfx = cfg.get('prefix')
    group = np.full(n, -1, np.int64)
    plen = 0
    if pfx:
        plen = int(pfx['len'])
        n_shared = int(round(float(pfx.get('share', 1.0)) * n))
        per_group = exact_shares(n_shared, pfx['weights'])
        labels = np.concatenate(
            [np.full(c, g) for g, c in enumerate(per_group)]
            + [np.full(n - n_shared, -1)])
        # sorted labels dealt into blocks: every block has the same mix
        group = block_order(np.sort(labels), block, stream(oseed, 'group'))
    shared = group >= 0

    lengths = np.zeros(n, np.int64)
    lengths[shared] = block_order(
        quantile_lengths(cfg.get('tail', {'dist': 'fixed', 'len': 0}),
                         int(shared.sum())), block, stream(oseed, 'tail'))
    lengths[~shared] = block_order(
        quantile_lengths(cfg['prompt'], int((~shared).sum())),
        block, stream(oseed, 'prompt_len')) if (~shared).any() else 0
    outputs = block_order(quantile_lengths(cfg['output'], n), block,
                          stream(oseed, 'output'))

    prng = stream(seed, 'prefix_ids')
    prefixes = [[int(t) for t in prng.integers(0, limit, plen)]
                for _ in range(len(pfx['weights']) if pfx else 0)]
    trng = stream(seed, 'prompt_ids')
    prompts = []
    for i in range(n):
        body = [int(t) for t in trng.integers(0, limit, int(lengths[i]))]
        prompts.append(prefixes[group[i]] + body if shared[i] else body)
    return ServeTrace(due, prompts, [int(x) for x in outputs],
                      [int(g) for g in group],
                      [plen if s else 0 for s in shared], prefixes, warm,
                      int(arr.get('warmup_steps', 0)))


def train_rows(cfg, seed, batch, seq_len):
    """Token rows of a training traffic file: `epoch_steps * batch` rows
    of ids, every row different, labels the next token (the last label
    of a row is drawn). int32 [rows, 2, seq_len]."""
    rows = int(cfg['epoch_steps']) * int(batch)
    toks = stream(seed, 'train_ids').integers(
        0, int(cfg['vocab_limit']), (rows, seq_len + 1), dtype=np.int32)
    return np.stack([toks[:, :-1], toks[:, 1:]], axis=1)
