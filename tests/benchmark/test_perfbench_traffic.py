"""The benchmark's traffic generator: what makes a cell repeat."""
import collections

import numpy as np
import pytest

from bench_testlib import BENCH, benchmark_json
from benchlib import traffic as T

SERVE_MIXES = ('offline-decode', 'prefix-turns')


@pytest.mark.parametrize('mix', SERVE_MIXES)
def test_same_seed_same_trace(mix):
    cfg = T.load(mix, BENCH)
    a, b = T.serve_trace(cfg, 2 ** 31 + 99, 20), T.serve_trace(
        cfg, 2 ** 31 + 99, 20)
    assert a.prompts == b.prompts and a.outputs == b.outputs
    assert np.array_equal(a.due, b.due) and a.group == b.group


@pytest.mark.parametrize('mix', SERVE_MIXES)
def test_two_seeds_same_multiset_and_arrivals(mix):
    cfg = T.load(mix, BENCH)
    a, b = T.serve_trace(cfg, 1, 45), T.serve_trace(cfg, 3 * 2 ** 31, 45)
    assert len(a) == len(b)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert sorted(a.outputs) == sorted(b.outputs)
    assert collections.Counter(a.group) == collections.Counter(b.group)
    inside = lambda t: sum(t.in_window(i, 45) for i in range(len(t)))
    assert inside(a) == inside(b)
    assert a.prompts != b.prompts          # the seed decides ids and order


def test_slotted_arrivals_one_per_slot():
    cfg = T.load('prefix-turns', BENCH)
    rate = cfg['arrival']['rate_per_s']
    t = T.serve_trace(cfg, 5, 45)
    n_warm = int(rate * t.warmup_s)
    slots = np.floor(np.asarray(t.due) * rate + 1e-9) + n_warm
    assert sorted(slots) == list(range(len(t)))
    assert sum(t.in_window(i, 45) for i in range(len(t))) == int(rate * 45)


def test_every_block_holds_the_same_lengths():
    cfg = T.load('offline-decode', BENCH)
    block = cfg['order']['block']
    t = T.serve_trace(cfg, 11, 45)
    first = sorted(t.outputs[:block])
    u = T.serve_trace(cfg, 12, 45)
    assert sorted(u.outputs[:block]) == first
    assert sorted(map(len, u.prompts[:block])) == sorted(
        map(len, t.prompts[:block]))


def test_offline_job_is_the_same_job_for_every_seed():
    cfg = T.load('offline-decode', BENCH)
    assert cfg['order']['seeded'] is False
    a, b = T.serve_trace(cfg, 1, 45), T.serve_trace(cfg, 2, 45)
    assert a.outputs == b.outputs
    assert list(map(len, a.prompts)) == list(map(len, b.prompts))
    assert a.prompts != b.prompts


def test_prefix_turns_uncached_part_fits_one_chunk():
    bm = benchmark_json()
    cfg = T.load('prefix-turns', BENCH)
    import json
    import os
    conf = json.load(open(os.path.join(
        BENCH, 'configs', 'gpt2-xl-serve-1chip.json')))
    chunk = conf['engine']['prefill_chunk']
    t = T.serve_trace(cfg, 21, bm['run_seconds'])
    for i in range(len(t)):
        assert 0 < len(t.prompts[i]) - t.prefix_len[i] <= chunk
        need = len(t.prompts[i]) + max(t.outputs[i], chunk) - 1
        assert need <= conf['engine']['max_len']
    shared = [g for g in t.group if g >= 0]
    assert abs(len(shared) / len(t) - 0.9) < 0.01


@pytest.mark.parametrize('mix', SERVE_MIXES)
def test_ids_stay_below_the_vocabulary(mix):
    cfg = T.load(mix, BENCH)
    t = T.serve_trace(cfg, 2 ** 32 + 5, 10)
    top = max(max(p) for p in t.prompts)
    assert top < 50257 and min(min(p) for p in t.prompts) >= 0


def test_offline_requests_fit_the_engine():
    cfg = T.load('offline-decode', BENCH)
    t = T.serve_trace(cfg, 4, 45)
    assert all(len(p) + o <= 1024 for p, o in zip(t.prompts, t.outputs))
    assert not any(g >= 0 for g in t.group)


def test_train_rows_differ_and_labels_are_next_tokens():
    cfg = T.load('seq1k-ingest', BENCH)
    rows = T.train_rows(cfg, 2 ** 31 + 1, 4, 64)
    assert rows.shape == (cfg['epoch_steps'] * 4, 2, 64)
    assert len({r.tobytes() for r in rows[:, 0]}) == len(rows)
    assert np.array_equal(rows[:, 0, 1:], rows[:, 1, :-1])
    assert rows.max() < 50257
    assert np.array_equal(rows, T.train_rows(cfg, 2 ** 31 + 1, 4, 64))
