"""The comparison has been shown to fail: the control (the reference in
the next lower precision put in the program's place) and each fault a
cell can have come out as not correct, at a size a test run can hold."""
import json
import os

import pytest

from bench_testlib import TINY, rehearse
from benchlib import reference, serve, train
from benchlib import traffic as T
from benchlib import weights as W


def tiny(name):
    return json.load(open(os.path.join(TINY, 'configs', name + '.json')))


@pytest.mark.parametrize('seed', (3, 2 ** 31 + 4, 5))
def test_serving_control_reads_above_the_limit(seed):
    """The real cells' control, fp8, reads far above the limit; bf16, the
    step below the float32 the tiny configuration states, flips too few
    of these few hundred positions to read anything on some seeds."""
    cfg = tiny('tiny-serve')
    m = cfg['model']
    rng = T.stream(seed, 'control')
    seqs = [([int(t) for t in rng.integers(0, 500, 8)],
             [int(t) for t in rng.integers(0, 500, 110)]) for _ in range(6)]
    with reference.highest():
        stacked = W.make_stacked(m, seed, 'float32')
        _, c16 = reference.served_gaps(stacked, m, seqs, 'bf16')
        _, c8 = reference.served_gaps(stacked, m, seqs, 'fp8')
    limit = cfg['correct']['logit_gap_max']
    bf16 = max(float(g.max()) for g in c16)
    fp8 = max(float(g.max()) for g in c8)
    assert fp8 > 3 * limit and fp8 >= bf16
    assert not serve.is_correct(
        {'logit_gap_max': {'value': fp8, 'limit': limit}})


@pytest.mark.parametrize('seed', (3, 2 ** 31 + 4, 5))
def test_training_control_and_half_batch_fail(seed):
    cfg = tiny('tiny-train')
    sc = cfg['step']
    rows = T.train_rows({'epoch_steps': 3, 'vocab_limit': 500}, seed,
                        sc['batch'], sc['seq_len'])
    batches = [(rows[i * 8:(i + 1) * 8, 0], rows[i * 8:(i + 1) * 8, 1])
               for i in range(3)]
    ref = train.follow(cfg, seed, batches)
    limits = cfg['correct']
    for planted in ({'quant': 'fp8'}, {'rows': slice(0, 4)}):
        got = train.readings(train.follow(cfg, seed, batches, **planted),
                             ref)
        failed = [k for k in limits if got[k] > limits[k]]
        assert failed, (planted, got)
    half = train.readings(
        train.follow(cfg, seed, batches, rows=slice(0, 4)), ref)
    assert half['grad_norm_gap'] > 10 * limits['grad_norm_gap']


def test_fault_token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.serving import PagedContinuousBatchingEngine as Eng
    emit = Eng._emit

    def altered(self, req, tokens):
        if tokens and not req.tokens:
            tokens = [(tokens[0] + 1) % 500] + list(tokens[1:])
        return emit(self, req, tokens)
    monkeypatch.setattr(Eng, '_emit', altered)
    result, _ = rehearse('serve-xl.prefix-turns', seed=9)
    assert result['correct'] is False
    c = result['compared']['logit_gap_max']
    assert c['value'] > c['limit']


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    from paddle_tpu.framework import functional
    init = functional.TrainStep.__init__

    def no_donation(self, *a, **kw):
        kw['donate'] = False
        init(self, *a, **kw)
    monkeypatch.setattr(functional.TrainStep, '__init__', no_donation)
    monkeypatch.setattr(functional, 'write_back_params', lambda *a: None)
    monkeypatch.setattr(functional.TrainStep, '_write_opt_state',
                        lambda self, state: None)
    result, _ = rehearse('train-large.seq1k-ingest', seed=9)
    assert result['correct'] is False
    c = result['compared']
    assert c['grad_norm_gap']['value'] == pytest.approx(1.0)
    assert c['change_norm_gap']['value'] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.framework import functional
    call = functional.TrainStep.__call__

    def half(self, inputs, labels):
        n = inputs.shape[0] // 2
        return call(self, inputs[:n], labels[:n])
    monkeypatch.setattr(functional.TrainStep, '__call__', half)
    result, _ = rehearse('train-large.seq1k-ingest', seed=9)
    assert result['correct'] is False
    c = result['compared']['grad_norm_gap']
    assert c['value'] > 10 * c['limit']
