"""The plain float32 reference against the program at a tiny width."""
import json
import os

import numpy as np
import pytest

from bench_testlib import TINY, rehearse
from benchlib import reference, serve
from benchlib import weights as W


def tiny(name):
    return json.load(open(os.path.join(TINY, 'configs', name + '.json')))


def test_forward_agrees_with_the_program():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    cfg = tiny('tiny-serve')
    m = cfg['model']
    stacked = W.make_stacked(m, 2 ** 31 + 3, 'float32')
    model = serve.build_model(m, 'float32', W.program_leaves(stacked))
    model.eval()
    ids = np.random.RandomState(0).randint(0, 500, (3, 40)).astype(np.int32)
    want = np.asarray(model(paddle.to_tensor(ids)).numpy())
    with reference.highest():
        got = np.asarray(reference.forward_logits(stacked, m,
                                                  jnp.asarray(ids)))
    assert np.abs(got - want).max() < 2e-5


def test_weights_repeat_and_cover_every_program_leaf():
    m = tiny('tiny-serve')['model']
    a = W.make_stacked(m, 2 ** 32 + 1, 'float32')
    b = W.make_stacked(m, 2 ** 32 + 1, 'float32')
    c = W.make_stacked(m, 2 ** 32 + 2, 'float32')
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a['wte'], c['wte'])
    leaves = W.program_leaves(a)
    assert len(leaves) == 4 + 12 * m['n_layer']
    assert np.array_equal(leaves['gpt.h.1.mlp.fc_in.weight'],
                          a['fc_in.w'][1])
    assert float(np.std(np.asarray(a['out.w']))) == pytest.approx(
        0.02 / np.sqrt(2 * m['n_layer']), rel=0.1)


def test_prefill_then_decode_through_the_paged_cache_agrees():
    """The served tokens (chunked prefill through block tables with
    shared prefix pages, then decode bursts) are the reference's own
    greedy tokens: no served token lies below the reference's best."""
    result, obs = rehearse('serve-xl.prefix-turns', seed=41)
    gap = result['compared']['logit_gap_max']
    assert gap['value'] is not None and gap['value'] <= 1e-4
    assert result['compared']['tokens_compared']['value'] >= 20
    assert sum(r.prefix_hit for r in obs['recs']) > 0   # shared pages


def test_loss_gradients_and_update_through_trainstep_agree():
    result, _ = rehearse('train-large.seq1k-ingest', seed=43)
    c = result['compared']
    assert c['rows_unknown_or_repeated']['value'] == 0
    for k in (1, 2, 3):
        assert c['loss_gap_step%d' % k]['value'] < 1e-4
    assert c['grad_norm_gap']['value'] < 1e-4
    assert c['change_norm_gap']['value'] < 1e-3
