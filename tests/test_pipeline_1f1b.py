"""1F1B pipeline schedule (VERDICT r2 item 5): interleaved fwd/bwd with
O(pp) stash and micro-level loss inside the last stage.

Parity bar: the 1F1B fleet step must produce the same losses as the plain
dp run (reference test style: test_dist_base.py check_with_place loss
deltas). Tied embeddings (wte in pre AND post) are the SharedLayerDesc
grad-correctness case (parallel_layers/pp_layers.py:62).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM


def _model(seed=0, layers=4, tie=True):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=layers,
                    num_heads=4, max_position_embeddings=32, dropout=0.0,
                    tie_word_embeddings=tie)
    return GPTForCausalLM(cfg)


def _batch(b=8, s=32, vocab=128):
    rng = np.random.RandomState(3)
    ids = paddle.to_tensor(rng.randint(0, vocab, (b, s)).astype(np.int32))
    lbl = paddle.to_tensor(rng.randint(0, vocab, (b, s)).astype(np.int32))
    return ids, lbl


def _strategy(schedule=None, acc=None, **hybrid):
    s = fleet.DistributedStrategy()
    cfg = {'dp_degree': 8, 'mp_degree': 1, 'pp_degree': 1,
           'sharding_degree': 1, 'sp_degree': 1}
    cfg.update(hybrid)
    s.hybrid_configs = cfg
    if schedule is not None:
        s.pipeline = True
        s.pipeline_configs['schedule_mode'] = schedule
        if acc is not None:
            s.pipeline_configs['accumulate_steps'] = acc
    return s


def _fleet_step(model, strategy):
    fleet.init(is_collective=True, strategy=strategy)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    return fleet.fleet_train_step(
        model, lambda lg, lb: model.loss(lg, lb), opt, strategy=strategy)


@pytest.mark.parametrize('tie', [True, False])
def test_1f1b_matches_dp(tie):
    """pp=2 1F1B (n_micro=4=2*pp by default): same losses as plain dp.
    tie=True exercises the tied-embedding (SharedLayerDesc) grad path —
    wte grads come from rank 0 (embedding) AND the last rank (head)."""
    ids, lbl = _batch()

    ref = _fleet_step(_model(seed=9, tie=tie), _strategy())
    ref_losses = [float(ref(ids, lbl).numpy()) for _ in range(3)]

    s = _strategy(schedule='1F1B', dp_degree=4, pp_degree=2)
    m_pp = _model(seed=9, tie=tie)
    step = _fleet_step(m_pp, s)
    jaxpr = step.trace_jaxpr(ids, lbl)
    assert 'ppermute' in jaxpr
    pp_losses = [float(step(ids, lbl).numpy()) for _ in range(3)]
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-4, atol=2e-5)


def test_1f1b_uneven_layers_matches_dp():
    """n_layers=3 with pp=2 (not divisible): the stack pads with a ghost
    identity layer and still matches the plain dp run — the reference's
    uneven seg_method capability (pp_layers.py:76)."""
    ids, lbl = _batch()
    ref = _fleet_step(_model(seed=21, layers=3), _strategy())
    ref_losses = [float(ref(ids, lbl).numpy()) for _ in range(2)]

    m = _model(seed=21, layers=3)
    step = _fleet_step(m, _strategy(schedule='1F1B', dp_degree=4,
                                    pp_degree=2))
    losses = [float(step(ids, lbl).numpy()) for _ in range(2)]
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)


def test_1f1b_accumulate_steps_honored():
    """accumulate_steps decouples n_micro from pp (VERDICT: >= 2*pp)."""
    ids, lbl = _batch(b=8)
    s = _strategy(schedule='1F1B', acc=8, dp_degree=4, pp_degree=2)
    model = _model(seed=2)
    step = _fleet_step(model, s)
    assert step._pp_state['n_micro'] == 8
    l0 = float(step(ids, lbl).numpy())
    l1 = float(step(ids, lbl).numpy())
    assert np.isfinite(l0) and l1 < l0


def test_1f1b_pp4_trains():
    ids, lbl = _batch(b=16)
    s = _strategy(schedule='1F1B', dp_degree=2, pp_degree=4)
    model = _model(seed=5)
    step = _fleet_step(model, s)
    losses = [float(step(ids, lbl).numpy()) for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_fthenb_mode_still_gpipe():
    ids, lbl = _batch()
    s = _strategy(schedule='F-then-B', dp_degree=4, pp_degree=2)
    model = _model(seed=7)
    step = _fleet_step(model, s)
    assert step._pp_state['schedule'] == 'gpipe'
    assert np.isfinite(float(step(ids, lbl).numpy()))


def test_1f1b_composes_with_mp():
    """1F1B pp2 x mp2 x dp2: TP-sharded params inside the cond-gated
    stages compile and train (the lax.cond branches are consistent
    within each mp group)."""
    ids, lbl = _batch(b=8)
    s = _strategy(schedule='1F1B', dp_degree=2, pp_degree=2, mp_degree=2)
    model = _model(seed=11)
    step = _fleet_step(model, s)
    losses = [float(step(ids, lbl).numpy()) for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
