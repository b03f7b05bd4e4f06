"""Pipeline parallelism (VERDICT r1 item 2 'done' bar): the GPipe
scan+ppermute schedule trains through fleet_train_step and PipelineEngine,
with loss parity vs the non-pipelined run on the 8-device virtual mesh.

Reference parity targets: framework/section_worker.cc:104 (micro-batch
schedule), fleet/meta_parallel/pipeline_parallel.py:109 (train_batch),
parallel_layers/pp_layers.py:62 (SharedLayerDesc tied weights).
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.pipeline import (PipelineEngine, make_pp_state,
                                             pipeline_state)
from paddle_tpu.distributed.meta_parallel.pp_layers import (LayerDesc,
                                                            PipelineLayer)
from paddle_tpu.distributed.topology import HybridCommunicateGroup
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM


def _model(seed=0, layers=4):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=layers,
                    num_heads=4, max_position_embeddings=32, dropout=0.0)
    return GPTForCausalLM(cfg)


def _batch(b=8, s=32, vocab=128):
    rng = np.random.RandomState(3)
    ids = paddle.to_tensor(rng.randint(0, vocab, (b, s)).astype(np.int32))
    lbl = paddle.to_tensor(rng.randint(0, vocab, (b, s)).astype(np.int32))
    return ids, lbl


def _strategy(**hybrid):
    s = fleet.DistributedStrategy()
    cfg = {'dp_degree': 8, 'mp_degree': 1, 'pp_degree': 1,
           'sharding_degree': 1, 'sp_degree': 1}
    cfg.update(hybrid)
    s.hybrid_configs = cfg
    return s


def _fleet_step(model, strategy):
    fleet.init(is_collective=True, strategy=strategy)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    return fleet.fleet_train_step(
        model, lambda lg, lb: model.loss(lg, lb), opt, strategy=strategy)


def test_gpt_pp4_uneven_layers_matches_dp():
    """pp=4 over 6 layers (not divisible): ghost identity padding keeps
    loss parity with dp (reference uneven seg_method, pp_layers.py:76)."""
    ids, lbl = _batch()
    ref = _fleet_step(_model(seed=17, layers=6), _strategy())
    ref_losses = [float(ref(ids, lbl).numpy()) for _ in range(2)]
    m = _model(seed=17, layers=6)
    step = _fleet_step(m, _strategy(dp_degree=2, pp_degree=4))
    losses = [float(step(ids, lbl).numpy()) for _ in range(2)]
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-5)


def test_gpt_pp4_matches_dp():
    """pp=4 GPT fleet step: same losses as the plain dp run."""
    ids, lbl = _batch()

    ref = _fleet_step(_model(seed=9), _strategy())
    ref_losses = [float(ref(ids, lbl).numpy()) for _ in range(3)]

    s = _strategy(dp_degree=2, pp_degree=4)
    m_pp = _model(seed=9)
    step = _fleet_step(m_pp, s)
    jaxpr = step.trace_jaxpr(ids, lbl)
    assert 'ppermute' in jaxpr  # the schedule is really in the program
    pp_losses = [float(step(ids, lbl).numpy()) for _ in range(3)]
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-4, atol=2e-5)
    # the context is scoped to the step
    assert pipeline_state() is None


def test_gpt_pp2_with_recompute_and_bf16():
    """pp composes with recompute (remat inside the stage scan) and amp."""
    ids, lbl = _batch()
    s = _strategy(dp_degree=4, pp_degree=2)
    s.recompute = True
    s.amp = True
    model = _model(seed=4)
    step = _fleet_step(model, s)
    l0 = float(step(ids, lbl).numpy())
    l1 = float(step(ids, lbl).numpy())
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0


def test_pipeline_layer_engine_trains():
    """Declarative PipelineLayer through PipelineEngine: heterogeneous
    stage fns via lax.switch, loss decreases, parity vs sequential."""
    hidden = 32

    def make_descs():
        return [LayerDesc(nn.Linear, hidden, hidden),
                LayerDesc(nn.Tanh),
                LayerDesc(nn.Linear, hidden, hidden),
                LayerDesc(nn.Tanh),
                LayerDesc(nn.Linear, hidden, hidden),
                LayerDesc(nn.Tanh),
                LayerDesc(nn.Linear, hidden, hidden),
                LayerDesc(nn.Tanh)]

    import paddle_tpu.nn.functional as F

    def loss_fn(out, labels):
        return F.mse_loss(out, labels)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, hidden).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, hidden).astype(np.float32))

    # sequential reference (pp degree 1)
    paddle.seed(21)
    ref_layer = PipelineLayer(make_descs(), num_stages=4, loss_fn=loss_fn)
    hcg1 = HybridCommunicateGroup(dp_degree=8)
    opt_ref = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=ref_layer.parameters())
    eng_ref = PipelineEngine(ref_layer, opt_ref, hcg1)
    ref_losses = [float(eng_ref.step(x, y).numpy()) for _ in range(4)]

    # pipelined (pp=4 over the first mesh axis arrangement dp2xpp4)
    paddle.seed(21)
    layer = PipelineLayer(make_descs(), num_stages=4, loss_fn=loss_fn)
    hcg = HybridCommunicateGroup(dp_degree=2, pp_degree=4)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=layer.parameters())
    eng = PipelineEngine(layer, opt, hcg)
    losses = [float(eng.step(x, y).numpy()) for _ in range(4)]

    assert losses[-1] < losses[0]
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=1e-5)


def test_pipeline_blocks_uneven_split_matches_sequential():
    """4 layers over 3 stages (r3 raised here): ghost identity padding
    keeps the pipelined forward equal to the sequential one."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.pipeline import pipeline_blocks
    model = _model(layers=4)
    model.eval()
    mesh = Mesh(np.array(jax.devices()[:3]), ('pp',))
    st = make_pp_state(mesh, n_stages=3)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(6, 8, 64).astype(np.float32))
    out = pipeline_blocks(model.gpt.h, x, st).numpy()
    ref = x
    for blk in model.gpt.h:
        ref = blk(ref)
    np.testing.assert_allclose(out, ref.numpy(), rtol=2e-4, atol=2e-5)
