"""Distributed tests on the 8-device virtual CPU mesh (reference pattern:
TestDistBase localhost multi-process, SURVEY.md §4.2 — here: SPMD shard_map
and sharding-spec assertions replace process spawning)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn


def _mesh(shape, names):
    devs = np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def test_eight_devices_present():
    assert len(jax.devices()) == 8


def test_topology_hcg():
    from paddle_tpu.distributed import HybridCommunicateGroup
    hcg = HybridCommunicateGroup(dp_degree=2, mp_degree=2, sharding_degree=2)
    assert hcg.mesh.shape['dp'] == 2
    assert hcg.mesh.shape['mp'] == 2
    assert hcg.mesh.shape['sharding'] == 2
    assert hcg.get_data_parallel_world_size() == 2
    assert hcg.get_model_parallel_world_size() == 2


def test_psum_inside_shard_map():
    from jax import shard_map
    mesh = _mesh((8,), ('dp',))
    x = jnp.arange(8.0)

    def f(x):
        return jax.lax.psum(x, 'dp')

    out = shard_map(f, mesh=mesh, in_specs=P('dp'), out_specs=P('dp'))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_dp_gradient_sync_via_jit():
    """Params replicated + batch sharded over dp => grads are global sums
    (what the reference's Reducer/allreduce achieves)."""
    mesh = _mesh((8,), ('dp',))
    w = jnp.ones((4, 2))
    x = np.random.RandomState(0).standard_normal((16, 4)).astype(np.float32)

    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P('dp')))
    ws = jax.device_put(w, NamedSharding(mesh, P()))
    g = jax.jit(jax.grad(loss))(ws, xs)
    g_ref = jax.grad(loss)(w, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5)


def test_fleet_train_step_dp_matches_single():
    """Loss-parity harness: dp-sharded fleet step == single-device step
    (reference: test_dist_base.check_with_place loss comparison)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.framework.functional import TrainStep

    def build():
        paddle.seed(11)
        m = nn.Linear(8, 4)
        o = paddle.optimizer.Adam(learning_rate=1e-2,
                                  parameters=m.parameters())
        return m, o

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    loss_fn = nn.MSELoss()

    m1, o1 = build()
    s1 = TrainStep(m1, loss_fn, o1)
    l1 = [float(s1(x, y).numpy()) for _ in range(3)]

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1, 'pp_degree': 1,
                               'sharding_degree': 1, 'sp_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    m2, o2 = build()
    s2 = fleet.fleet_train_step(m2, loss_fn, o2, strategy=strategy)
    l2 = [float(s2(x, y).numpy()) for _ in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=1e-4)


def test_fleet_zero3_matches_single():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.framework.functional import TrainStep

    def build():
        paddle.seed(13)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        o = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                   parameters=m.parameters())
        return m, o

    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    loss_fn = nn.MSELoss()

    m1, o1 = build()
    s1 = TrainStep(m1, loss_fn, o1)
    l1 = [float(s1(x, y).numpy()) for _ in range(3)]

    strategy = fleet.DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs['stage'] = 3
    strategy.hybrid_configs = {'dp_degree': 2, 'mp_degree': 1, 'pp_degree': 1,
                               'sharding_degree': 4, 'sp_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    m2, o2 = build()
    s2 = fleet.fleet_train_step(m2, loss_fn, o2, strategy=strategy)
    l2 = [float(s2(x, y).numpy()) for _ in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=1e-4)
    # params really are sharded over the 'sharding' axis
    shardings = {n: p._data.sharding for n, p in m2.named_parameters()}
    assert any('sharding' in str(s.spec) for s in shardings.values())


def test_tp_layers_match_plain_linear():
    from paddle_tpu.distributed.meta_parallel import (ColumnParallelLinear,
                                                      RowParallelLinear)
    paddle.seed(5)
    col = ColumnParallelLinear(8, 16)
    row = RowParallelLinear(16, 8)
    x = paddle.randn([4, 8])
    mid = col(x)
    out = row(mid)
    ref_mid = x.numpy() @ col.weight.numpy() + col.bias.numpy()
    ref = ref_mid @ row.weight.numpy() + row.bias.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4)
    assert col.weight.placement == (None, 'mp')
    assert row.weight.placement == ('mp', None)


def test_ring_attention_matches_full():
    from paddle_tpu.ops.ring_attention import ring_attention_sharded
    mesh = _mesh((8,), ('sp',))
    rng = np.random.RandomState(0)
    b, n, h, d = 2, 64, 4, 16
    q = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)

    def ref(q, k, v, causal):
        s = np.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
        if causal:
            mask = np.tril(np.ones((n, n), bool))
            s = np.where(mask[None, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum('bhqk,bkhd->bqhd', p, v)

    for causal in (False, True):
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   ref(np.asarray(q), np.asarray(k),
                                       np.asarray(v), causal),
                                   atol=2e-4,
                                   err_msg='causal=%s' % causal)


def test_ulysses_attention_matches_full():
    from paddle_tpu.ops.ring_attention import ulysses_attention_sharded
    mesh = _mesh((8,), ('sp',))
    rng = np.random.RandomState(1)
    # h=16 over sp=8 gives 2 local heads per device — exercises the
    # head-reconstruction order in head2seq (regression: heads were
    # permuted whenever h/sp > 1)
    b, n, h, d = 2, 64, 16, 16
    q = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, h, d)), jnp.float32)

    s = np.einsum('bqhd,bkhd->bhqk', np.asarray(q), np.asarray(k)) / np.sqrt(d)

    def ref_of(scores, causal):
        if causal:
            mask = np.tril(np.ones((n, n), bool))
            scores = np.where(mask[None, None], scores, -1e30)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum('bhqk,bkhd->bqhd', p, np.asarray(v))

    for causal in (False, True):
        out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), ref_of(s, causal),
                                   atol=2e-4, err_msg='causal=%s' % causal)


def test_collective_api_world1_identity():
    import paddle_tpu.distributed as dist
    x = paddle.to_tensor([1., 2.])
    dist.all_reduce(x)
    np.testing.assert_allclose(x.numpy(), [1., 2.])
    out = []
    dist.all_gather(out, x)
    assert len(out) == 1


def test_dryrun_multichip_entry():
    import sys
    sys.path.insert(0, '/root/repo')
    if jax.default_backend() == 'cpu':
        # the 8-device factorization includes pp configs, which hit
        # XLA:CPU's SPMD partitioner gap ("UNIMPLEMENTED: PartitionId
        # instruction is not supported for SPMD partitioning"). The
        # 2-device run drives the same dryrun surface — sharding audit,
        # telemetry/fleet snapshots, and the wide-event line — through
        # the dp/mp/sharding primary config only. It runs in a child
        # process: dryrun_multichip must be the first JAX use in its
        # process for the CPU device-count override to take effect, and
        # this process already holds the suite's 8-device backend.
        import subprocess
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        env.pop('XLA_FLAGS', None)
        proc = subprocess.run(
            [sys.executable, '-c',
             'import __graft_entry__ as g; g.dryrun_multichip(2)'],
            cwd='/root/repo', env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        from paddle_tpu.monitor import events as _ev
        assert _ev.parse_event_lines(proc.stdout), proc.stdout
    else:
        import __graft_entry__ as g
        g.dryrun_multichip(8)


def test_embedding_service_local_cluster():
    """Same-process PS cluster (reference: brpc_service_dense_sgd_test.cc
    pattern)."""
    from paddle_tpu.distributed.ps.runtime import local_cluster
    servers, client = local_cluster(num_servers=2, dim=4, optimizer='sgd',
                                    lr=0.5)
    ids = np.asarray([1, 5, 9, 1])
    rows = client.pull(0, ids)
    assert rows.shape == (4, 4)
    np.testing.assert_allclose(rows[0], rows[3])  # same id, same row
    grads = np.ones((4, 4), np.float32)
    client.push(0, ids, grads)
    rows2 = client.pull(0, ids)
    # id 1 appears twice: two grads applied
    np.testing.assert_allclose(rows2[0], rows[0] - 0.5 * 2, atol=1e-6)
    np.testing.assert_allclose(rows2[1], rows[1] - 0.5, atol=1e-6)
    for s in servers:
        s.stop()


def test_embedding_service_socket_transport():
    from paddle_tpu.distributed.ps.embedding_service import (EmbeddingServer,
                                                             EmbeddingClient)
    srv = EmbeddingServer()
    srv.create_table(0, dim=3, optimizer='adagrad', lr=0.1)
    srv.start(block=False)
    client = EmbeddingClient(endpoints=['127.0.0.1:%d' % srv.port])
    ids = np.asarray([7, 8])
    rows = client.pull(0, ids)
    assert rows.shape == (2, 3)
    client.push(0, ids, np.ones((2, 3), np.float32))
    rows2 = client.pull(0, ids)
    assert not np.allclose(rows, rows2)
    srv.stop()


def test_sync_batchnorm_global_stats_under_dp():
    """SyncBatchNorm's contract — BN statistics span the GLOBAL batch —
    holds under pjit dp sharding (the class doc's 'implicit sync' claim):
    running mean after one step equals the global batch mean, not any
    per-shard mean (reference sync_batch_norm_op.cu semantics)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.framework import functional as func_mod

    paddle.seed(0)
    bn = nn.SyncBatchNorm(3)
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ('dp',))

    rng = np.random.RandomState(0)
    # per-shard means differ strongly: shard i gets offset i
    x = rng.randn(16, 3, 4, 4).astype(np.float32)
    x += np.repeat(np.arange(8), 2)[:, None, None, None]

    params = func_mod.extract_params(bn)
    buffers = func_mod.extract_buffers(bn)

    def step(params, buffers, xb):
        out, new_buf = func_mod.functional_call(bn, params, buffers,
                                                args=(xb,), training=True)
        return out, new_buf

    xb = jax.device_put(x, NamedSharding(mesh, P('dp')))
    out, new_buf = jax.jit(step)(params, buffers, xb)

    global_mean = x.mean(axis=(0, 2, 3))
    momentum = bn._momentum
    expect = (1 - momentum) * global_mean  # running mean starts at 0
    got = np.asarray(new_buf['_mean'])
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)
    # the normalized output is standardized over the GLOBAL batch
    o = np.asarray(out)
    np.testing.assert_allclose(o.mean(axis=(0, 2, 3)), 0.0, atol=1e-4)


def test_fleet_wrapper_behaviors(tmp_path):
    """Former pass-bodies now act: distributed_model pre-places params on
    the fleet mesh, save_persistables writes the model state, and
    DataParallel registers with fleet + validates its input."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.parallel import DataParallel

    fleet._FLEET['model'] = None
    fleet.init(is_collective=True)
    net = nn.Linear(4, 3)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    fleet.distributed_optimizer(opt)
    fleet.distributed_model(net)
    # params now live on the hcg mesh (placed, not host-committed)
    sh = net.weight._data.sharding
    assert set(getattr(sh, 'mesh', None).axis_names) >= {'dp'}

    out_dir = str(tmp_path / 'persist')
    fleet.save_persistables(None, out_dir)
    import os
    assert os.path.exists(os.path.join(out_dir, 'persistables.pdparams'))
    state = paddle.load(os.path.join(out_dir, 'persistables.pdparams'))
    np.testing.assert_allclose(np.asarray(state['weight']),
                               net.weight.numpy())

    fleet.barrier_worker()  # no PS service: must be a clean no-op

    fleet._FLEET['model'] = None
    dp = DataParallel(net)
    assert fleet._FLEET['model'] is net
    with dp.no_sync():
        pass
    with pytest.raises(TypeError):
        DataParallel('not a layer')


@pytest.mark.slow
def test_ring_attention_long_context_8k():
    """Long-context evidence: seq 8192 sharded sp=8 (1024 tokens/device)
    through ring attention, fwd + grads, against a blocked numpy
    reference. The full [n, n] score matrix (8192^2 = 67M entries per
    head) never materializes on any one device."""
    from paddle_tpu.ops.ring_attention import ring_attention_sharded
    mesh = _mesh((8,), ('sp',))
    rng = np.random.RandomState(0)
    b, n, h, d = 1, 8192, 1, 8
    q = jnp.asarray(rng.standard_normal((b, n, h, d)) * 0.2, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, h, d)) * 0.2, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, h, d)) * 0.2, jnp.float32)

    out = ring_attention_sharded(q, k, v, mesh, causal=True)

    # blocked reference (numpy, streaming over k-chunks to stay small)
    qf = np.asarray(q[0, :, 0]); kf = np.asarray(k[0, :, 0])
    vf = np.asarray(v[0, :, 0])
    scale = 1.0 / np.sqrt(d)
    m = np.full(n, -np.inf); l = np.zeros(n); acc = np.zeros((n, d))
    for start in range(0, n, 1024):
        kb = kf[start:start + 1024]; vb = vf[start:start + 1024]
        s = qf @ kb.T * scale
        col = np.arange(start, start + 1024)
        s = np.where(col[None, :] <= np.arange(n)[:, None], s, -np.inf)
        m_new = np.maximum(m, s.max(-1))
        p = np.exp(s - m_new[:, None])
        corr = np.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[:, None] + p @ vb
        m = m_new
    ref_out = acc / l[:, None]
    np.testing.assert_allclose(np.asarray(out)[0, :, 0], ref_out,
                               atol=3e-4)

    # gradients flow through the ring
    def loss(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True)
                       .astype(jnp.float32) ** 2)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # check dq against the dense jnp reference gradient (fits on CPU)
    def dense_loss(q, k, v):
        s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum('bhqk,bkhd->bqhd', p, v)
        return jnp.sum(o ** 2)
    dq_ref = jax.grad(dense_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(dq_ref),
                               atol=3e-4)
    for gi in g[1:]:
        arr = np.asarray(gi)
        assert np.isfinite(arr).all() and np.abs(arr).max() > 0


def test_fleet_zero3_bf16_multi_precision():
    """ZeRO-3 composes with a bf16 model and multi_precision masters: the
    f32 master/slot entries ride the sharded opt-state pytree, stored
    params stay bf16 AND sharded, and training stays finite."""
    from paddle_tpu.distributed import fleet

    paddle.seed(23)
    m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    m.bfloat16()
    o = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                               multi_precision=True,
                               parameters=m.parameters())

    def loss_fn(out, lab):
        return paddle.nn.functional.mse_loss(out.astype('float32'), lab)

    strategy = fleet.DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs['stage'] = 3
    strategy.hybrid_configs = {'dp_degree': 2, 'mp_degree': 1,
                               'pp_degree': 1, 'sharding_degree': 4,
                               'sp_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    step = fleet.fleet_train_step(m, loss_fn, o, strategy=strategy)

    rng = np.random.RandomState(4)
    x = paddle.to_tensor(
        rng.standard_normal((16, 8)).astype(np.float32)).astype('bfloat16')
    y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))
    losses = [float(step(x, y).numpy()) for _ in range(3)]
    assert all(np.isfinite(losses)), losses

    for n, p in m.named_parameters():
        assert p.dtype == paddle.bfloat16, n
    shardings = {n: p._data.sharding for n, p in m.named_parameters()}
    assert any('sharding' in str(s.spec) for s in shardings.values())
    # masters exist, are f32, were WRITTEN BACK by the jitted step (a
    # lazily re-created slot would have all-zero moments), and ride the
    # sharded opt-state pytree
    import jax.numpy as jnp
    pmap = dict(m.named_parameters())
    for n, p in pmap.items():
        slots = o._get_slots(p)
        if not p.stop_gradient:
            assert slots['master'].dtype == jnp.float32, n
            assert slots['moment1'].dtype == jnp.float32, n
            assert np.abs(np.asarray(slots['moment1'])).max() > 0, n
    assert any('sharding' in str(o._get_slots(p)['master'].sharding.spec)
               for p in pmap.values() if not p.stop_gradient)
