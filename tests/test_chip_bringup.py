"""What the bring-up PR promised, pinned on the CPU.

The path to the chip has no hidden fallback: chip_smoke.py and bench.py fail
without a TPU (the smoke's rehearsal switch runs the same phases at toy widths
and never prints the ok line); the compile cache lives where
JAX_COMPILATION_CACHE_DIR says; a device asked for by name raises when it is
not there; the peaks table does not know "a TPU"; a flash kernel that was
chosen and raises is not replaced by a reference; a mesh-jitted step runs the
Pallas kernels under shard_map; the engines' cache donation — on by default
only on the chip — gives the same tokens; and a process that holds the chip
does not start a child that needs it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import device as device_mod
from paddle_tpu.monitor.perf import costmodel
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, **env):
    """A repo script in a CPU child with its own compile cache dir."""
    full = dict(os.environ, JAX_PLATFORMS='cpu',
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'jax_cache'), **env)
    return subprocess.run([sys.executable] + args, cwd=_REPO, env=full,
                          capture_output=True, text=True, timeout=300)


# ---- chip_smoke.py / bench.py: exit codes and the last line -----------------

@pytest.mark.parametrize('chips,phases', [
    (1, ('train:', 'serve/paged:', 'serve/gateway:', 'serve/parity:',
         'serve/bf16:')),
    (4, ('multichip/dp2 x mp2:', 'multichip/dp2 x sharding2:'))])
def test_chip_smoke_rehearsal_runs_every_phase(tmp_path, chips, phases):
    proc = _run(['chip_smoke.py', '--rehearse', '--chips', str(chips)],
                tmp_path,
                XLA_FLAGS='--xla_force_host_platform_device_count=%d' % chips)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    for phase in phases:
        assert any(ln.startswith(phase) for ln in lines), (phase, lines)
    # --chips 4 runs the multichip phase and nothing else
    assert any(ln.startswith('train:') for ln in lines) == (chips == 1)
    # a rehearsal is not a chip run: it never prints the ok line
    assert '"ok"' not in proc.stdout
    assert 'rehearsal' in lines[-1]


@pytest.mark.parametrize('script', ['chip_smoke.py', 'bench.py'])
def test_no_chip_means_no_result(tmp_path, script):
    proc = _run([script], tmp_path)
    assert proc.returncode != 0
    assert '{' not in proc.stdout, proc.stdout   # no row, no ok line
    assert 'TPU' in proc.stderr


def test_chip_smoke_ok_line_format(monkeypatch, capsys):
    """The last line is exactly the contract's object, device as jax reports
    it — checked by letting the phases pass trivially on a pretend TPU."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'chip_smoke', os.path.join(_REPO, 'chip_smoke.py'))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    class Dev:
        platform, device_kind = 'tpu', 'TPU v5 lite'
    monkeypatch.setattr(jax, 'devices', lambda *a: [Dev()])
    monkeypatch.setattr(smoke, 'phase_train', lambda *a: None)
    monkeypatch.setattr(smoke, 'phase_serve', lambda *a: None)
    from paddle_tpu.framework import compile_cache
    monkeypatch.setattr(compile_cache, 'configure', lambda: '/x')
    smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(last)['ok'] is True


def test_bench_extra_exits_nonzero_when_a_rung_raised(monkeypatch, capsys):
    import bench_extra
    from paddle_tpu.framework import compile_cache
    monkeypatch.setattr(compile_cache, 'configure', lambda: None)
    for name in dir(bench_extra):
        if name.startswith('bench_'):
            monkeypatch.setattr(bench_extra, name,
                                lambda on_tpu, _n=name: {'metric': _n})

    def boom(on_tpu):
        raise RuntimeError('rung exploded')
    monkeypatch.setattr(bench_extra, 'bench_serving_fabric', boom)
    with pytest.raises(RuntimeError, match='rung exploded'):
        bench_extra.main()
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    # the error row is printed and the later rungs still ran
    assert any('rung exploded' in r.get('error', '') for r in rows)
    assert rows[-1]['metric'] == 'bench_ingest'


# ---- where the compile cache lives ------------------------------------------

_RESOLVE = ('import jax; from paddle_tpu.framework import compile_cache as c;'
            'print(c.configure(%s)); print(jax.config.jax_compilation_cache_dir)')


@pytest.mark.parametrize('env_dir,arg,want', [
    ('/x/placed', None, '/x/placed'),          # env set: used
    ('/x/placed', '/y/predictor', '/x/placed'),  # ... and never overwritten
    (None, None, os.path.join(_REPO, '.jax_cache'))])  # unset: fixed path
def test_compile_cache_dir_resolution(env_dir, arg, want):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    if env_dir:
        env['JAX_COMPILATION_CACHE_DIR'] = env_dir
    proc = subprocess.run([sys.executable, '-c', _RESOLVE % repr(arg)],
                          cwd=_REPO, env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want]


# ---- devices and peaks asked for by name ------------------------------------

def test_set_device_tpu_raises_without_a_tpu():
    for name in ('tpu', 'tpu:0', 'gpu'):
        with pytest.raises(RuntimeError, match='asked for by name'):
            device_mod.resolve_device(name)
    with pytest.raises(RuntimeError, match='asked for by name'):
        paddle.set_device('tpu')
    # the Place facades mean "the accelerator, whatever it is"
    assert device_mod.resolve_device(paddle.CUDAPlace(0)) == jax.devices()[0]
    assert device_mod.resolve_device(paddle.CPUPlace()).platform == 'cpu'


def test_unknown_tpu_kind_has_no_peaks():
    assert costmodel.platform_peaks('TPU v5 lite')[1:] == (197e12, 819e9)
    for kind in ('TPU v4', 'tpu'):     # 'tpu' is not one chip
        with pytest.raises(KeyError, match='no peaks recorded'):
            costmodel.platform_peaks(kind)
    # both peaks given: nothing to look up
    assert costmodel.platform_peaks('TPU v9', 2.0, 1.0) == ('TPU v9', 2., 1.)


# ---- flash attention: routing before the call, no catch after it ------------

def _qkv(n=256, d=64):
    rng = np.random.RandomState(0)
    return tuple(jnp.asarray(rng.randn(1, 2, n, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize('impl', ['_fwd_impl', '_bwd_impl'])
def test_chosen_flash_kernel_failure_propagates(monkeypatch, impl):
    """A kernel that was chosen and raises (a Mosaic compile error, say) is
    not replaced by the jnp reference, forward or backward, strict or not."""
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '0')

    def mosaic_error(*a, **kw):
        raise RuntimeError('Mosaic failed to compile TPU kernel')
    monkeypatch.setattr(fa, impl, mosaic_error)
    q, k, v = _qkv()
    with pytest.raises(RuntimeError, match='Mosaic failed'):
        jax.grad(lambda q: fa.flash_attention_bhnd(q, k, v).sum())(q)


def test_ineligible_shape_routes_to_blockwise_by_name(monkeypatch):
    """head_dim 80 cannot take the kernels: sdpa decides that before the
    call and runs the blockwise op — the flash op is never entered."""
    import paddle_tpu.nn.functional as F
    monkeypatch.setattr(fa, 'is_available', lambda: True)
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '0')

    def not_flash(*a, **kw):
        raise AssertionError('flash op entered for an ineligible shape')
    monkeypatch.setattr(fa, 'flash_attention_bnhd', not_flash)
    rng = np.random.RandomState(1)
    q, k, v = (paddle.to_tensor(rng.randn(1, 512, 2, 80).astype(np.float32))
               for _ in range(3))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = fa._ref_bhnd(*(jnp.swapaxes(t._data, 1, 2) for t in (q, k, v)),
                       True, 80 ** -0.5)
    np.testing.assert_allclose(out.numpy(), np.swapaxes(ref, 1, 2),
                               rtol=2e-5, atol=2e-5)
    # strict mode turns the same routing decision into an error
    monkeypatch.undo()
    monkeypatch.setattr(fa, 'is_available', lambda: True)
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '1')
    with pytest.raises(RuntimeError, match='head_dim'):
        F.scaled_dot_product_attention(q, k, v, is_causal=True)


# ---- a mesh-jitted step runs the Pallas kernels under shard_map -------------

def _flash_lm_step(build_step):
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
        max_position_embeddings=512, dropout=0.0, fused_loss=True))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    return build_step(model, lambda out, labels: model.loss(out, labels), opt)


@pytest.fixture
def flash_batch(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '1')
    rng = np.random.RandomState(0)
    return tuple(paddle.to_tensor(rng.randint(0, 512, (4, 512))
                                  .astype(np.int32)) for _ in range(2))


@pytest.mark.parametrize('hybrid,extra', [
    ({'dp_degree': 2, 'mp_degree': 2}, {}),
    ({'dp_degree': 2, 'sharding_degree': 2},
     {'sharding': True, 'sharding_configs': {'stage': 3}})])
def test_fleet_step_partitions_flash_kernels(flash_batch, hybrid, extra):
    """GSPMD cannot partition a Mosaic kernel (on the chip the compile
    fails), so fleet_train_step names the attention operands' layout and
    the kernels run per shard — same losses as the single-device step."""
    from jax.sharding import Mesh
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import HybridCommunicateGroup
    from paddle_tpu.framework.functional import TrainStep
    single = _flash_lm_step(TrainStep)
    ref = [float(single(*flash_batch).numpy()) for _ in range(2)]

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(
        {'dp_degree': 1, 'mp_degree': 1, 'pp_degree': 1,
         'sharding_degree': 1, 'sp_degree': 1, 'ep_degree': 1}, **hybrid)
    for key, value in extra.items():
        if isinstance(value, dict):
            getattr(strategy, key).update(value)
        else:
            setattr(strategy, key, value)
    hcg = HybridCommunicateGroup(devices=jax.devices()[:4],
                                 **strategy.hybrid_configs)
    assert isinstance(hcg.mesh, Mesh) and hcg.mesh.size == 4
    step = _flash_lm_step(lambda m, loss_fn, opt: fleet.fleet_train_step(
        m, loss_fn, opt, strategy=strategy, hcg=hcg))
    losses = [float(step(*flash_batch).numpy()) for _ in range(2)]
    np.testing.assert_allclose(losses, ref, rtol=1e-5)
    jaxpr = step.trace_jaxpr(*flash_batch)
    assert 'pallas_call' in jaxpr and 'shard_map' in jaxpr


# ---- cache donation: the device-only default, exercised here ----------------

@pytest.fixture(scope='module')
def toy_lm():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=128, dropout=0.0))
    model.eval()
    rng = np.random.RandomState(3)
    prefix = [int(t) for t in rng.randint(0, 211, 16)]
    prompts = [(prefix if i % 2 else []) +
               [int(t) for t in rng.randint(0, 211, n)]
               for i, n in enumerate((3, 17, 7, 12, 5, 21, 9, 4))]
    return model, prompts


@pytest.mark.parametrize('kind', ['paged', 'spec', 'preempt'])
def test_engine_cache_donation_keeps_tokens(toy_lm, kind):
    """The engine donates its KV pools on tpu/gpu only, so that branch
    never ran in a CPU test. jax's CPU backend does donate when asked:
    with donate=True every old buffer is deleted at dispatch, and prefix
    reuse, speculative verify, preemption/replay and perf_estimate's
    stashed arguments must still give the tokens of the undonated run."""
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    model, prompts = toy_lm

    def engine(donate):
        kw = {'spec': {'spec_k': 3},
              'preempt': {'preempt': True, 'num_pages': 13}}.get(kind, {})
        return PagedContinuousBatchingEngine(
            model, num_seqs=2, max_len=64, page_size=8, prefill_chunk=8,
            decode_block=2, donate=donate, **kw)

    tokens = []
    for donate in (False, True):
        eng = engine(donate)
        held = jax.tree_util.tree_leaves(eng._pools)
        reqs = [eng.add_request(p, max_new_tokens=10, priority=i % 3)
                for i, p in enumerate(prompts)]
        eng.run()
        assert all(a.is_deleted() for a in held) is donate
        assert eng.perf_estimate() is not None
        tokens.append([r.tokens for r in reqs])
    assert tokens[0] == tokens[1]


# ---- one process per chip ---------------------------------------------------

def test_fabric_refuses_a_worker_that_would_need_the_parents_chip(
        monkeypatch):
    from paddle_tpu.serving.fabric import spawn_worker
    assert not device_mod.process_holds_accelerator()   # tests run on cpu
    monkeypatch.setattr(device_mod, 'process_holds_accelerator', lambda: True)
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    with pytest.raises(RuntimeError, match='one process per chip'):
        spawn_worker(preset='gpt-nano')
