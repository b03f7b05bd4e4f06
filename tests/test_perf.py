"""Performance-introspection tests (paddle_tpu/monitor/perf/).

The load-bearing assertions:
  1. the recompile ORACLE: one injected retrace after the warmup
     barrier produces exactly one perf_recompiles_total increment,
     attributed to this file's callsite and the offending abstract
     shapes, plus exactly one flight dump — and raises under strict;
  2. serving steady state: a full paged-engine burst ends armed with
     ZERO recompiles (the engine design's core invariant, now watched);
  3. the step timeline's phase arithmetic under a fake clock (sum of
     phases == wall, remainder lands in 'other', straggler detection
     fires against the rolling median) — sleep-free;
  4. the cost model reproduces exact analytic FLOPs on a known matmul
     and classifies it on the roofline;
  5. the disabled path stays near-free and records nothing.

All tests run CPU-only (conftest pins jax_platforms=cpu) and without
sleeps; the watchdog listener is process-global, so every test pairs
construction with close().
"""
import gc
import glob
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.monitor import MetricRegistry, set_default_registry
from paddle_tpu.monitor.perf import (COMPILE_EVENTS, CompileWatchdog,
                                     PHASES, RecompileError, StepTimeline,
                                     costmodel)
from paddle_tpu.monitor.runtime import jax_cache_entries
from paddle_tpu.monitor.telemetry import PERF_FAMILIES
from paddle_tpu.monitor.tracing import FlightRecorder, Tracer

REPO = __file__.rsplit('/tests/', 1)[0]


def _fresh_fn():
    """A never-before-jitted function (fresh closure -> fresh jit cache
    entry, so every call here genuinely compiles)."""
    salt = np.float32(np.random.rand())

    def f(x):
        return (x * salt).sum()
    return f


def _watchdog(tmp_path=None, **kw):
    """Watchdog + private registry + private tracer whose flight ring
    dumps (cooldown 0) into tmp_path when given."""
    reg = MetricRegistry()
    rec = FlightRecorder(dump_dir=str(tmp_path) if tmp_path else None,
                         cooldown=0.0, registry=reg)
    tracer = Tracer(recorder=rec, registry=reg)
    wd = CompileWatchdog(registry=reg, tracer=tracer, **kw)
    return wd, reg, tracer


# -- the recompile oracle ----------------------------------------------------

def test_recompile_oracle_attribution_and_flight_dump(tmp_path):
    wd, reg, _ = _watchdog(tmp_path, strict=False, name='oracle')
    try:
        if not wd.active:
            pytest.skip('jax.monitoring listeners unavailable')
        # numpy inputs: jnp.zeros would itself fire an eager compile
        # event per new shape and pollute the exact counts below
        f = jax.jit(_fresh_fn())
        f(np.zeros((4, 16), np.float32)).block_until_ready()
        assert wd.counts['compile'] >= 1
        assert wd.counts['trace'] >= 1
        wd.declare_warmup('oracle warm')
        assert wd.armed
        before = wd.counts['compile']

        f(np.zeros((4, 32), np.float32)).block_until_ready()  # RETRACE

        assert wd.counts['compile'] == before + 1
        assert wd.recompiles == 1
        assert reg.get('perf_recompiles_total').value() == 1.0
        rec = wd.records[-1]
        assert rec['after_warmup'] == 'oracle warm'
        assert 'test_perf' in rec['callsite']       # charged to US
        assert 'float32[4,32]' in rec['signature']  # the offending avals
        dumps = glob.glob(str(tmp_path / 'flight_recompile_*.json'))
        assert len(dumps) == 1                      # exactly one dump
        with open(dumps[0]) as fh:
            spans = json.load(fh)['spans']
        hits = [s for s in spans if s.get('name') == 'perf.recompile']
        assert len(hits) == 1
        assert hits[0]['tags']['signature'] == rec['signature']
    finally:
        wd.close()
    assert not wd.active


def test_strict_mode_raises_out_of_the_dispatch():
    wd, reg, _ = _watchdog(strict=True)
    try:
        if not wd.active:
            pytest.skip('jax.monitoring listeners unavailable')
        f = jax.jit(_fresh_fn())
        f(np.ones((2, 2), np.float32)).block_until_ready()
        wd.declare_warmup('strict warm')
        with pytest.raises(RecompileError, match='strict warm'):
            f(np.ones((2, 3), np.float32))
        assert wd.recompiles == 1
        # suspended(): deliberate compiles inside a warm window are fine
        with wd.suspended():
            f(np.ones((2, 4), np.float32)).block_until_ready()
        assert wd.recompiles == 1
        assert wd.armed                              # re-armed on exit
    finally:
        wd.close()


def test_owner_filter_ignores_other_objects_compiles():
    """Replica A's armed watchdog must not be tripped by a compile on a
    stack that never touches A (the gateway multi-replica hazard)."""
    class Owner:
        def compile_something(self, f, x):
            return f(x).block_until_ready()

    a, b = Owner(), Owner()
    wd, reg, _ = _watchdog(strict=False, owner=a)
    try:
        if not wd.active:
            pytest.skip('jax.monitoring listeners unavailable')
        wd.declare_warmup('owner warm')
        b.compile_something(jax.jit(_fresh_fn()),
                            np.ones((3, 3), np.float32))
        assert wd.recompiles == 0                    # b's compile: ignored
        a.compile_something(jax.jit(_fresh_fn()),
                            np.ones((3, 3), np.float32))
        assert wd.recompiles == 1                    # a's compile: charged
    finally:
        wd.close()


def test_watchdog_counts_cross_check_runtime_sampler():
    """The watchdog's event counts and the RuntimeSampler's trace-cache
    gauge watch the same phenomenon: a fresh jit compile must move
    BOTH."""
    wd, reg, _ = _watchdog()
    try:
        if not wd.active:
            pytest.skip('jax.monitoring listeners unavailable')
        # census entries die with their (weakly-referenced) functions, so
        # a GC pass inside the window can drop more entries than the
        # fresh compile adds when a long suite ran first. Collect before
        # EACH read so both censuses count only live entries, and keep a
        # strong ref to the jitted fn so its entries are alive at read 2.
        f = jax.jit(_fresh_fn())
        gc.collect()
        entries0 = jax_cache_entries()
        assert entries0 is not None and entries0 >= 0
        c0 = wd.counts['compile']
        f(np.ones((5,), np.float32)).block_until_ready()
        assert wd.counts['compile'] == c0 + 1
        gc.collect()
        assert jax_cache_entries() > entries0
    finally:
        wd.close()


def test_close_is_idempotent_and_no_events_after():
    wd, reg, _ = _watchdog()
    active = wd.active
    wd.close()
    wd.close()
    assert not wd.active
    if active:
        c0 = dict(wd.counts)
        jax.jit(_fresh_fn())(np.ones((7,), np.float32)) \
            .block_until_ready()
        assert wd.counts == c0


# -- serving steady state ----------------------------------------------------

def test_paged_engine_steady_state_zero_recompiles():
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    reg = MetricRegistry()
    prev = set_default_registry(reg)
    try:
        cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        paddle.seed(7)
        m = GPTForCausalLM(cfg)
        m.eval()
        eng = PagedContinuousBatchingEngine(m, num_seqs=4, max_len=48,
                                            page_size=8, prefill_chunk=8,
                                            decode_block=2)
        assert eng.perf.registry is reg
        assert not eng.perf.armed
        rng = np.random.RandomState(0)
        prompts = [[int(t) for t in rng.randint(0, 211, n)]
                   for n in (4, 7, 5, 9, 6, 8)]
        for p in prompts:
            eng.add_request(p, max_new_tokens=8)
        eng.run()
        # every program traced -> the engine armed itself mid-run...
        assert eng.perf.armed
        assert 'steady state' in eng.perf.warmup_label
        # ...and the burst stayed retrace-free
        assert eng.perf.recompiles == 0
        assert reg.get('perf_recompiles_total').value() == 0.0
        assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1,
                                        'verify': 0}
        # the timeline saw the decode bursts, split into real phases
        assert eng.timeline.steps > 0
        assert float(reg.get('perf_steps_total').value()) == \
            eng.timeline.steps
        summary = eng.timeline.summary()
        assert summary['host_dispatch']['count'] > 0
        assert summary['device_block']['count'] > 0
        # cost model over the stashed decode args: flat trace counts
        # (the lowering must hit the jaxpr cache, not retrace)
        est = eng.perf_estimate(bursts=eng.timeline.steps,
                                wall_seconds=1.0)
        assert est is not None
        assert est['flops'] > 0
        assert est['roofline_bound'] in ('compute', 'bandwidth')
        assert est['compile_s_warm'] >= 0.0
        assert 'mfu_est' in est
        assert eng.compiled_sizes()['decode'] == 1   # still 1: no retrace
        assert eng.perf.recompiles == 0
        eng.shutdown()
        assert not eng.perf.active
    finally:
        set_default_registry(prev)


def test_spec_engine_perf_estimate_prices_the_verify_program():
    """Under speculation the plain decode program never dispatches; the
    cost model must price the verify forward instead of returning
    None."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    dropout=0.0)
    paddle.seed(7)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = PagedContinuousBatchingEngine(m, num_seqs=2, max_len=48,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2, spec_k=3)
    try:
        eng.generate([[1, 2, 3, 4], [5, 6, 7]], max_new_tokens=6)
        assert eng._decode_args is None          # decode never ran
        est = eng.perf_estimate(bursts=eng.timeline.steps,
                                wall_seconds=0.5)
        assert est is not None
        assert est['flops'] > 0
        assert est['roofline_bound'] in ('compute', 'bandwidth')
        assert 'mfu_est' in est
        assert eng.compiled_sizes()['verify'] == 1   # no retrace
    finally:
        eng.shutdown()


def test_engine_rebind_perf_moves_registry_and_owner():
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    dropout=0.0)
    paddle.seed(7)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = PagedContinuousBatchingEngine(m, num_seqs=2, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2)
    try:
        old_wd = eng.perf
        reg = MetricRegistry()
        eng.rebind_perf(reg)
        assert not old_wd.active          # old listener unregistered
        assert eng.perf.registry is reg
        assert eng.timeline.registry is reg
        assert eng.perf.owner is eng
        assert not eng.perf.armed
    finally:
        eng.shutdown()


# -- step timeline -----------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def test_timeline_phase_sum_and_other_remainder():
    clock = FakeClock()
    reg = MetricRegistry()
    tl = StepTimeline(registry=reg, tracer=Tracer(registry=reg),
                      clock=clock)
    with tl.phase('data_wait'):
        clock.tick(0.25)
    with tl.phase('host_dispatch'):
        clock.tick(0.05)
    with tl.phase('device_block'):
        clock.tick(0.50)
    out = tl.end_step(wall_seconds=1.0)
    assert out['data_wait'] == pytest.approx(0.25)
    assert out['host_dispatch'] == pytest.approx(0.05)
    assert out['device_block'] == pytest.approx(0.50)
    assert out['other'] == pytest.approx(0.20)       # wall - phases
    assert out['total'] == pytest.approx(1.0)
    assert sum(out[p] for p in PHASES) == pytest.approx(out['total'])
    assert tl.steps == 1
    # the histograms saw exactly these observations
    count, total = reg.get('perf_step_phase_seconds') \
        .labels('device_block').value()
    assert count == 1 and total == pytest.approx(0.50)
    with pytest.raises(ValueError):
        tl.record('warp_drive', 1.0)
    assert tl.end_step() is None                     # nothing recorded


def test_timeline_straggler_detection_and_percentiles():
    clock = FakeClock()
    reg = MetricRegistry()
    tl = StepTimeline(registry=reg, tracer=Tracer(registry=reg),
                      clock=clock, straggler_factor=2.0, min_history=8)
    for _ in range(8):
        with tl.phase('device_block'):
            clock.tick(0.1)
        assert not tl.end_step()['straggler']
    assert tl.percentile(50) == pytest.approx(0.1)
    # 3x the median: flagged, counted, and visible in the registry
    with tl.phase('device_block'):
        clock.tick(0.3)
    assert tl.end_step()['straggler']
    assert tl.stragglers == 1
    assert reg.get('perf_stragglers_total').value() == 1.0
    # discard() drops a dangling partial step (epoch-end data_wait)
    with tl.phase('data_wait'):
        clock.tick(5.0)
    tl.discard()
    assert tl.end_step() is None
    assert tl.steps == 9


def test_timeline_disabled_path_records_nothing_and_stays_cheap():
    tl = StepTimeline(registry=MetricRegistry())
    tl.enabled = False
    with tl.phase('device_block'):
        pass
    tl.record('device_block', 1.0)
    assert tl.end_step(wall_seconds=9.9) is None
    assert tl.steps == 0
    # generous bound: 20k disabled phase entries must be trivially fast
    t0 = time.monotonic()
    for _ in range(20000):
        with tl.phase('host_dispatch'):
            pass
    assert time.monotonic() - t0 < 2.0


# -- cost model --------------------------------------------------------------

def test_cost_model_exact_flops_on_known_matmul():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    est = costmodel.estimate(lambda x, y: x @ y, args=(a, b),
                             step_seconds=0.001)
    if est is None:
        pytest.skip('backend exposes no cost analysis')
    assert est['flops'] == 2.0 * 64 * 128 * 32       # 524288 exactly
    assert est['bytes_accessed'] > 0
    assert est['arithmetic_intensity'] == pytest.approx(
        est['flops'] / est['bytes_accessed'])
    assert est['roofline_bound'] in ('compute', 'bandwidth')
    assert est['ideal_step_s'] > 0
    assert est['mfu_est'] == pytest.approx(
        est['flops'] / 0.001 / est['peak_flops'])
    assert 0 < est['roofline_frac'] <= 1.0 or est['roofline_frac'] >= 0


def test_cost_model_roofline_classification():
    # intensity 1000 on v5e's ridge of 197e12/819e9 ~ 240 -> compute-bound
    # (a TPU is named by its device_kind: bare 'tpu' is not one chip)
    r = costmodel.roofline(1000.0e9, 1.0e9, platform='TPU v5 lite')
    assert r['roofline_bound'] == 'compute'
    assert r['ridge_intensity'] == pytest.approx(197e12 / 819e9)
    # intensity 1 -> far under any ridge -> bandwidth-bound
    r = costmodel.roofline(1.0e9, 1.0e9, platform='TPU v5 lite')
    assert r['roofline_bound'] == 'bandwidth'
    assert r['ideal_step_s'] == pytest.approx(1.0e9 / 819e9)
    # overrides beat the table
    r = costmodel.roofline(10.0, 1.0, platform='anything',
                           peak_flops=20.0, peak_bandwidth=1.0)
    assert r['ideal_step_s'] == pytest.approx(1.0)


def test_cost_model_record_publishes_gauges():
    reg = MetricRegistry()
    est = {'mfu_est': 0.37, 'arithmetic_intensity': 120.5,
           'roofline_bound': 'bandwidth'}
    costmodel.record(est, registry=reg)
    assert reg.get('perf_mfu_est').value() == pytest.approx(0.37)
    assert reg.get('perf_arithmetic_intensity').value() == \
        pytest.approx(120.5)
    assert reg.get('perf_roofline_bound').value() == 0.0
    assert costmodel.record(None, registry=reg) is None


# -- Model.fit / summary_perf wiring -----------------------------------------

def _tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 4), nn.Linear(4, 1))
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters()),
        loss=nn.MSELoss())
    return model


def test_model_fit_wires_timeline_and_watchdog():
    from paddle_tpu.io import Dataset

    class DS(Dataset):
        def __len__(self):
            return 12

        def __getitem__(self, i):
            return (np.full((8,), i, np.float32),
                    np.zeros((1,), np.float32))

    reg = MetricRegistry()
    prev = set_default_registry(reg)
    try:
        model = _tiny_model()
        model.fit(DS(), batch_size=4, epochs=2, verbose=0, shuffle=False)
        # the fit loop finalized one timeline step per batch
        steps = reg.get('perf_steps_total').value()
        assert steps == 6                            # 3 batches x 2 epochs
        count, _ = reg.get('perf_step_phase_seconds') \
            .labels('data_wait').value()
        assert count == 6
        # epoch 1 re-ran the SAME shapes: zero post-warmup recompiles
        assert reg.get('perf_recompiles_total').value() == 0.0
        assert model._perf_timeline is None          # cleaned up
    finally:
        set_default_registry(prev)


def test_model_summary_perf_reports_cost_model():
    import paddle_tpu as paddle
    reg = MetricRegistry()
    model = _tiny_model()
    x = paddle.to_tensor(np.random.rand(4, 8).astype('float32'))
    y = paddle.to_tensor(np.random.rand(4, 1).astype('float32'))
    est = model.summary_perf([x], [y], step_seconds=0.01, registry=reg)
    if est is None:
        pytest.skip('backend exposes no cost analysis')
    assert est['flops'] > 0
    assert est['roofline_bound'] in ('compute', 'bandwidth')
    assert est['mfu_est'] > 0
    assert reg.get('perf_mfu_est').value() == pytest.approx(
        est['mfu_est'])


# -- schema + tooling --------------------------------------------------------

def test_perf_families_are_in_the_committed_baseline():
    with open(os.path.join(REPO, 'tools',
                           'metrics_schema_baseline.json')) as fh:
        baseline = json.load(fh)
    for kind, name, _doc, labels in PERF_FAMILIES:
        assert name in baseline, name
        assert baseline[name]['type'] == kind
        assert tuple(baseline[name].get('labels', [])) == labels
    assert len(COMPILE_EVENTS) == 3


def test_perf_report_cli_joins_snapshot_flight_and_bench(tmp_path):
    from paddle_tpu.monitor import telemetry
    # a snapshot with live perf counters folded in
    reg = MetricRegistry()
    wd = CompileWatchdog(registry=reg,
                         tracer=Tracer(registry=reg))
    wd.enabled = False                      # no live listening needed
    wd._on_event('/jax/core/compile/backend_compile_duration', 1.25)
    tl = StepTimeline(registry=reg, tracer=Tracer(registry=reg),
                      clock=FakeClock())
    tl.record('device_block', 0.5)
    tl.end_step()
    wd.close()
    treg = telemetry.dryrun_registry(0.5, 1.0, batch=4, registry=reg)
    snap = tmp_path / 'snap.txt'
    snap.write_text(telemetry.snapshot_line(treg, 8, '[perf]') + '\n')
    # a flight dump carrying one recompile span
    rec = FlightRecorder(dump_dir=str(tmp_path), cooldown=0.0,
                         registry=reg)
    rec.record({'name': 'perf.recompile', 'start': 1.0, 'duration': 0.2,
                'tags': {'duration_s': 0.2, 'callsite': 'x.py:1:f',
                         'signature': 'float32[2,2]'}})
    rec.dump('recompile')
    # a bench row carrying the perf fields
    bench_path = tmp_path / 'cap.jsonl'
    bench_path.write_text(json.dumps(
        {'metric': 'serving_cb_tokens_per_sec', 'value': 100.0,
         'compile_s_cold': 3.2, 'compile_s_warm': 0.1, 'recompiles': 0,
         'mfu_est': 0.21, 'roofline_bound': 'bandwidth'}) + '\n')

    import importlib.util
    spec = importlib.util.spec_from_file_location(
        '_perf_report', os.path.join(REPO, 'tools', 'perf_report.py'))
    pr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pr)
    lines = pr.report(snap_text=snap.read_text(),
                      flight_dir=str(tmp_path),
                      bench_paths=[str(bench_path)])
    text = '\n'.join(lines)
    assert 'config perf' in text
    assert 'compiles[compile]: 1 (mean 1.250s)' in text
    assert 'phase device_block' in text
    assert 'recompile 0.200s at x.py:1:f' in text
    assert 'signature: float32[2,2]' in text
    assert 'serving_cb_tokens_per_sec' in text and '0.21' in text
