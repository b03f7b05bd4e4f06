"""The process's compile log (`monitor/perf/watchdog.py`): every program
jax builds, which stage, whose, and what was traced inside it; the
watchdog reads its events from the log's listener and counts what it
counted before."""
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework.functional import TrainStep
from paddle_tpu.monitor import MetricRegistry, tracing
from paddle_tpu.monitor.perf import CompileWatchdog, compile_log
from paddle_tpu.serving import PagedContinuousBatchingEngine
from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

STAGES = ('trace', 'lower', 'compile')


def _fresh_fn():
    """A never-before-jitted function: every call here compiles."""
    salt = np.float32(np.random.rand())

    def fresh(x):
        return (x * salt).sum()
    return fresh


def _engine_run():
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(vocab_size=211, hidden_size=64,
                                 num_layers=2, num_heads=4,
                                 max_position_embeddings=128, dropout=0.0))
    m.eval()
    eng = PagedContinuousBatchingEngine(m, num_seqs=2, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2)
    try:
        eng.generate([[1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11, 12]],
                     max_new_tokens=4)
    finally:
        eng.shutdown()
    return type(eng).__name__, {'_prefill_fn', '_decode_fn'}


def _train_run():
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    ce = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda lo, la: ce(lo, la), opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 4, (4,)).astype(np.int64))
    step(x, y)
    step.multi_step(paddle.to_tensor(rng.randn(2, 4, 8).astype(np.float32)),
                    paddle.to_tensor(rng.randint(0, 4, (2, 4))
                                     .astype(np.int64)))
    return 'TrainStep', {'pure_step', 'multi'}


class _Bare:
    """A plain jax.monitoring listener: what a sum of every event reads."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if event.startswith('/jax/core/compile/'):
            self.seconds += duration
            self.events += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self)


def _eager_op():
    """An eager op on a shape no other test uses: a program of its own."""
    n = int(np.random.randint(10 ** 5, 10 ** 6))
    return (jax.numpy.zeros((n,), np.float32) + 1.0).block_until_ready()


@pytest.mark.parametrize('run', [_engine_run, _train_run],
                         ids=['engine', 'train_step'])
def test_programs_are_owned_and_sums_match_a_plain_listener(run):
    bare = _Bare()
    try:
        owner, programs = run()
        _eager_op()
    finally:
        bare.close()
    log = compile_log()
    entries = log.entries()
    tags = [e['tags'] for e in entries]
    # the owner's programs carry its name, at every stage ...
    owned = {(t['program'], t['stage']) for t in tags
             if t['owner'] == owner}
    assert {(p, s) for p in programs for s in STAGES} <= owned
    # ... eager ops and helpers nobody registered carry none
    assert {t['owner'] for t in tags} == {owner, None}
    assert any(t['program'] == 'add' and t['owner'] is None
               and t['stage'] == 'compile' for t in tags)
    # nested-inclusive = what a plain listener sums; outer-only below it
    tot = log.totals(time.monotonic())
    assert sum(tot['nested_s'].values()) == pytest.approx(bare.seconds,
                                                          abs=1e-6)
    nested = sum(n for t in tags for n in t['nested_n'].values())
    assert sum(tot['counts'].values()) == bare.events == \
        len(entries) + nested
    assert nested > 0                  # jnp helpers traced inside
    for s in STAGES:
        assert 0.0 <= tot['outer_s'][s] <= tot['nested_s'][s]
    assert 0.0 < tot['owned_s'] <= sum(tot['outer_s'].values())
    assert tot['programs'] == tot['counts']['compile'] > 0
    assert {t['cache'] for t in tags if t['stage'] == 'compile'} <= \
        {'hit', 'miss', 'none'}
    assert {t['cache'] for t in tags if t['stage'] != 'compile'} == {None}
    # the costliest program first; a stamp before it all reads zero
    top = log.report(top=3)
    assert [r['seconds'] for r in top] == sorted(
        (r['seconds'] for r in top), reverse=True)
    assert log.totals(entries[0]['end_mono'])['programs'] == 0


def test_a_program_traced_inside_another_is_summed_into_it():
    seen = []

    def bare(event, duration, fun_name='', **kw):
        if event.endswith('jaxpr_trace_duration'):
            seen.append((fun_name, duration))
    jax.monitoring.register_event_duration_secs_listener(bare)
    inner = jax.jit(_fresh_fn())
    outer = jax.jit(lambda x: inner(x) + 1.0)
    try:
        outer(np.ones((5,), np.float32)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(bare)
    log = compile_log()
    entries = log.entries()
    # one entry a stage, the lambda's: the inner trace is in its sums
    assert [(e['tags']['program'], e['tags']['stage'])
            for e in entries] == [('<lambda>', s) for s in STAGES]
    lam = entries[0]['tags']
    assert seen[-1][1] == lam['seconds']
    inside = [d for f, d in seen[:-1]]
    assert any('fresh' in f for f, d in seen[:-1])
    assert lam['nested_n']['trace'] == len(inside)
    assert lam['nested_s']['trace'] == pytest.approx(sum(inside))
    tot = log.totals(time.monotonic())
    assert tot['outer_s']['trace'] == lam['seconds']
    assert tot['nested_s']['trace'] == pytest.approx(
        lam['seconds'] + sum(inside))
    assert tot['counts']['trace'] == 1 + len(inside)
    # one program built: the inner one is part of the outer's
    assert tot['programs'] == 1
    assert [r['program'] for r in log.report()] == ['<lambda>']


def test_entries_are_span_dicts_on_both_clocks():
    jax.jit(_fresh_fn())(np.ones((3,), np.float32)).block_until_ready()
    entries = compile_log().entries()
    span = tracing.Tracer(registry=MetricRegistry()).start_span('x')
    span.finish()
    assert entries and all(set(e) == set(span.to_dict()) for e in entries)
    for e in entries:
        assert e['name'] == 'perf.compile'
        d = e['tags']['seconds']
        assert e['end'] - e['start'] == pytest.approx(d, abs=1e-6)
        assert e['end_mono'] - e['start_mono'] == pytest.approx(d, abs=1e-6)
    assert [(e['tags']['program'], e['tags']['stage'])
            for e in entries] == [('fresh', s) for s in STAGES]
    chrome = tracing.spans_to_chrome(entries)['traceEvents']
    assert [ev['name'] for ev in chrome[1:]] == \
        ['perf.compile'] * len(entries)


@pytest.mark.parametrize('cut', ['full', 'tracing_off'])
def test_a_log_that_left_entries_out_reads_none(cut, monkeypatch):
    """Keep-first: once the log is full, or tracing was off, no total
    that reaches past the first entry left out is given."""
    log = compile_log()
    f = jax.jit(_fresh_fn())
    f(np.ones((2,), np.float32)).block_until_ready()
    before = time.monotonic()
    kept = len(log.entries())
    if cut == 'full':
        monkeypatch.setattr(log, 'capacity', kept)
        f(np.ones((3,), np.float32)).block_until_ready()
    else:
        tracer = tracing.default_tracer()
        tracer.disable()
        try:
            f(np.ones((3,), np.float32)).block_until_ready()
            assert log.totals(before) is None       # tracing off: nothing
        finally:
            tracer.enable()
    assert len(log.entries()) == kept and log.left_out >= 3
    assert log.totals(before)['programs'] == 1
    assert log.totals(time.monotonic()) is None
    log.reset()
    assert log.left_out == 0 and log.totals(time.monotonic()) is not None


def test_the_watchdog_reads_the_log_and_registers_no_listener():
    from jax._src import monitoring   # the listener lists jax keeps
    listeners = len(monitoring.get_event_duration_listeners())
    reg = MetricRegistry()
    wd = CompileWatchdog(registry=reg, strict=False, name='log',
                         tracer=tracing.Tracer(registry=reg))
    log = compile_log()
    try:
        assert wd.active
        assert len(monitoring.get_event_duration_listeners()) == listeners
        f = jax.jit(_fresh_fn())
        f(np.zeros((4, 16), np.float32)).block_until_ready()
        # the watchdog counts every event the log saw, nested ones too
        assert wd.counts == log.totals(time.monotonic())['counts']
        assert wd.counts['compile'] == 1
        assert reg.get('perf_compiles_total').labels('compile').value() == 1
        wd.declare_warmup('log warm')
        f(np.zeros((4, 32), np.float32)).block_until_ready()  # RETRACE
        assert wd.counts == log.totals(time.monotonic())['counts']
        assert wd.recompiles == 1
        rec = wd.records[-1]
        assert 'test_compile_log' in rec['callsite']
        assert 'float32[4,32]' in rec['signature']
        # the flight ring holds the record and where the retrace went
        ring = wd._tracer.recorder.spans()
        assert [s['name'] for s in ring] == ['perf.recompile'] + \
            ['perf.compile'] * 3
        assert [(s['tags']['program'], s['tags']['stage'])
                for s in ring[1:]] == [('fresh', s) for s in STAGES]
        assert ring[1:] == log.entries()[-3:]
    finally:
        wd.close()
    assert not wd.active
    counts = dict(wd.counts)
    f(np.zeros((4, 64), np.float32)).block_until_ready()
    assert wd.counts == counts
    # the log saw all three programs, the watchdog gone or not
    assert log.totals(time.monotonic())['programs'] == 3
