"""Distributed request tracing (paddle_tpu/monitor/tracing.py) and its
three consumers:

  1. cross-process propagation — ResilientChannel injects per-attempt
     trace context, the PS/graph servers continue the trace, and one
     faulted request yields a single causally-linked span tree across
     client retries and the server handler;
  2. serving lifecycle — queued→admit→prefill→decode→retire spans with
     prefix-cache-hit / spec-accept events, TTFT exemplars;
  3. flight recorder + export — bounded ring, exactly-one dump on
     circuit-open / deadline expiry, /debug/traces, Chrome-trace export
     merged by profiler.merge_traces into rank-grouped lanes.

Plus the no-overhead guard: tracing disabled must not measurably slow
the RPC or serving decode hot paths (same discipline as the metrics
registry's disabled-path test in test_monitor.py).
"""
import collections
import glob
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.monitor import (MetricRegistry, MetricsServer, to_dict,
                                tracing)
from paddle_tpu.monitor.registry import set_default_registry
from paddle_tpu.monitor.tracing import (NULL_SPAN, TRACE_KEY,
                                        FlightRecorder, Tracer,
                                        set_default_tracer,
                                        spans_to_chrome)
from paddle_tpu.distributed.resilience import (CircuitBreaker,
                                               CircuitOpenError, Deadline,
                                               DeadlineExceeded,
                                               ResilientChannel,
                                               RetryPolicy)
from paddle_tpu.distributed.ps.embedding_service import EmbeddingServer
from paddle_tpu.serving import PagedContinuousBatchingEngine
from paddle_tpu.testing import chaos
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

FAST = dict(retry_policy=RetryPolicy(max_attempts=4, base_delay=0.02,
                                     max_delay=0.05),
            call_timeout=2.0)


@pytest.fixture(autouse=True)
def no_leaked_faults():
    yield
    assert chaos.active_faults() == 0, 'a chaos injector leaked'


@pytest.fixture
def traced(tmp_path):
    """Fresh registry + tracer (flight dir under tmp_path) installed as
    the process defaults. Swapped in BEFORE anything under test is
    constructed — engines and channels cache the default at creation."""
    reg = MetricRegistry()
    flight = tmp_path / 'flight'
    rec = FlightRecorder(capacity=256, dump_dir=str(flight),
                         cooldown=3600.0, registry=reg)
    tr = Tracer(registry=reg, recorder=rec)
    prev_reg = set_default_registry(reg)
    prev_tr = set_default_tracer(tr)
    yield tr, reg, flight
    set_default_tracer(prev_tr)
    set_default_registry(prev_reg)


@pytest.fixture(scope='module')
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# -- tracer core -------------------------------------------------------------

def test_span_identity_nesting_and_clock():
    t = [100.0]
    tr = Tracer(registry=MetricRegistry(), clock=lambda: t[0])
    with tr.start_span('outer', tags={'k': 'v'}) as outer:
        assert len(outer.trace_id) == 32 and len(outer.span_id) == 16
        assert outer.parent_id is None
        assert tr.current() is outer
        t[0] = 101.5
        with tr.start_span('inner') as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert inner.start == 101.5
            t[0] = 102.0
        assert tr.current() is outer
    assert tr.current() is None
    assert outer.end == 102.0
    d = [s for s in tr.recorder.spans() if s['name'] == 'outer'][0]
    assert d['tags'] == {'k': 'v'} and d['status'] == 'ok'
    # explicit parent and wire ctx both beat the contextvar
    child = tr.start_span('c', parent=outer)
    assert child.parent_id == outer.span_id
    remote = tr.start_span('r', ctx=outer.ctx())
    assert (remote.trace_id, remote.parent_id) == (outer.trace_id,
                                                   outer.span_id)
    child.finish()
    remote.finish()
    remote.finish()                  # idempotent


def test_span_exit_records_error():
    tr = Tracer(registry=MetricRegistry())
    with pytest.raises(ValueError):
        with tr.start_span('boom'):
            raise ValueError('x')
    d = tr.recorder.spans()[-1]
    assert d['status'] == 'error' and 'ValueError' in d['error']


def test_disabled_tracer_is_null_and_cheap():
    reg = MetricRegistry()
    tr = Tracer(enabled=False, registry=reg)
    sp = tr.start_span('anything')
    assert sp is NULL_SPAN and not sp
    assert sp.ctx() is None
    with sp as s:
        s.set_tag('a', 1).add_event('e').set_error(ValueError())
    sp.finish()
    snap = to_dict(reg)
    assert snap['trace_spans_started_total']['samples'][0]['value'] == 0
    assert snap['trace_spans_finished_total']['samples'][0]['value'] == 0
    assert len(tr.recorder.spans()) == 0
    t0 = time.perf_counter()
    for _ in range(100_000):
        tr.start_span('x')
    assert time.perf_counter() - t0 < 1.0


def test_server_span_always_pops_trace_key():
    tr = Tracer(enabled=False, registry=MetricRegistry())
    msg = {'op': 'pull', TRACE_KEY: {'trace_id': 'aa', 'span_id': 'bb'}}
    assert tr.server_span(msg, 'ps.server') is NULL_SPAN
    assert TRACE_KEY not in msg      # handlers never see the metadata
    tr.enable()
    msg2 = {'op': 'pull', TRACE_KEY: {'trace_id': 'aa', 'span_id': 'bb'}}
    sp = tr.server_span(msg2, 'ps.server')
    assert TRACE_KEY not in msg2
    assert sp.name == 'ps.server.pull'
    assert (sp.trace_id, sp.parent_id) == ('aa', 'bb')
    sp.finish()
    # untraced message on an enabled tracer: no span, nothing popped
    assert tr.server_span({'op': 'pull'}, 'ps.server') is NULL_SPAN


def test_default_ring_holds_a_serving_window(model, traced):
    """The benchmark's `window_spans` readers give None once the ring has
    dropped a span of their window, so the default ring has to hold what
    the engine finishes in one: the spans a step and a request, COUNTED
    from a run (a span added to the step shows here), times the steps and
    requests of the fullest window a cell takes — 45 s of
    serve-xl.offline-decode, 990 steps and 128 requests at 45 ms a step
    (PERF.md section 7) — with room for a step a third as long."""
    tr, reg, flight = traced
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    n_requests = 4
    for i in range(n_requests):
        eng.add_request([1 + i, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=5)
    eng.run()
    assert tr.recorder.dropped == 0
    count = collections.Counter(s['name'] for s in tr.recorder.spans())
    steps = count.pop('serving.step')
    of_a_request = sum(count.pop(n) for n in (
        'serving.request', 'serving.prefill', 'serving.decode',
        'serving.prefill_call'))
    a_step = 1 + sum(count.values()) / steps      # what is left nests in it
    a_request = of_a_request / n_requests
    window = 3 * (990 * a_step + 128 * a_request)
    assert window <= FlightRecorder(registry=MetricRegistry()).capacity


def test_flight_recorder_ring_dump_and_cooldown(tmp_path):
    reg = MetricRegistry()
    t = [0.0]
    rec = FlightRecorder(capacity=3, dump_dir=str(tmp_path),
                         cooldown=10.0, registry=reg, clock=lambda: t[0])
    for i in range(5):
        rec.record({'name': 'n%d' % i})
    assert len(rec) == 3 and rec.dropped == 2
    assert [s['name'] for s in rec.spans()] == ['n2', 'n3', 'n4']
    p1 = rec.maybe_dump('chaos_fault')
    assert p1 and os.path.exists(p1)
    payload = json.load(open(p1))
    assert payload['reason'] == 'chaos_fault'
    assert payload['span_count'] == 3 and payload['dropped'] == 2
    assert rec.maybe_dump('chaos_fault') is None          # cooldown
    assert rec.maybe_dump('circuit_open') is not None     # other reason
    t[0] = 11.0
    assert rec.maybe_dump('chaos_fault') is not None      # window over
    snap = to_dict(reg)
    fam = snap['trace_flight_dumps_total']['samples']
    by_reason = {s['labels']['reason']: s['value'] for s in fam}
    assert by_reason == {'chaos_fault': 2.0, 'circuit_open': 1.0}
    # no dump_dir -> inspection only
    rec2 = FlightRecorder(capacity=3, registry=reg)
    assert rec2.dump_dir is None or 'PADDLE_TPU_FLIGHT_DIR' in os.environ
    rec2.dump_dir = None
    assert rec2.maybe_dump('chaos_fault') is None
    with pytest.raises(ValueError):
        rec2.dump()
    rec.clear()
    assert len(rec) == 0


# -- cross-process propagation under chaos -----------------------------------

@pytest.mark.chaos
def test_one_trace_spans_client_retries_and_server(traced):
    """N injected faults -> exactly N error attempt spans, all parented
    on one rpc.call, the server handler span parented on the surviving
    attempt, every span sharing one trace_id."""
    tr, reg, flight = traced
    srv = EmbeddingServer()
    srv.create_table(0, dim=4, seed=0)
    srv.start()
    ch = ResilientChannel(srv.endpoint, **FAST)
    try:
        with chaos.drop_connections(point='send', times=2) as fault:
            out = ch.call({'op': 'pull', 'table': 0,
                           'ids': np.array([1, 2], np.int64)})
        assert fault.fired == 2
        assert np.asarray(out).shape == (2, 4)
        # the handler finishes its span after replying; give it a beat
        deadline = time.time() + 2.0
        while time.time() < deadline:
            if any(s['name'] == 'ps.server.pull'
                   for s in tr.recorder.spans()):
                break
            time.sleep(0.01)
    finally:
        ch.close()
        srv.stop()
    spans = tr.recorder.spans()
    calls = [s for s in spans if s['name'] == 'rpc.call']
    attempts = [s for s in spans if s['name'] == 'rpc.attempt']
    servers = [s for s in spans if s['name'] == 'ps.server.pull']
    assert len(calls) == 1 and len(attempts) == 3 and len(servers) == 1
    call = calls[0]
    assert call['tags']['endpoint'] == srv.endpoint
    # single trace across both processes' spans
    assert {s['trace_id'] for s in spans} == {call['trace_id']}
    assert all(a['parent_id'] == call['span_id'] for a in attempts)
    failed = [a for a in attempts if a['status'] == 'error']
    assert len(failed) == fault.fired == 2
    ok = [a for a in attempts if a['status'] == 'ok']
    assert len(ok) == 1
    assert servers[0]['parent_id'] == ok[0]['span_id']
    assert ok[0]['tags']['retries'] == 2
    # chaos annotated the in-flight call span, once per fault
    ev = [e for e in call['events'] if e['name'] == 'chaos.fault']
    assert len(ev) == 2
    assert all(e['args']['point'] == 'send' for e in ev)
    # backoff waits were recorded on the call span too
    assert sum(1 for e in call['events'] if e['name'] == 'backoff') == 2
    # and each fault offered the recorder a dump (one survives cooldown)
    assert len(glob.glob(str(flight / 'flight_chaos_fault_*.json'))) == 1


@pytest.mark.chaos
def test_circuit_open_dumps_exactly_once(traced):
    tr, reg, flight = traced
    ch = ResilientChannel('127.0.0.1:1',
                          retry_policy=RetryPolicy(max_attempts=6,
                                                   base_delay=0.001,
                                                   max_delay=0.002),
                          breaker=CircuitBreaker(failure_threshold=3,
                                                 reset_timeout=60.0))
    with pytest.raises(CircuitOpenError):
        ch.call({'op': 'stats'})
    dumps = glob.glob(str(flight / 'flight_circuit_open_*.json'))
    assert len(dumps) == 1
    payload = json.load(open(dumps[0]))
    assert payload['reason'] == 'circuit_open'
    # the failing attempt made it into the ring BEFORE the dump
    att = [s for s in payload['spans'] if s['name'] == 'rpc.attempt']
    assert att and all(s['status'] == 'error' for s in att)
    assert att[-1]['tags']['retries'] == 2
    # a second (fast-failed) call must not dump again
    with pytest.raises(CircuitOpenError):
        ch.call({'op': 'stats'})
    assert len(glob.glob(str(flight / 'flight_circuit_open_*.json'))) == 1
    # both call spans carry the fast-fail tag: the first trips the
    # breaker on attempt 3 and fast-fails attempt 4; the second never
    # gets an attempt at all
    fast = [s for s in tr.recorder.spans() if s['name'] == 'rpc.call'
            and s['tags'].get('circuit_open_fast_fail')]
    assert len(fast) == 2
    ch.close()


@pytest.mark.chaos
def test_deadline_expiry_dumps(traced):
    tr, reg, flight = traced
    ch = ResilientChannel('127.0.0.1:1', **FAST)
    with pytest.raises(DeadlineExceeded):
        ch.call({'op': 'stats'}, deadline=Deadline(0.0))
    dumps = glob.glob(str(flight / 'flight_deadline_expired_*.json'))
    assert len(dumps) == 1
    call = [s for s in tr.recorder.spans() if s['name'] == 'rpc.call'][-1]
    assert call['tags']['deadline_expired'] is True
    ch.close()


def test_disabled_tracing_keeps_call_payload_clean(traced):
    """Tracing off: no TRACE_KEY on the wire, no spans recorded."""
    tr, reg, flight = traced
    tr.disable()
    srv = EmbeddingServer()
    srv.create_table(0, dim=4, seed=0)
    srv.start()
    ch = ResilientChannel(srv.endpoint, **FAST)
    try:
        out = ch.call({'op': 'pull', 'table': 0,
                       'ids': np.array([3], np.int64)})
        assert np.asarray(out).shape == (1, 4)
    finally:
        ch.close()
        srv.stop()
    assert tr.recorder.spans() == []


# -- serving lifecycle --------------------------------------------------------

def test_serving_lifecycle_spans_and_exemplars(model, traced):
    tr, reg, flight = traced
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    prompts = [[int(t) for t in np.random.RandomState(5).randint(0, 211, n)]
               for n in (12, 3)]
    eng.generate(prompts, max_new_tokens=6)
    spans = tr.recorder.spans()
    reqs = [s for s in spans if s['name'] == 'serving.request']
    assert len(reqs) == 2
    for r in reqs:
        assert r['parent_id'] is None
        names = [e['name'] for e in r['events']]
        assert names[0] == 'queued'
        assert 'admitted' in names and names[-1] == 'retired'
        assert r['tags']['tokens'] == 6
        assert r['tags']['prompt_len'] in (12, 3)
    by_span = {r['span_id']: r['trace_id'] for r in reqs}
    prefills = [s for s in spans if s['name'] == 'serving.prefill']
    decodes = [s for s in spans if s['name'] == 'serving.decode']
    assert len(prefills) == 2 and len(decodes) == 2
    for ph in prefills + decodes:
        assert ph['parent_id'] in by_span
        assert ph['trace_id'] == by_span[ph['parent_id']]
    # the 12-token prompt prefilled in two chunks of <= 8
    chunks = max(len([e for e in p['events']
                      if e['name'] == 'prefill_chunk']) for p in prefills)
    assert chunks == 2
    bursts = [s for s in spans if s['name'] == 'serving.decode_burst']
    assert bursts and all(s['tags']['block'] == 4 for s in bursts)
    # TTFT observations carry trace_id exemplars linking back to requests
    snap = to_dict(reg, buckets=True)
    ttft = snap['serving_ttft_seconds']['samples'][0]
    exemplars = ttft.get('exemplars') or {}
    assert exemplars
    traces = {r['trace_id'] for r in reqs}
    assert {e['trace_id'] for e in exemplars.values()} <= traces
    gap = snap['serving_inter_token_seconds']['samples'][0]
    assert gap.get('exemplars')
    n_ex = snap['trace_exemplars_total']['samples'][0]['value']
    assert n_ex > 0


def test_paged_prefix_hit_and_spec_accept_events(model, traced):
    tr, reg, flight = traced
    rng = np.random.RandomState(11)
    system = [int(t) for t in rng.randint(0, 211, 16)]
    prompts = [system + [int(t) for t in rng.randint(0, 211, 3)]
               for _ in range(4)]
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4, spec_k=2)
    eng.generate(prompts, max_new_tokens=6)
    assert eng.metrics.report()['prefix_hits'] > 0
    reqs = [s for s in tr.recorder.spans()
            if s['name'] == 'serving.request']
    assert len(reqs) == 4
    events = [e for r in reqs for e in r['events']]
    hits = [e for e in events if e['name'] == 'prefix_cache_hit']
    assert hits and all(e['args']['tokens'] > 0 for e in hits)
    accepts = [e for e in events if e['name'] == 'spec_accept']
    assert accepts and all(e['args']['proposed'] == 2 for e in accepts)


# -- /debug/traces + export ---------------------------------------------------

def test_debug_traces_endpoint_and_head(traced):
    tr, reg, flight = traced
    with tr.start_span('unit.request', tags={'k': 'v'}):
        pass
    with MetricsServer(registry=reg, tracer=tr) as srv:
        body = json.loads(urllib.request.urlopen(
            srv.url + '/debug/traces', timeout=5).read().decode())
        assert body['enabled'] is True
        assert body['capacity'] == 256 and body['dropped'] == 0
        assert [s['name'] for s in body['spans']] == ['unit.request']
        chrome = json.loads(urllib.request.urlopen(
            srv.url + '/debug/traces?format=chrome',
            timeout=5).read().decode())
        names = [e['name'] for e in chrome['traceEvents']]
        assert 'process_name' in names and 'unit.request' in names
        # HEAD answers every route with real headers and an empty body
        for path in ('/healthz', '/metrics', '/debug/traces'):
            req = urllib.request.Request(srv.url + path, method='HEAD')
            resp = urllib.request.urlopen(req, timeout=5)
            assert resp.status == 200
            assert int(resp.headers['Content-Length']) > 0
            assert resp.read() == b''
        req = urllib.request.Request(srv.url + '/nope', method='HEAD')
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req, timeout=5)


def test_no_tracer_endpoint_404(traced):
    tr, reg, flight = traced
    srv = MetricsServer(registry=reg, tracer=tr)
    srv.tracer = None
    with srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + '/debug/traces', timeout=5)
        assert ei.value.code == 404


def test_chrome_export_merges_with_rank_traces(traced, tmp_path):
    """Acceptance: a host-span export dir + a per-rank device-trace dir
    merge into ONE valid Chrome-trace JSON with rank-grouped lanes."""
    tr, reg, flight = traced
    with tr.start_span('host.step', tags={'step': 1}) as sp:
        sp.add_event('mark', x=1)
    host_dir = tmp_path / 'host'
    tr.recorder.export_chrome(str(host_dir / 'host.trace.json'),
                              process_name='trainer host')
    rank_dir = tmp_path / 'rank1'
    os.makedirs(str(rank_dir))
    with open(str(rank_dir / 'device.trace.json'), 'w') as fh:
        json.dump({'traceEvents': [
            {'ph': 'M', 'name': 'process_name', 'pid': 7,
             'args': {'name': 'tpu worker'}},
            {'ph': 'X', 'name': 'xla_op', 'pid': 7, 'tid': 1,
             'ts': 10.0, 'dur': 5.0}]}, fh)
    out = str(tmp_path / 'merged.json')
    profiler.merge_traces([str(host_dir), str(rank_dir)], out)
    merged = json.load(open(out))
    assert merged['metadata']['merged_ranks'] == 2
    evs = merged['traceEvents']
    pnames = [e['args']['name'] for e in evs
              if e.get('ph') == 'M' and e.get('name') == 'process_name']
    assert any(n.startswith('rank 0:') for n in pnames)
    assert any(n == 'rank 1: tpu worker' for n in pnames)
    names = [e.get('name') for e in evs]
    assert 'host.step' in names and 'xla_op' in names and 'mark' in names
    # rank lanes are disjoint pid ranges
    host_pid = [e['pid'] for e in evs if e.get('name') == 'host.step'][0]
    dev_pid = [e['pid'] for e in evs if e.get('name') == 'xla_op'][0]
    assert host_pid < (1 << 20) <= dev_pid


def test_spans_to_chrome_shapes():
    tr = Tracer(registry=MetricRegistry(), clock=iter(
        [1.0, 1.25, 1.5]).__next__)
    with tr.start_span('a', tags={'q': 7}) as sp:
        sp.add_event('e')
    doc = spans_to_chrome(tr.recorder.spans(), pid=42)
    xs = [e for e in doc['traceEvents'] if e['ph'] == 'X']
    assert xs[0]['ts'] == 1.0e6 and xs[0]['dur'] == 0.5e6
    assert xs[0]['pid'] == 42 and xs[0]['args']['q'] == 7
    inst = [e for e in doc['traceEvents'] if e['ph'] == 'i']
    assert inst[0]['name'] == 'e' and inst[0]['ts'] == 1.25e6


# -- profiler fixes -----------------------------------------------------------

def test_profiler_stop_without_start_is_safe():
    p = profiler.Profiler(timer_only=False)
    p.stop()                                    # never started
    p.stop()                                    # and again
    profiler.stop_profiler()                    # module-level too
    profiler.stop_profiler()


def test_profiler_failed_start_leaves_no_stale_state(monkeypatch,
                                                     tmp_path):
    def boom(*a, **k):
        raise RuntimeError('trace backend unavailable')
    monkeypatch.setattr(profiler.jax.profiler, 'start_trace', boom)
    p = profiler.Profiler(log_dir=str(tmp_path))
    with pytest.raises(RuntimeError):
        p.start()
    p.stop()                                    # must not raise
    with pytest.raises(RuntimeError):
        profiler.start_profiler(log_dir=str(tmp_path))
    assert profiler._active_dir[0] is None      # no stale active dir
    profiler.stop_profiler()                    # paired stop is a no-op


def test_record_event_emits_host_span(traced):
    tr, reg, flight = traced
    with profiler.RecordEvent('fused_step'):
        pass
    ev = profiler.RecordEvent('begin_end')
    ev.begin()
    ev.end()
    names = [s['name'] for s in tr.recorder.spans()]
    assert names == ['fused_step', 'begin_end']


# -- overhead guards ----------------------------------------------------------

def test_disabled_tracing_adds_no_measurable_channel_overhead(traced):
    """Same shape as the registry's disabled-overhead guard: with the
    tracer off a loopback call does strictly less work, so its trimmed
    mean must not exceed the enabled mean + generous slack."""
    tr, reg, flight = traced
    srv = EmbeddingServer()
    srv.create_table(0, dim=4, seed=0)
    srv.start()
    ch = ResilientChannel(srv.endpoint)
    msg = {'op': 'dims', 'table_id': 0}

    def mean_call_s(n=60):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            ch.call(msg)
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return sum(ts[:n // 2]) / (n // 2)

    try:
        assert tr.enabled
        mean_call_s(10)                          # warm both paths
        enabled = mean_call_s()
        tr.disable()
        try:
            disabled = mean_call_s()
        finally:
            tr.enable()
    finally:
        ch.close()
        srv.stop()
    assert disabled <= enabled + 2e-3, (disabled, enabled)


def test_disabled_tracing_adds_no_measurable_decode_overhead(model,
                                                             traced):
    """Drive the same engine's decode hot loop with tracing on, then
    off: the disabled path must not be slower beyond scheduling noise
    (a decode step costs milliseconds; the guard is absolute)."""
    tr, reg, flight = traced
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    prompt = [1, 2, 3]

    def run_one():
        eng.add_request(prompt, max_new_tokens=16)
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0

    run_one()                                    # compile both programs
    enabled = min(run_one() for _ in range(3))
    tr.disable()
    try:
        disabled = min(run_one() for _ in range(3))
    finally:
        tr.enable()
    # generous absolute slack: CPU jit dispatch jitter dwarfs span cost
    assert disabled <= enabled * 1.5 + 0.05, (disabled, enabled)


# -- one clock, two sinks, and the step's spans --------------------------------

def test_every_span_carries_ordered_monotonic_stamps(model, traced):
    """Beside the epoch stamps (cross-process alignment) every span and
    event carries time.monotonic, the engine's and the timeline's
    clock; the request's own stamps ARE the events' stamps."""
    tr, reg, flight = traced
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    t_before = time.monotonic()
    reqs = [eng.add_request([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=5),
            eng.add_request([3, 4], max_new_tokens=5)]
    eng.run()
    t_after = time.monotonic()
    spans = tr.recorder.spans()
    assert {s['name'] for s in spans} >= {
        'serving.request', 'serving.prefill', 'serving.decode',
        'serving.step', 'serving.step.wait', 'serving.step.admit',
        'serving.step.prefill', 'serving.prefill_call',
        'serving.decode_burst'}
    for s in spans:
        assert t_before <= s['start_mono'] <= s['end_mono'] <= t_after
        assert s['start'] <= s['end'] and abs(s['start'] - time.time()) < 600
        monos = [e['mono'] for e in s['events']]
        assert monos == sorted(monos)
        assert all(s['start_mono'] <= m <= s['end_mono'] for m in monos)
    by_id = {s['tags']['request_id']: s for s in spans
             if s['name'] == 'serving.request'}
    for r in reqs:
        s = by_id[r.id]
        ev = {e['name']: e for e in s['events']}
        assert list(ev) == ['queued', 'admitted', 'first_token', 'retired']
        assert s['start_mono'] == ev['queued']['mono'] == r._arrival_t
        assert ev['admitted']['mono'] == r._admit_t
        assert ev['first_token']['mono'] == r._first_token_t
    # a burst outlives the step that dispatched it: it is a root, and
    # the step that fetches it has the wait as its child
    steps = {s['span_id'] for s in spans if s['name'] == 'serving.step'}
    assert all(s['parent_id'] is None for s in spans
               if s['name'] == 'serving.decode_burst')
    assert all(s['parent_id'] in steps for s in spans
               if s['name'] == 'serving.step.wait')


def test_annotated_span_is_the_one_dual_sink_path(traced, monkeypatch):
    """start_span(annotate=True) enters a TraceAnnotation of the span's
    name until finish(); RecordEvent is a thin caller of it; a disabled
    tracer enters nothing."""
    tr, reg, flight = traced
    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(('enter', self.name))

        def __exit__(self, *exc):
            log.append(('exit', self.name))
    monkeypatch.setattr(tracing, '_TraceAnnotation', Ann)
    with tr.start_span('outer', annotate=True):
        with profiler.RecordEvent('inner'):
            pass
        sp = tr.start_span('plain')
        sp.finish()
    assert log == [('enter', 'outer'), ('enter', 'inner'),
                   ('exit', 'inner'), ('exit', 'outer')]
    assert [s['name'] for s in tr.recorder.spans()] == ['inner', 'plain',
                                                        'outer']
    sp = tr.start_span('held', annotate=True)
    sp.finish(mono=sp.start_mono + 2.5)
    sp.finish()                                  # idempotent: one exit
    assert log[-2:] == [('enter', 'held'), ('exit', 'held')]
    assert tr.recorder.spans()[-1]['end_mono'] == sp.start_mono + 2.5
    tr.disable()
    try:
        del log[:]
        with tr.start_span('off', annotate=True) as off:
            with profiler.RecordEvent('off_too'):
                pass
        assert off is NULL_SPAN and log == []
    finally:
        tr.enable()


def test_disabled_tracing_opens_no_step_span(model, traced):
    tr, reg, flight = traced
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    tr.disable()
    try:
        out = eng.generate([[1, 2, 3]], max_new_tokens=6)
        # a flagged burst still counts, it just leaves no record
        assert eng.timeline.steps > 0
    finally:
        tr.enable()
    assert len(out[0]) == 6
    assert tr.recorder.spans() == []
    snap = to_dict(reg)
    assert snap['trace_spans_started_total']['samples'][0]['value'] == 0


def test_train_step_span(traced):
    from paddle_tpu.framework.functional import TrainStep
    tr, reg, flight = traced
    paddle.seed(3)
    net = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    y = paddle.to_tensor(np.zeros((3, 2), np.float32))
    for _ in range(3):
        step(x, y)
    got = [s for s in tr.recorder.spans() if s['name'] == 'train.step']
    assert [s['tags']['step'] for s in got] == [1, 2, 3]
    assert all(s['start_mono'] <= s['end_mono'] for s in got)
    # the optimizer's device ops are named after its rule
    assert 'optimizer.adamw' in step.compiled_hlo(x, y)[0]
