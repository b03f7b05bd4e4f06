"""Request-centric observability (paddle_tpu/monitor/events.py +
tracing.TraceRetention + per-tenant attribution through the serving
stack).

The load-bearing contracts:
  1. EXACTLY one canonical wide event per serving request — engine-
     direct or gateway-fronted, failed-over or not — carrying the full
     schema (REQUEST_EVENT_FIELDS);
  2. per-request kv_page_seconds, with no page shared, sum to the page
     allocator's pool-occupancy integral (same clock);
  3. chaos oracle: N failovers mean N wide events with failovers=N and
     N failover-retained span trees, each retrievable from tail
     retention by the wide event's trace_id;
  4. disabled paths cost one attribute load + branch;
  5. tenant label cardinality is bounded by construction;
  6. the gateway's _ttfts snapshot is safe under concurrent mutation
     (the slo_burn_rate deque race regression).
"""
import collections
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.monitor import MetricsServer
from paddle_tpu.monitor.events import (FIELD_NAMES, RequestLog,
                                       TenantLabeler, event_line,
                                       parse_event_lines,
                                       set_default_request_log)
from paddle_tpu.monitor.registry import MetricRegistry
from paddle_tpu.monitor.tracing import (TraceRetention, Tracer,
                                        set_default_tracer)
from paddle_tpu.serving import (PagedContinuousBatchingEngine,
                                ServingGateway)
from paddle_tpu.serving.gateway import slo_burn_rate
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

MNT = 8


@pytest.fixture(scope='module')
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope='module')
def prompts():
    rng = np.random.RandomState(3)
    return [[int(t) for t in rng.randint(0, 211, n)]
            for n in (3, 17, 7, 12, 5, 21)]


def _ev(**kw):
    """A schema-complete event dict with overridable defaults."""
    base = dict(request_id='r', tenant='t', trace_id='tr', arrival_t=0.0,
                admit_t=0.1, first_token_t=0.2, finish_t=0.5,
                queue_wait_s=0.1, prefill_chunks=1, prompt_tokens=4,
                output_tokens=8, prefix_hit_tokens=0, spec_proposed=0,
                spec_accepted=0, kv_page_seconds=0.4, failovers=0,
                replicas=[0], outcome='ok')
    base.update(kw)
    return base


# ---- RequestLog -------------------------------------------------------


def test_emit_validates_schema_and_orders_fields():
    log = RequestLog(capacity=8, registry=MetricRegistry())
    ev = log.emit(**_ev(request_id='a'))
    assert tuple(ev.keys()) == FIELD_NAMES
    # a partial emit records None for missing fields, never KeyErrors
    ev2 = log.emit(request_id='b', outcome='error')
    assert ev2['tenant'] is None and ev2['kv_page_seconds'] is None
    with pytest.raises(ValueError, match='tennant'):
        log.emit(tennant='acme')
    assert len(log) == 2


def test_ring_bound_and_drop_counter():
    reg = MetricRegistry()
    log = RequestLog(capacity=3, registry=reg)
    for i in range(5):
        log.emit(**_ev(request_id='r%d' % i))
    assert len(log) == 3
    assert [e['request_id'] for e in log.events()] == ['r2', 'r3', 'r4']
    assert log.dropped == 2
    assert reg.get('request_events_total').value() == 5.0
    assert reg.get('request_events_dropped_total').value() == 2.0
    log.clear()
    assert len(log) == 0


def test_sink_writes_jsonl_and_rotates(tmp_path):
    reg = MetricRegistry()
    sink = str(tmp_path / 'req.jsonl')
    # ~350 bytes/line: a 1300-byte cap forces exactly one rotation
    # across 6 writes, so current + backup together hold every event
    log = RequestLog(capacity=64, sink_path=sink, max_sink_bytes=1300,
                     sink_backups=2, registry=reg)
    for i in range(6):
        log.emit(**_ev(request_id='r%d' % i))
    lines = [json.loads(ln) for ln in open(sink) if ln.strip()]
    assert lines and all(tuple(sorted(e)) == tuple(sorted(FIELD_NAMES))
                         for e in lines)
    assert reg.get('request_sink_rotations_total').value() == 1.0
    rotated = tmp_path / 'req.jsonl.1'
    assert rotated.exists()
    old = [json.loads(ln) for ln in open(str(rotated)) if ln.strip()]
    # nothing lost across the rotation boundary
    assert len(old) + len(lines) == 6


def test_event_filters():
    log = RequestLog(capacity=16, registry=MetricRegistry())
    log.emit(**_ev(request_id='a', tenant='p', outcome='ok', failovers=0))
    log.emit(**_ev(request_id='b', tenant='p', outcome='error',
                   failovers=2))
    log.emit(**_ev(request_id='c', tenant='q', outcome='ok', failovers=1))
    assert [e['request_id'] for e in log.events(tenant='p')] == ['a', 'b']
    assert [e['request_id'] for e in log.events(outcome='error')] == ['b']
    assert [e['request_id'] for e in log.events(min_failovers=1)] \
        == ['b', 'c']
    assert [e['request_id'] for e in log.events(limit=1)] == ['c']
    assert [e['request_id']
            for e in log.events(tenant='p', min_failovers=1, limit=5)] \
        == ['b']


def test_event_time_range_filters_are_half_open():
    log = RequestLog(capacity=16, registry=MetricRegistry())
    for i, t in enumerate((10.0, 20.0, 30.0)):
        log.emit(**_ev(request_id='t%d' % i, arrival_t=t))
    log.emit(**_ev(request_id='noarr', arrival_t=None))
    assert [e['request_id'] for e in log.events(since_ts=20.0)] \
        == ['t1', 't2']
    # [since, until): the until bound is exclusive
    assert [e['request_id'] for e in log.events(until_ts=20.0)] == ['t0']
    assert [e['request_id']
            for e in log.events(since_ts=10.0, until_ts=30.0)] \
        == ['t0', 't1']
    # string values coerce (the HTTP route's path), garbage raises
    assert [e['request_id'] for e in log.events(since_ts='25')] == ['t2']
    with pytest.raises(ValueError):
        log.events(since_ts='zap')
    # events that never entered the system carry no arrival_t and never
    # match a time window
    assert all(e['request_id'] != 'noarr'
               for e in log.events(since_ts=0.0))
    # composes with the other filters
    assert [e['request_id']
            for e in log.events(since_ts=10.0, limit=1)] == ['t2']


def test_concurrent_emit_is_safe():
    reg = MetricRegistry()
    log = RequestLog(capacity=4096, registry=reg)

    def writer(base):
        for i in range(200):
            log.emit(**_ev(request_id='%d-%d' % (base, i)))

    ts = [threading.Thread(target=writer, args=(b,)) for b in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not any(t.is_alive() for t in ts)
    assert len(log) == 800
    assert reg.get('request_events_total').value() == 800.0
    assert log.dropped == 0


def test_disabled_emit_is_cheap_and_inert():
    reg = MetricRegistry()
    log = RequestLog(capacity=8, registry=reg)
    log.disable()
    t0 = time.monotonic()
    for _ in range(100_000):
        assert log.emit(request_id='x') is None
    elapsed = time.monotonic() - t0
    # one attribute load + branch; the bound is deliberately loose for
    # CI jitter — the real budget is ~100ns/call
    assert elapsed < 2.0, elapsed
    assert len(log) == 0
    assert reg.get('request_events_total').value() == 0.0
    log.enable()
    assert log.emit(**_ev()) is not None


def test_tenant_labeler_bounds_cardinality():
    lab = TenantLabeler(cap=4, buckets=2)
    assert lab.label(None) == 'default'
    first = [lab.label('t%d' % i) for i in range(4)]
    assert first == ['t0', 't1', 't2', 't3']      # interned verbatim
    overflow = {lab.label('x%d' % i) for i in range(50)}
    assert overflow <= {'overflow_0', 'overflow_1'}
    # interned tenants keep their identity after overflow starts
    assert lab.label('t2') == 't2'
    # hashed bucket is stable per tenant
    assert lab.label('x7') == lab.label('x7')
    all_labels = set(first) | overflow | {'default'}
    assert len(all_labels) <= 4 + 2 + 1


def test_event_line_roundtrip():
    ev = _ev(request_id='rr', tenant='acme')
    line = event_line(ev, 4, '[cfg]')
    assert line.startswith('request_event(4)[cfg]: {')
    parsed = parse_event_lines('noise\n%s\nmore noise\n' % line)
    assert len(parsed) == 1
    tag, got = parsed[0]
    assert tag == 'cfg' and got == ev
    assert parse_event_lines('request_event(1)[x]: not json') == []


def test_default_log_swap_returns_previous():
    mine = RequestLog(capacity=4, registry=MetricRegistry())
    prev = set_default_request_log(mine)
    try:
        from paddle_tpu.monitor.events import default_request_log
        assert default_request_log() is mine
    finally:
        assert set_default_request_log(prev) is mine


# ---- TraceRetention ---------------------------------------------------


def _span(tid, name='root', parent=None, start=0.0, end=1.0,
          status='ok'):
    return {'trace_id': tid, 'span_id': name, 'parent_id': parent,
            'name': name, 'start': start, 'end': end, 'status': status}


def test_retention_keeps_slow_error_forced_and_samples():
    reg = MetricRegistry()
    ret = TraceRetention(capacity=16, slow_threshold_s=0.5,
                         keep_probability=0.0, registry=reg)
    # healthy + fast -> discarded
    ret.offer(_span('fast', end=0.1))
    assert ret.get('fast') is None
    assert reg.get('trace_retention_discarded_total').value() == 1.0
    # slow root -> kept with reason 'slow'
    ret.offer(_span('slow', end=2.0))
    assert [t['reasons'] for t in ret.traces(reason='slow')] == [['slow']]
    # an errored child keeps the whole tree
    ret.offer(_span('err', name='child', parent='root-id', status='error',
                    end=0.1))
    ret.offer(_span('err', end=0.1))
    tree = ret.get('err')
    assert tree is not None and len(tree) == 2
    # forced mark lands when the tree completes
    ret.mark('forced-tid', 'failover')
    ret.offer(_span('forced-tid', end=0.1))
    assert ret.traces(reason='failover')[0]['trace_id'] == 'forced-tid'
    assert reg.get('trace_retained_total').labels('failover').value() \
        == 1.0
    # probabilistic baseline keep with a deterministic rng
    ret2 = TraceRetention(capacity=4, keep_probability=0.5,
                          registry=MetricRegistry(), rng=lambda: 0.1)
    ret2.offer(_span('lucky', end=0.1))
    assert ret2.traces()[0]['reasons'] == ['sampled']


def test_retention_bounds_and_stragglers():
    reg = MetricRegistry()
    ret = TraceRetention(capacity=2, slow_threshold_s=0.0,
                         pending_capacity=2, registry=reg)
    for i in range(3):                       # every root is 'slow'
        ret.offer(_span('t%d' % i, end=1.0))
    assert len(ret) == 2                     # FIFO eviction at capacity
    assert ret.get('t0') is None and ret.get('t2') is not None
    assert reg.get('trace_retention_evicted_total').value() >= 1.0
    # pending (incomplete) trees are bounded too
    for i in range(4):
        ret.offer(_span('p%d' % i, name='c', parent='x', end=1.0))
    assert len(ret._pending) <= 2
    # straggler span of an already-kept tree is appended, not re-decided
    ret.offer(_span('t2', name='late-child', parent='root', end=1.5))
    names = [s['name'] for s in ret.get('t2')]
    assert 'late-child' in names
    ret.clear()
    assert len(ret) == 0


# ---- slo_burn_rate deque race (regression) ----------------------------


def test_slo_burn_rate_safe_under_concurrent_mutation():
    """Regression: slo_burn_rate used to iterate the gateway's _ttfts
    deque directly; a driver thread appending (and the maxlen evicting)
    mid-iteration raised ``RuntimeError: deque mutated during
    iteration``. The snapshot fix must survive a hostile writer."""
    samples = collections.deque(maxlen=512)
    stop = threading.Event()
    errors = []

    def writer():
        t = 0.0
        while not stop.is_set():
            t += 0.001
            samples.append((t, 0.9))

    th = threading.Thread(target=writer)
    th.start()
    try:
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            try:
                rate = slo_burn_rate(samples, time.monotonic(), 0.5, 30.0)
            except RuntimeError as e:          # pragma: no cover
                errors.append(e)
                break
            assert 0.0 <= rate <= 1.0
    finally:
        stop.set()
        th.join(10)
    assert not errors, errors


# ---- engine-level: one event per request + exact KV attribution -------


def test_engine_one_event_per_request_kv_attribution(model, prompts):
    log = RequestLog(capacity=64, registry=MetricRegistry())
    prev = set_default_request_log(log)
    try:
        eng = PagedContinuousBatchingEngine(
            model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
            decode_block=2, prefix_cache=False)
        # ServingMetrics rides the process default registry: assert
        # per-tenant deltas, not absolutes
        treg = eng.metrics.registry
        base_req = treg.get('tenant_requests_total') \
            .labels('premium').value()
        base_tok = treg.get('tenant_tokens_total').labels('batch').value()
        reqs = [eng.add_request(p, max_new_tokens=MNT,
                                tenant='premium' if i % 2 == 0 else
                                'batch')
                for i, p in enumerate(prompts)]
        eng.run()
    finally:
        set_default_request_log(prev)
    events = log.events()
    assert len(events) == len(prompts)              # exactly one each
    assert len({e['request_id'] for e in events}) == len(prompts)
    by_tenant = {}
    for e in events:
        by_tenant.setdefault(e['tenant'], []).append(e)
    assert sorted(by_tenant) == ['batch', 'premium']
    for e in events:
        assert e['outcome'] == 'ok' and e['failovers'] == 0
        assert e['output_tokens'] == MNT
        assert e['prompt_tokens'] in {len(p) for p in prompts}
        assert e['admit_t'] >= e['arrival_t']
        assert e['finish_t'] >= e['first_token_t'] >= e['admit_t']
        assert e['queue_wait_s'] == pytest.approx(
            e['admit_t'] - e['arrival_t'])
        assert e['kv_page_seconds'] > 0.0
    # THE attribution invariant: with no page shared, per-request
    # page·seconds sum to the allocator's pool-occupancy integral (a
    # request's window opens once ALL its pages are reserved, so the
    # pool's integral leads by the reservation's own microseconds)
    total = sum(e['kv_page_seconds'] for e in events)
    assert total == pytest.approx(eng.pages.page_seconds(), rel=1e-2)
    assert total <= eng.pages.page_seconds()
    assert sum(r.kv_page_seconds for r in reqs) == total
    # per-tenant families materialized with bounded labels
    assert treg.get('tenant_requests_total').labels('premium').value() \
        - base_req == 3.0
    assert treg.get('tenant_tokens_total').labels('batch').value() \
        - base_tok == 3.0 * MNT


def test_paged_engine_emits_spec_counts(model, prompts):
    log = RequestLog(capacity=64, registry=MetricRegistry())
    prev = set_default_request_log(log)
    try:
        eng = PagedContinuousBatchingEngine(
            model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
            decode_block=2, spec_k=2)
        eng.generate(prompts[:3], max_new_tokens=MNT, tenant='spec')
    finally:
        set_default_request_log(prev)
    events = log.events(tenant='spec')
    assert len(events) == 3
    assert all(e['kv_page_seconds'] > 0.0 for e in events)
    # the n-gram proposer drafted every decode step after the first
    assert sum(e['spec_proposed'] for e in events) > 0
    assert all(0 <= e['spec_accepted'] <= e['spec_proposed']
               for e in events)


def test_emit_event_false_suppresses_engine_event(model, prompts):
    """The gateway's replica path: the engine-level event is suppressed
    so the gateway emits the single canonical one."""
    log = RequestLog(capacity=16, registry=MetricRegistry())
    prev = set_default_request_log(log)
    try:
        eng = PagedContinuousBatchingEngine(
            model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
            decode_block=2)
        eng.add_request(prompts[0], max_new_tokens=MNT, emit_event=False)
        eng.run()
    finally:
        set_default_request_log(prev)
    assert len(log) == 0


# ---- gateway chaos oracle ---------------------------------------------


@pytest.mark.chaos
def test_gateway_failover_chaos_oracle(model, prompts):
    """N failovers => exactly one wide event per submitted request, the
    victims carrying failovers=1 and both replicas in placement order,
    and exactly N failover-retained span trees retrievable by the wide
    events' trace_ids."""
    reg = MetricRegistry()
    log = RequestLog(capacity=64, registry=reg)
    ret = TraceRetention(capacity=64, registry=reg)
    tracer = Tracer(enabled=True, registry=reg, retention=ret)
    prev_log = set_default_request_log(log)
    prev_tr = set_default_tracer(tracer)
    try:
        gw = ServingGateway(
            lambda: PagedContinuousBatchingEngine(
                model, num_seqs=2, max_len=32, page_size=8,
                prefill_chunk=8, decode_block=2),
            replicas=2, registry=reg)
        reqs = [gw.submit(p, max_new_tokens=MNT,
                          tenant='premium' if i % 2 == 0 else 'batch')
                for i, p in enumerate(prompts)]
        gw.step()
        gw.step()
        # the oracle: replica 0's in-flight non-finished requests at the
        # moment of loss — each fails over exactly once
        victims = [g for g in gw.pool[0].assigned if len(g.tokens) < MNT]
        expected = len(victims)
        assert expected > 0
        gw.kill_replica(0)
        gw.run()
    finally:
        set_default_request_log(prev_log)
        set_default_tracer(prev_tr)

    assert all(r.done for r in reqs)
    events = log.events()
    assert len(events) == len(prompts)              # EXACTLY one each
    assert len({e['request_id'] for e in events}) == len(prompts)
    failed_over = [e for e in events if e['failovers']]
    assert len(failed_over) == expected
    assert all(e['failovers'] == 1 for e in failed_over)
    assert all(e['replicas'] == [0, 1] for e in failed_over)
    assert reg.get('gateway_failover_total').value() == expected
    # tail retention kept EXACTLY the failed-over trees...
    kept = ret.traces(reason='failover')
    assert len(kept) == expected
    assert {t['trace_id'] for t in kept} \
        == {e['trace_id'] for e in failed_over}
    # ...and each wide event's trace_id joins to a full span tree
    for e in failed_over:
        tree = ret.get(e['trace_id'])
        assert tree is not None
        assert 'serving.request' in {s['name'] for s in tree}
    # untouched requests were not retained (no slow/sample reasons set)
    for e in events:
        if not e['failovers']:
            assert ret.get(e['trace_id']) is None
    # per-tenant counters on the gateway registry
    got = sum(reg.get('tenant_requests_total').labels(t).value()
              for t in ('premium', 'batch'))
    assert got == len(prompts)


# ---- /requests route --------------------------------------------------


def test_requests_route_serves_and_filters():
    log = RequestLog(capacity=16, registry=MetricRegistry())
    log.emit(**_ev(request_id='a', tenant='p', failovers=0))
    log.emit(**_ev(request_id='b', tenant='p', failovers=2,
                   outcome='error'))
    log.emit(**_ev(request_id='c', tenant='q', failovers=1))
    with MetricsServer(registry=MetricRegistry(), events=log) as srv:
        def get(qs=''):
            body = urllib.request.urlopen(
                srv.url + '/requests' + qs, timeout=5).read().decode()
            return json.loads(body)
        all_ev = get()
        assert all_ev['count'] == 3 and all_ev['dropped'] == 0
        assert [e['request_id'] for e in all_ev['events']] \
            == ['a', 'b', 'c']
        assert get('?tenant=p')['count'] == 2
        assert get('?outcome=error&tenant=p')['count'] == 1
        got = get('?min_failovers=1&limit=1')
        assert [e['request_id'] for e in got['events']] == ['c']
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + '/requests?limit=zap',
                                   timeout=5)
        assert ei.value.code == 400
    # a server with no log attached answers 404, like other optional
    # routes
    with MetricsServer(registry=MetricRegistry()) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + '/requests', timeout=5)
        assert ei.value.code == 404


def test_requests_route_time_range_filters():
    log = RequestLog(capacity=16, registry=MetricRegistry())
    for i, t in enumerate((10.0, 20.0, 30.0)):
        log.emit(**_ev(request_id='t%d' % i, arrival_t=t))
    with MetricsServer(registry=MetricRegistry(), events=log) as srv:
        def get(qs=''):
            body = urllib.request.urlopen(
                srv.url + '/requests' + qs, timeout=5).read().decode()
            return json.loads(body)
        assert [e['request_id'] for e in get('?since_ts=20')['events']] \
            == ['t1', 't2']
        assert [e['request_id'] for e in get('?until_ts=20')['events']] \
            == ['t0']
        got = get('?since_ts=10&until_ts=30')
        assert [e['request_id'] for e in got['events']] == ['t0', 't1']
        assert get('?since_ts=20.5&tenant=t')['count'] == 1
        for bad in ('?since_ts=zap', '?until_ts=1e'):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + '/requests' + bad,
                                       timeout=5)
            assert ei.value.code == 400
