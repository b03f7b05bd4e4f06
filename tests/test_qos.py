"""Overload-robust multi-tenant QoS (ISSUE 17): admission control at
the gateway front door, priority preemption with exact-parity resume in
the paged engine, and the simulator's million-request policy sweeps.

The load-bearing contracts:

  1. admission is pure policy over an injected clock — token buckets
     and quotas are exact functions of (now, tenant), rejection never
     consumes credit, and tests never sleep;
  2. a shed request is DATA, not an exception: an already-finished
     handle with `error` set and exactly ONE wide event
     (outcome='rejected'), and it never touches an engine;
  3. preempt-and-resume never buys QoS with output drift: a victim's
     delivered stream is token-for-token IDENTICAL to an unpreempted
     run (determinism + the Request._replay swallow), and zero-retrace
     still holds;
  4. the simulator's QoS path makes the same admission decisions in
     virtual time, deterministically.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.capacity import workload
from paddle_tpu.capacity.qos import (REJECT_REASONS, QosPolicy,
                                     TenantClass, TokenBucket)
from paddle_tpu.capacity.simulator import ServiceModel, simulate, sweep_qos
from paddle_tpu.monitor import events as _events
from paddle_tpu.monitor.registry import MetricRegistry
from paddle_tpu.serving import (PagedContinuousBatchingEngine,
                                ServingGateway)
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
            for n in (5, 9, 7, 12, 4, 11, 6, 8)]


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


def _capture_log():
    """Fresh RequestLog installed as default; caller must restore."""
    log = _events.RequestLog(capacity=256)
    prev = _events.set_default_request_log(log)
    return log, prev


def _events_for(log, req_id):
    return [e for e in log.events() if e['request_id'] == req_id]


# ---- pure policy units (fake clock, no jax) ---------------------------


def test_token_bucket_fake_clock():
    b = TokenBucket(rate=2.0, burst=4.0)
    assert all(b.take(0.0) for _ in range(4))
    assert not b.take(0.0)             # empty; reject leaves level alone
    assert b.level(0.0) == pytest.approx(0.0)
    assert b.take(0.5)                 # 0.5s * 2/s == 1 token refilled
    assert not b.take(0.5)
    assert b.level(10.0) == pytest.approx(4.0)   # capped at burst


def test_policy_quota_checked_before_rate():
    pol = QosPolicy(classes=[
        TenantClass('bg', rate=100.0, burst=1.0, max_concurrent=1)])
    ok, reason = pol.admit(0.0, 'bg')
    assert ok and reason is None
    # in-flight cap hit: quota rejection must NOT spend a bucket token
    lvl = pol.bucket_level('bg', 0.0)
    ok, reason = pol.admit(0.0, 'bg')
    assert (ok, reason) == (False, 'quota')
    assert pol.bucket_level('bg', 0.0) == pytest.approx(lvl)
    pol.finish('bg')
    assert pol.inflight('bg') == 0
    ok, _ = pol.admit(0.0, 'bg')       # slot free again, bucket empty
    assert (ok, _) == (False, 'rate')
    assert reason in REJECT_REASONS


def test_policy_roundtrip_and_priorities():
    pol = QosPolicy(
        classes=[TenantClass('premium', priority=2),
                 TenantClass('bg', rate=5.0, burst=8.0,
                             max_concurrent=3)],
        max_pending=16, max_queue_wait_s=1.5)
    clone = QosPolicy.from_dict(pol.to_dict())
    assert clone.to_dict() == pol.to_dict()
    assert clone.priority_of('premium') == 2
    assert clone.priority_of('bg') == 0
    assert clone.priority_of('unknown') == 0      # default class
    assert clone.max_pending == 16
    assert clone.max_queue_wait_s == pytest.approx(1.5)
    # fresh state: the clone starts with a full bucket
    assert clone.bucket_level('bg', 0.0) == pytest.approx(8.0)


# ---- gateway admission ------------------------------------------------


def _factory(model):
    return lambda: PagedContinuousBatchingEngine(
        model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
        decode_block=2)


def test_gateway_rate_and_quota_rejections(model, prompts):
    log, prev = _capture_log()
    try:
        clock = FakeClock()
        gw = ServingGateway(
            _factory(model), replicas=1, clock=clock,
            registry=MetricRegistry(),
            admission=QosPolicy(classes=[
                TenantClass('premium', priority=1),
                TenantClass('bg', rate=1.0, burst=1.0),
                TenantClass('q', max_concurrent=1)]))
        ok_h = gw.submit(prompts[0], max_new_tokens=MNT, tenant='bg')
        shed = gw.submit(prompts[1], max_new_tokens=MNT, tenant='bg')
        q1 = gw.submit(prompts[2], max_new_tokens=MNT, tenant='q')
        q2 = gw.submit(prompts[3], max_new_tokens=MNT, tenant='q')
        prem = gw.submit(prompts[4], max_new_tokens=MNT, tenant='premium')

        # bucket empty at the same instant: shed, instantly final
        assert shed.done and 'rate' in str(shed.error)
        assert not shed.tokens
        # concurrency quota: q2 shed while q1 is in flight
        assert q2.done and 'quota' in str(q2.error)
        assert not ok_h.done and not q1.done and not prem.done

        gw.run()
        assert ok_h.done and ok_h.error is None and len(ok_h.tokens) == MNT
        assert q1.error is None and prem.error is None

        rep = gw.report()
        assert rep['rejected'] == 2
        # shed requests never became engine traffic
        assert rep['requests'] == 3 and rep['completed'] == 3
        reg = gw.registry
        assert reg.get('qos_rejected_total').labels('rate', 'bg') \
                  .value() == 1
        assert reg.get('qos_rejected_total').labels('quota', 'q') \
                  .value() == 1
        assert reg.get('qos_admitted_total').labels('premium').value() == 1

        # exactly one wide event per request, correct outcome + priority
        for h, outcome in ((ok_h, 'ok'), (shed, 'rejected'),
                           (q1, 'ok'), (q2, 'rejected'), (prem, 'ok')):
            evs = _events_for(log, h.id)
            assert len(evs) == 1, (h.id, evs)
            assert evs[0]['outcome'] == outcome
        assert _events_for(log, prem.id)[0]['priority'] == 1
        assert _events_for(log, shed.id)[0]['first_token_t'] is None
        # admission slots all released: the policy holds no in-flight
        for t in ('bg', 'q', 'premium'):
            assert gw.admission.inflight(t) == 0
    finally:
        _events.set_default_request_log(prev)


def test_gateway_bounded_queue_and_deadline_shed(model, prompts):
    log, prev = _capture_log()
    try:
        clock = FakeClock()
        gw = ServingGateway(
            _factory(model), replicas=1, clock=clock,
            registry=MetricRegistry(),
            admission=QosPolicy(
                classes=[TenantClass('hi', priority=1),
                         TenantClass('lo', priority=0)],
                max_pending=1, max_queue_wait_s=0.5))
        gw.kill_replica(0)       # nothing routable: everything parks
        lo1 = gw.submit(prompts[0], max_new_tokens=MNT, tenant='lo')
        assert not lo1.done      # parked
        # same class at capacity: the NEWCOMER sheds (queue_full)
        lo2 = gw.submit(prompts[1], max_new_tokens=MNT, tenant='lo')
        assert lo2.done and 'queue_full' in str(lo2.error)
        # higher class at capacity: the parked low request is the victim
        hi = gw.submit(prompts[2], max_new_tokens=MNT, tenant='hi')
        assert lo1.done and 'queue_full' in str(lo1.error)
        assert not hi.done
        # parked past the deadline: shed on the next drain
        clock.t = 1.0
        assert gw.step() == 0
        assert hi.done and 'deadline' in str(hi.error)
        for h in (lo1, lo2, hi):
            evs = _events_for(log, h.id)
            assert len(evs) == 1 and evs[0]['outcome'] == 'rejected'
        assert gw.report()['rejected'] == 3
    finally:
        _events.set_default_request_log(prev)


def test_gateway_fifo_within_priority_class(model, prompts):
    """Parked work drains best-class-first, FIFO inside a class."""
    gw = ServingGateway(
        _factory(model), replicas=1, registry=MetricRegistry(),
        admission=QosPolicy(classes=[TenantClass('hi', priority=1),
                                     TenantClass('lo', priority=0)]))
    gw.kill_replica(0)
    order = []
    handles = [gw.submit(prompts[i], max_new_tokens=MNT, tenant=t)
               for i, t in enumerate(('lo', 'lo', 'hi', 'lo', 'hi'))]
    with gw._lock:
        gw._add_replica_locked()     # capacity returns; next step drains
    while gw.step():
        pass
    for h in handles:
        assert h.error is None and len(h.tokens) == MNT
    # admission order onto the replica == drain order
    order = sorted(range(5), key=lambda i: handles[i]._eng_req._admit_t)
    assert order == [2, 4, 0, 1, 3]


# ---- paged-engine preemption: evict, resume, exact parity -------------


@pytest.fixture(scope='module')
def paged_preempt(model):
    """One preempt-enabled paged engine (and its wide-event log,
    installed BEFORE construction) shared by the preemption tests —
    each compile of the three jitted programs is seconds of suite
    budget. Tests mutate scheduler.max_preempts and must set it."""
    log = _events.RequestLog(capacity=256)
    prev = _events.set_default_request_log(log)
    eng = PagedContinuousBatchingEngine(
        model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
        decode_block=2, preempt=True)
    yield eng, log
    _events.set_default_request_log(prev)


def test_preempt_resume_exact_token_parity(paged_preempt, prompts):
    eng, log = paged_preempt
    eng.scheduler.max_preempts = None
    # uniform priorities never preempt, so the shared engine doubles as
    # its own unpreempted oracle (greedy + seeded == deterministic)
    ref = eng.generate(prompts[:3], max_new_tokens=MNT)

    reg = eng.metrics.registry
    pre0 = reg.get('qos_preempted_total').labels('lo').value()
    res0 = reg.get('qos_resumed_total').labels('lo').value()
    base = eng.scheduler.preempted
    r0 = eng.add_request(prompts[0], max_new_tokens=MNT, tenant='lo',
                         priority=0)
    r1 = eng.add_request(prompts[1], max_new_tokens=MNT, tenant='lo',
                         priority=0)
    while min(len(r0.tokens), len(r1.tokens)) < 2:
        eng.step()       # both residents mid-decode
    r2 = eng.add_request(prompts[2], max_new_tokens=MNT, tenant='hi',
                         priority=1)
    while eng.scheduler.pending:
        eng.step()

    # the high-priority arrival evicted exactly one resident, which
    # then resumed and finished
    assert eng.scheduler.preempted == base + 1
    victim = r1 if r1._preempts else r0
    assert victim._preempts == 1 and victim.outcome == 'ok'
    assert reg.get('qos_preempted_total').labels('lo').value() == pre0 + 1
    assert reg.get('qos_resumed_total').labels('lo').value() == res0 + 1
    # THE invariant: caller-visible streams identical to an
    # unpreempted run — no duplicate, no gap, no drift
    assert [r0.tokens, r1.tokens, r2.tokens] == ref
    # eviction + resume compiled nothing new
    assert set(eng.trace_counts.values()) <= {0, 1}
    # exactly one wide event each; the victim's says ok (it finished)
    for r in (r0, r1, r2):
        evs = _events_for(log, r.id)
        assert len(evs) == 1 and evs[0]['outcome'] == 'ok'
    assert _events_for(log, r2.id)[0]['priority'] == 1


def test_preempt_budget_exhausted_is_terminal(paged_preempt, prompts):
    eng, log = paged_preempt
    eng.scheduler.max_preempts = 0
    base = eng.scheduler.preempted
    r0 = eng.add_request(prompts[0], max_new_tokens=MNT, priority=0)
    r1 = eng.add_request(prompts[1], max_new_tokens=MNT, priority=0)
    while min(len(r0.tokens), len(r1.tokens)) < 2:
        eng.step()
    r2 = eng.add_request(prompts[2], max_new_tokens=MNT, priority=1)
    while eng.scheduler.pending:
        eng.step()
    eng.scheduler.max_preempts = None
    assert eng.scheduler.preempted == base + 1
    victim = r1 if r1._preempts else r0
    survivor = r0 if victim is r1 else r1
    assert victim.done and victim.outcome == 'preempted'
    assert survivor.outcome == 'ok' and r2.outcome == 'ok'
    evs = _events_for(log, victim.id)
    assert len(evs) == 1 and evs[0]['outcome'] == 'preempted'
    # its pages really came back: no resident holds a mapping (what
    # remains ref'd belongs to the prefix cache, not to requests)
    assert not eng.scheduler.resident and not eng.scheduler._nblocks


@pytest.mark.slow
def test_engine_priority_admission_fifo_within_class(model, prompts):
    eng = PagedContinuousBatchingEngine(model, num_seqs=1, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2)
    reqs = [eng.add_request(prompts[i], max_new_tokens=4, priority=p)
            for i, p in enumerate((0, 0, 1, 0))]
    while eng.scheduler.pending:
        eng.step()
    order = sorted(range(4), key=lambda i: reqs[i]._admit_t)
    assert order == [2, 0, 1, 3]


# ---- chaos: failover + shedding compose -------------------------------


@pytest.mark.slow
def test_kill_replica_mid_burst_with_active_shedding(model, prompts):
    """A replica dies while the admission layer is actively shedding:
    failover victims are re-placed and complete (outcome 'ok', counted
    once), shed requests stay shed (outcome 'rejected', counted once) —
    the two outcomes never double-count a request."""
    log, prev = _capture_log()
    try:
        gw = ServingGateway(
            _factory(model), replicas=2, registry=MetricRegistry(),
            admission=QosPolicy(classes=[
                TenantClass('premium', priority=1),
                TenantClass('bg', rate=1.0, burst=2.0)]))
        handles = []
        for i, p in enumerate(prompts):
            handles.append(gw.submit(
                p, max_new_tokens=MNT,
                tenant='premium' if i % 2 == 0 else 'bg'))
        gw.step()
        gw.kill_replica(0)
        while gw.step():
            pass
        shed = [h for h in handles if h.error is not None]
        done_ok = [h for h in handles if h.error is None]
        assert len(shed) == 2      # bg burst 2.0 admits 2 of 4
        assert all('rejected: rate' in str(h.error) for h in shed)
        assert all(h.failovers == 0 for h in shed)
        assert all(len(h.tokens) == MNT for h in done_ok)
        assert any(h.failovers for h in done_ok)   # the kill was real
        rep = gw.report()
        assert rep['rejected'] == len(shed)
        assert rep['completed'] == len(done_ok)
        # one event per request; outcomes partition the burst exactly
        outcomes = {}
        for h in handles:
            evs = _events_for(log, h.id)
            assert len(evs) == 1
            outcomes[h.id] = evs[0]['outcome']
        assert sum(1 for o in outcomes.values() if o == 'rejected') \
            == len(shed)
        assert sum(1 for o in outcomes.values() if o == 'ok') \
            == len(done_ok)
    finally:
        _events.set_default_request_log(prev)


# ---- simulator QoS ----------------------------------------------------

SIM_MODEL = ServiceModel(prefill_chunk_s=0.002, decode_burst_s=0.004)


def _mixed_spec(n=800, mean_gap=0.0005, seed=2):
    return workload.WorkloadSpec(
        requests=n, seed=seed, vocab_size=512,
        arrival={'process': 'poisson', 'mean_gap_s': mean_gap},
        lengths={'dist': 'ladder', 'lens': [8, 16, 24, 32]},
        output={'dist': 'fixed', 'len': 16},
        tenants={'mode': 'round_robin',
                 'tenants': [{'name': 'premium'}, {'name': 'bg'}]})


def _throttle():
    return QosPolicy(classes=[TenantClass('premium', priority=1),
                              TenantClass('bg', rate=120.0, burst=8.0)])


def test_sim_qos_sheds_and_protects_premium():
    tr = workload.generate(_mixed_spec())
    open_res = simulate(tr, SIM_MODEL, replicas=1)
    qos_res = simulate(tr, SIM_MODEL, replicas=1, qos=_throttle())

    summ = qos_res.summary()
    assert summ['rejected'] > 0
    assert 0.0 < summ['shed_rate'] < 1.0
    # premium never sheds (no rate class) and its tail collapses vs the
    # open door: that IS graceful degradation
    prem = np.asarray(tr.tenant_id) == tr.tenant_names.index('premium')
    open_p99 = float(np.percentile(open_res.ttft()[prem], 99))
    by_prio = qos_res.ttft_percentiles_by_priority([99])
    assert by_prio[1][99] < open_p99 * 0.75
    ok = qos_res.ok_mask()
    assert ok[prem].all()

    # shed rows join the wide schema with nothing fabricated
    evs = qos_res.to_events()
    shed_evs = [e for e in evs if e['outcome'] == 'rejected']
    assert len(shed_evs) == summ['rejected']
    assert all(e['first_token_t'] is None and e['output_tokens'] == 0
               for e in shed_evs)
    assert {e['priority'] for e in evs} == {0, 1}


def test_sim_qos_is_deterministic():
    tr = workload.generate(_mixed_spec(n=400))
    pol = _throttle()
    a = simulate(tr, SIM_MODEL, replicas=1,
                 qos=QosPolicy.from_dict(pol.to_dict()))
    b = simulate(tr, SIM_MODEL, replicas=1,
                 qos=QosPolicy.from_dict(pol.to_dict()))
    assert np.array_equal(a.outcome, b.outcome)
    assert np.array_equal(a.first, b.first)
    assert np.array_equal(a.finish, b.finish)


def test_sweep_qos_slo_verdicts():
    tr = workload.generate(_mixed_spec())
    sweep = sweep_qos(tr, SIM_MODEL,
                      [('open', {}), ('throttled', _throttle())],
                      replicas=1, slo_ttft_s=1.0)
    points = {p['policy']: p for p in sweep['points']}
    assert points['open']['shed_rate'] == 0.0
    assert points['throttled']['rejected'] > 0
    assert not points['open']['meets_slo']
    assert points['throttled']['meets_slo']


# ---- the offline gate CLI ---------------------------------------------


def test_capacity_report_qos_policy_protocol(tmp_path):
    spec = {'requests': 300, 'seed': 2, 'vocab_size': 512,
            'arrival': {'process': 'poisson', 'mean_gap_s': 0.0005},
            'lengths': {'dist': 'ladder', 'lens': [8, 16, 24, 32]},
            'output': {'dist': 'fixed', 'len': 16},
            'tenants': {'mode': 'round_robin',
                        'tenants': [{'name': 'premium'},
                                    {'name': 'bg'}]}}
    pol = dict(_throttle().to_dict(), name='throttled')

    def run(*args):
        return subprocess.run(
            [sys.executable, 'tools/capacity_report.py'] + list(args),
            capture_output=True, text=True)

    ok = run('--spec-inline', json.dumps(spec),
             '--qos-policy', json.dumps(pol),
             '--qos-policy', '{"name": "open", "classes": []}',
             '--replicas', '1', '--slo-ms', '1000')
    assert ok.returncode == 0, ok.stdout + ok.stderr
    out = json.loads(ok.stdout.splitlines()[-1])
    points = {p['policy']: p for p in out['qos_sweep']['points']}
    assert points['throttled']['rejected'] > 0
    assert points['open']['shed_rate'] == 0.0
    assert 'by_priority' in points['throttled']

    nothing = run('--qos-policy', json.dumps(pol))
    assert nothing.returncode == 2    # no trace/spec to sweep over
