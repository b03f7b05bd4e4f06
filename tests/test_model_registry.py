"""Multi-model serving tests (paddle_tpu/serving/registry/).

The load-bearing assertions of the registry subsystem's contract:
  1. a checkpoint artifact's fingerprint is a pure function of its
     content (same bytes -> same id, any flip -> different id), and
     the serving pointer only ever names a registered version;
  2. weight paging is exact accounting, not heuristics — the
     resident-bytes gauge never exceeds the byte budget, evictions
     follow the LRU oracle exactly, and a model with in-flight
     references is NEVER unloaded (deferred eviction), while a
     double-release is a hard error like a PageAllocator double-free;
  3. a rollout is zero-downtime: every request submitted before,
     during and after the swap completes, and post-swap requests are
     served by the new version.

Engines here are duck-typed stubs (the engine contract: scheduler
.pending/.queue, enqueue, step, generate, shutdown, rebind_perf,
metrics) so the paging/refcount logic is tested without JAX compiles.
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.monitor import events as _events
from paddle_tpu.serving.gateway import AutoscalePolicy
from paddle_tpu.serving.gateway.gateway import ServingGateway
from paddle_tpu.serving.gateway.router import (LeastLoadedRouter,
                                               ModelAffinityRouter)
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.registry import ModelHost, ModelRegistry
from paddle_tpu.serving.registry.registry import artifact_fingerprint
from paddle_tpu.serving.scheduler import DONE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- duck-typed stub engine ------------------------------------------

class StubEngine:
    """Minimal engine-contract implementation: completes every queued
    request on step(), emitting `max_new_tokens` copies of the version
    digit so tests can tell WHICH weights served a request."""

    max_len = 128
    num_slots = 4
    spec_k = 0
    trace_counts = {'prefill': 1, 'decode': 1}

    def __init__(self, entry):
        self.entry = entry
        self.metrics = ServingMetrics()
        self._reqs = []

    class _Sched:
        def __init__(self, eng):
            self.eng = eng

        @property
        def pending(self):
            return sum(1 for r in self.eng._reqs if not r.done)

        @property
        def queue(self):
            return tuple(r for r in self.eng._reqs if not r.done)

    @property
    def scheduler(self):
        return StubEngine._Sched(self)

    def enqueue(self, req):
        if req._arrival_t is None:
            req._arrival_t = self.metrics.now()
        self._reqs.append(req)
        return req

    def step(self):
        for r in self._reqs:
            if not r.done:
                r.tokens.extend([int(self.entry.version[-1])]
                                * r.max_new_tokens)
                r.state = DONE
                r.outcome = 'ok'
                r._finished.set()
        return self.scheduler.pending

    def generate(self, prompts, max_new_tokens=2, emit_event=True):
        return [[1] * max_new_tokens for _ in prompts]

    def shutdown(self):
        pass

    def rebind_perf(self, registry):
        pass


@pytest.fixture
def registry(tmp_path):
    reg = ModelRegistry(root=str(tmp_path))
    for m, v, scale in [('alpha', 'v1', 1.0), ('alpha', 'v2', 2.0),
                        ('beta', 'v1', 3.0), ('gamma', 'v1', 4.0)]:
        reg.publish(m, v, {'w': [scale] * 64})
    return reg


def make_host(registry, **kw):
    return ModelHost(registry, lambda entry: StubEngine(entry), **kw)


# ---- registry: fingerprints and the serving pointer ------------------

def test_fingerprint_is_content_addressed(tmp_path, registry):
    reg2 = ModelRegistry(root=str(tmp_path / 'other'))
    reg2.publish('alpha', 'v9', {'w': [1.0] * 64})
    # identical content under different (model, version) names -> same id
    assert reg2.entry('alpha', 'v9').fingerprint == \
        registry.entry('alpha', 'v1').fingerprint
    # any content change -> different id
    assert registry.entry('alpha', 'v1').fingerprint != \
        registry.entry('alpha', 'v2').fingerprint
    # recomputing from disk agrees with the registered value
    e = registry.entry('alpha', 'v1')
    assert artifact_fingerprint(e.path) == e.fingerprint


def test_serving_pointer_resolution(registry):
    # the FIRST published version holds the pointer: shipping v2 does
    # not silently change what serves — promotion is set_serving()
    assert registry.resolve('alpha').version == 'v1'
    assert registry.set_serving('alpha', 'v2') == 'v1'
    assert registry.serving_version('alpha') == 'v2'
    assert registry.resolve('alpha').version == 'v2'
    registry.set_serving('alpha', 'v1')
    # explicit version bypasses the pointer
    assert registry.resolve('alpha', 'v2').version == 'v2'
    with pytest.raises(KeyError):
        registry.set_serving('alpha', 'v7')
    with pytest.raises(KeyError):
        registry.resolve('nosuch')
    assert ('alpha', 'v1') in registry
    assert registry.versions('alpha') == ['v1', 'v2']


# ---- weight paging: budget, LRU oracle, refcounts --------------------

def test_byte_budget_holds_k_of_n_with_lru_oracle(registry):
    nbytes = registry.entry('alpha', 'v1').nbytes
    # room for exactly two resident artifacts (all four are equal-sized)
    host = make_host(registry, byte_budget=2 * nbytes + nbytes // 2)
    evicted = []
    resident = []          # LRU oracle: least-recently-used-first order

    def oracle_load(key):
        if key in resident:
            resident.remove(key)
        while len(resident) >= 2:
            evicted.append(resident.pop(0))
        resident.append(key)

    for key in [('alpha', 'v1'), ('beta', 'v1'), ('gamma', 'v1'),
                ('alpha', 'v1'), ('alpha', 'v2'), ('beta', 'v1')]:
        host.load(*key)
        oracle_load(key)
        assert host.resident_bytes <= host.byte_budget
        assert sorted(host.resident_models()) == sorted(resident)

    counts = {m: int(host._m_evictions.labels(model=m).value())
              for m in ('alpha', 'beta', 'gamma')}
    want = {m: sum(1 for k in evicted if k[0] == m)
            for m in ('alpha', 'beta', 'gamma')}
    assert counts == want
    # gauge families agree with the accessors
    assert host._m_resident_bytes.value() == host.resident_bytes
    assert host._m_models.value() == len(host.resident_models())


def test_oversized_artifact_rejected(registry):
    nbytes = registry.entry('alpha', 'v1').nbytes
    host = make_host(registry, byte_budget=nbytes // 2)
    with pytest.raises(RuntimeError, match='budget'):
        host.load('alpha', 'v1')


def test_deferred_eviction_with_inflight_refs(registry):
    nbytes = registry.entry('alpha', 'v1').nbytes
    host = make_host(registry, byte_budget=4 * nbytes)
    host.load('alpha', 'v1')
    host.acquire('alpha', 'v1')
    # eviction with a live reference defers instead of unloading: the
    # weights stay resident (bytes still accounted) but the version
    # stops being routable — no NEW request lands on it
    assert host.evict('alpha', 'v1') is False
    assert ('alpha', 'v1') in host.resident_models()
    assert host.resident_bytes == nbytes
    assert not host.hosts_model('alpha', 'v1')
    assert host.refcount('alpha', 'v1') == 1
    assert host._m_deferred.value() == 1
    # the last release completes the deferred eviction
    host.release('alpha', 'v1')
    assert host.resident_models() == []
    assert host.resident_bytes == 0


def test_double_release_raises(registry):
    host = make_host(registry)
    host.load('alpha', 'v1')
    host.acquire('alpha', 'v1')
    host.release('alpha', 'v1')
    with pytest.raises(ValueError, match='double-release'):
        host.release('alpha', 'v1')
    with pytest.raises(ValueError, match='double-release'):
        host.release('beta', 'v1')   # never acquired at all


def test_pinned_model_cannot_be_evicted(registry):
    host = make_host(registry)
    host.load('alpha', 'v1', pin=True)
    with pytest.raises(ValueError, match='pinned'):
        host.evict('alpha', 'v1')
    host.unpin('alpha', 'v1')
    assert host.evict('alpha', 'v1') is True
    with pytest.raises(KeyError):
        host.evict('alpha', 'v1')    # no longer resident


def test_churn_1k_loads_zero_leak(registry):
    """1000 load/acquire/release/evict cycles across all models leave
    zero residue: no bytes, no models, no refcounts, no parked work."""
    keys = [('alpha', 'v1'), ('alpha', 'v2'), ('beta', 'v1'),
            ('gamma', 'v1')]
    nbytes = registry.entry('alpha', 'v1').nbytes
    host = make_host(registry, byte_budget=2 * nbytes + nbytes // 2)
    for i in range(1000):
        key = keys[i % len(keys)]
        host.load(*key)
        host.acquire(*key)
        host.release(*key)
    for key in list(host.resident_models()):
        assert host.refcount(*key) == 0
        host.evict(*key)
    assert host.resident_models() == []
    assert host.resident_bytes == 0
    assert host._m_resident_bytes.value() == 0
    assert host._m_models.value() == 0
    assert host.step() == 0          # nothing parked, nothing loading


# ---- host as engine: park on miss, serve after async load ------------

def test_request_parks_until_model_loads(registry):
    host = make_host(registry)
    req = host.add_request([1, 2, 3], max_new_tokens=4, model='beta',
                           emit_event=False)
    assert not req.done                 # parked: beta not resident yet
    for _ in range(50):
        if req.done:
            break
        host.step()
    assert req.done and req.outcome == 'ok'
    assert req.tokens == [1, 1, 1, 1]   # beta v1 served it
    assert host.hosts_model('beta', 'v1')
    # the in-flight reference was released on retirement
    assert host.refcount('beta', 'v1') == 0


def test_unknown_model_rejected_at_front_door(registry):
    host = make_host(registry)
    with pytest.raises(KeyError):
        host.add_request([1], max_new_tokens=2, model='nosuch',
                         emit_event=False)


# ---- affinity routing ------------------------------------------------

class _FakeReplica:
    def __init__(self, index, hosts, load):
        self.index = index
        self._hosts = hosts
        self._load = load
        self.engine = self

    def routable(self):
        return True

    def load(self):
        return self._load

    def hosts_model(self, model, version=None):
        return model in self._hosts


def test_model_affinity_router_prefers_hosting_replicas():
    pool = [_FakeReplica(0, {'beta'}, load=5),
            _FakeReplica(1, {'alpha'}, load=3),
            _FakeReplica(2, {'alpha'}, load=1),
            _FakeReplica(3, set(), load=0)]
    r = ModelAffinityRouter()
    # hosting replicas first (by load), then the rest (by load)
    assert [x.index for x in r.candidates_for(pool, 'alpha')] == \
        [2, 1, 3, 0]
    assert [x.index for x in r.candidates_for(pool, 'beta')] == \
        [0, 3, 2, 1]
    # unknown model degrades to plain least-loaded order
    assert [x.index for x in r.candidates_for(pool, 'nosuch')] == \
        [3, 2, 1, 0]
    # the base router interface is intact (gateway fallback path)
    assert isinstance(r, LeastLoadedRouter)
    assert [x.index for x in r.candidates(pool)] == [3, 2, 1, 0]


# ---- gateway: multi-model routing + zero-downtime rollout ------------

def test_gateway_multimodel_rollout_zero_loss(registry):
    log = _events.RequestLog()
    prev = _events.set_default_request_log(log)
    try:
        gw = ServingGateway(lambda: make_host(registry),
                            replicas=2, router=ModelAffinityRouter())
        try:
            registry.set_serving('alpha', 'v1')
            before = [gw.submit([1, 2], max_new_tokens=4,
                                model=('alpha' if i % 2 else 'beta'),
                                tenant='t%d' % (i % 3))
                      for i in range(10)]
            gw.run()
            summary = gw.rollout('alpha', 'v2')
            after = [gw.submit([3], max_new_tokens=4, model='alpha')
                     for _ in range(4)]
            gw.run()
        finally:
            gw.shutdown()
    finally:
        _events.set_default_request_log(prev)

    # zero loss: every request before and after the swap completed
    assert all(r.done and r.error is None for r in before + after)
    assert summary['model'] == 'alpha'
    assert summary['from_version'] == 'v1'
    assert summary['to_version'] == 'v2'
    assert summary['replicas'] == [0, 1]
    # pre-swap alpha requests were served by v1, post-swap by v2
    assert all(r.tokens == [1] * 4 for r in before
               if r.sampling.get('model') == 'alpha')
    assert all(r.tokens == [2] * 4 for r in after)
    # wide events carry the model dimension and filter on it
    evs = log.events(model='alpha')
    assert len(evs) == 5 + 4
    assert {e['model'] for e in log.events()} == {'alpha', 'beta'}
    assert all('model' in e for e in log.events())


def test_gateway_rollout_without_hosts_raises(tmp_path):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    import paddle_tpu as paddle
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_position_embeddings=32, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    gw = ServingGateway(
        lambda: PagedContinuousBatchingEngine(m, num_seqs=2, max_len=16),
        replicas=1)
    try:
        with pytest.raises(ValueError, match='ModelHost-backed'):
            gw.rollout('alpha', 'v2')
    finally:
        gw.shutdown()


# ---- autoscaler: per-tenant premium burn -----------------------------

def test_premium_tenant_burn_scales_before_aggregate():
    """Fake clock: aggregate burn stays at zero while one premium
    tenant burns; the policy must scale up on the tenant signal alone,
    naming the tenant in the reason."""
    pol = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=3.0, cooldown_s=0.0,
                          premium_tenants=('premium',))
    hot = {'premium': 0.9, 'bulk': 0.0}
    assert pol.decide(0.0, 0.0, 0.5, 1, 2, tenant_burns=hot).delta == 0
    assert pol.decide(1.0, 0.0, 0.5, 1, 2, tenant_burns=hot).delta == 0
    d = pol.decide(3.0, 0.0, 0.5, 1, 2, tenant_burns=hot)
    assert d.delta == +1
    assert 'premium' in d.reason and 'burn' in d.reason


def test_non_premium_tenant_burn_is_ignored():
    pol = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=2.0, cooldown_s=0.0,
                          premium_tenants=('premium',))
    cold = {'bulk': 0.9}        # a non-premium tenant burning alone
    for t in (0.0, 2.0, 4.0, 6.0):
        assert pol.decide(t, 0.0, 0.0, 0, 2,
                          tenant_burns=cold).delta <= 0
    # ...and a burning premium tenant suppresses idle scale-down
    pol2 = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=2.0, cooldown_s=0.0,
                           premium_tenants=('premium',))
    hot = {'premium': 0.9}
    assert pol2.decide(0.0, 0.0, 0.0, 0, 2, tenant_burns=hot).delta == 0
    d = pol2.decide(2.0, 0.0, 0.0, 0, 2, tenant_burns=hot)
    assert d.delta == +1        # premium burn wins over idle


def test_policy_without_premium_config_is_positional_compatible():
    """Callers predating tenant_burns keep working unchanged."""
    pol = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=0.0, cooldown_s=0.0)
    assert pol.premium_tenants == ()
    assert pol.decide(0.0, 0.9, 0.9, 4, 2).delta == +1


# ---- workload: model dimension, hash-compat --------------------------

def test_workload_models_deterministic_and_hash_compat():
    from paddle_tpu.capacity.workload import WorkloadSpec
    base = WorkloadSpec(requests=200, seed=5)
    multi = WorkloadSpec(requests=200, seed=5,
                         models={'mode': 'zipf', 'count': 3, 'a': 3.0})
    # the models key is absent-when-unset: pre-change specs hash the same
    assert 'models' not in base.to_dict()
    assert base.hash == WorkloadSpec(requests=200, seed=5).hash
    assert multi.hash != base.hash
    # round-trips through the canonical dict
    assert WorkloadSpec.from_dict(multi.to_dict()).hash == multi.hash

    t1, t2 = multi.generate(), multi.generate()
    assert t1.models() == t2.models()          # seeded determinism
    assert (t1.model_id == t2.model_id).all()
    assert set(t1.models()) <= {'model_000', 'model_001', 'model_002'}
    mix = t1.model_mix()
    assert sum(mix.values()) == 200
    # zipf: the head model dominates
    assert mix['model_000'] == max(mix.values())
    # the model stream is independent: same arrivals/tenants either way
    assert (base.generate().arrival == t1.arrival).all()
    # single-model trace reports no model dimension
    assert base.generate().models() is None
    assert base.generate().model_mix() == {}


def test_workload_models_jsonl_round_trip():
    from paddle_tpu.capacity.workload import Trace, WorkloadSpec
    spec = WorkloadSpec(requests=20, seed=2,
                        models={'mode': 'round_robin',
                                'models': [{'name': 'a'}, {'name': 'b'}]})
    trace = spec.generate()
    back = Trace.from_jsonl(trace.to_jsonl())
    assert back.models() == trace.models()
    assert back.models()[:4] == ['a', 'b', 'a', 'b']
    # single-model traces round-trip without a model column at all
    single = WorkloadSpec(requests=20, seed=2).generate()
    text = single.to_jsonl()
    assert '"model"' not in text
    assert Trace.from_jsonl(text).models() is None


# ---- offline gate: tools/registry_report.py --------------------------

def _run_gate(*args):
    """(exit code, parsed JSON lines) — gate_common emits one JSON
    object per line: findings (regression: true) or the ok-summary."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools',
                                      'registry_report.py')] + list(args),
        capture_output=True, text=True, cwd=REPO)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.strip()]
    return proc.returncode, lines


def test_registry_report_exit_codes(tmp_path):
    # nothing to check -> 2
    rc, _ = _run_gate()
    assert rc == 2

    clean = tmp_path / 'clean.json'
    clean.write_text(json.dumps({
        'model': 'alpha', 'from_version': 'v1', 'to_version': 'v2',
        'replicas': 2, 'cache_hits': 3, 'cache_misses': 0,
        'requests': 10, 'completed': 10}))
    rc, out = _run_gate('--rollout', str(clean))
    assert rc == 0
    assert out[-1]['ok'] is True
    assert out[-1]['rollout']['to_version'] == 'v2'

    lossy = tmp_path / 'lossy.json'
    lossy.write_text(json.dumps({
        'model': 'alpha', 'from_version': 'v1', 'to_version': 'v2',
        'requests': 10, 'completed': 8, 'cache_misses': 0}))
    rc, out = _run_gate('--rollout', str(lossy))
    assert rc == 1
    assert out[0]['problem'] == 'rollout_lost_requests'
    assert out[0]['regression'] is True

    cold = tmp_path / 'cold.json'
    cold.write_text(json.dumps({
        'model': 'alpha', 'to_version': 'v2', 'requests': 4,
        'completed': 4, 'cache_hits': 0, 'cache_misses': 2}))
    rc, out = _run_gate('--rollout', str(cold))
    assert rc == 1
    assert out[0]['problem'] == 'rollout_compile_cache_miss'


def test_registry_report_metrics_cross_checks(tmp_path):
    metrics = tmp_path / 'metrics.json'
    metrics.write_text(json.dumps({
        'registry_resident_bytes': {
            'type': 'gauge', 'labels': [],
            'samples': [{'labels': {}, 'value': 900.0}]},
        'registry_models_resident': {
            'type': 'gauge', 'labels': [],
            'samples': [{'labels': {}, 'value': 2.0}]}}))
    rc, out = _run_gate('--metrics', str(metrics), '--byte-budget',
                        '1000')
    assert rc == 0
    assert out[-1]['registry_metrics']['registry_resident_bytes'] == 900.0
    rc, out = _run_gate('--metrics', str(metrics), '--byte-budget', '800')
    assert rc == 1
    assert out[0]['problem'] == 'resident_bytes_over_budget'


def test_registry_report_model_events_gate(tmp_path):
    sink = tmp_path / 'events.jsonl'
    rows = [{'request_id': i, 'model': 'alpha', 'outcome': 'ok',
             'output_tokens': 4} for i in range(3)]
    rows.append({'request_id': 9, 'model': 'alpha', 'outcome': 'error',
                 'output_tokens': 0})
    sink.write_text('\n'.join(json.dumps(r) for r in rows) + '\n')
    rc, out = _run_gate('--jsonl', str(sink))
    assert rc == 0          # no --model gate: report only
    assert out[-1]['models']['alpha']['requests'] == 4
    assert out[-1]['models']['alpha']['errors'] == 1
    rc, out = _run_gate('--jsonl', str(sink), '--model', 'alpha')
    assert rc == 1
    assert out[0]['problem'] == 'model_request_not_ok'
