"""Multi-replica serving gateway tests (paddle_tpu/serving/gateway/).

The load-bearing assertions from the gateway's contract:
  1. routing/failover/drain never buy availability with output drift —
     whatever the pool does internally, delivered tokens are IDENTICAL
     to a single engine's greedy run (seeded determinism + the
     delivered-token ledger give exactly-once delivery);
  2. chaos-oracle failover (the test_resilience.py discipline): a
     replica partitioned mid-burst yields EXACTLY as many
     gateway_failover_total increments as it had in-flight non-finished
     requests, and 100% of requests still complete;
  3. the autoscaler is a pure function of (clock, observations) —
     sustained burn scales up, sustained idle scales down, flapping and
     cooldown suppress everything else.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.monitor.registry import MetricRegistry
from paddle_tpu.serving import (PagedContinuousBatchingEngine,
                                ServingGateway)
from paddle_tpu.serving.gateway import (AutoscalePolicy, LeastLoadedRouter,
                                        RoundRobinRouter, slo_burn_rate)
from paddle_tpu.serving.gateway.replica import DEAD, DRAINING, STOPPED
from paddle_tpu.testing import chaos
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

MNT = 8          # max_new_tokens everywhere: keeps the suite fast


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
            for n in (3, 17, 7, 12, 5, 21, 9, 4, 14, 6)]


@pytest.fixture(scope='module')
def reference(model, prompts):
    """Single-engine greedy outputs — the parity oracle."""
    return _factory(model)().generate(prompts, max_new_tokens=MNT)


def _factory(model, **kw):
    return lambda: PagedContinuousBatchingEngine(
        model, num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
        decode_block=2, **kw)


def _gw(model, factory=None, **kw):
    kw.setdefault('registry', MetricRegistry())
    return ServingGateway(factory or _factory(model), **kw)


def _counter(gw, name, labels=None):
    fam = gw.registry.get(name)
    if labels is None:
        return fam.value()
    return fam.labels(*labels).value()


# ---- routing ----------------------------------------------------------


def test_least_loaded_spreads_and_parity(model, prompts, reference):
    """Sync drive: the router spreads a burst across both replicas on
    their live queue/occupancy gauges, and delivered tokens match the
    single-engine run exactly."""
    gw = _gw(model, replicas=2)
    out = gw.generate(prompts, max_new_tokens=MNT)
    assert out == reference
    routed = [_counter(gw, 'gateway_route_total', (str(i),))
              for i in range(2)]
    assert sum(routed) == len(prompts)
    assert all(v > 0 for v in routed), routed
    assert _counter(gw, 'gateway_requests_completed_total') == len(prompts)
    assert _counter(gw, 'gateway_failover_total') == 0
    assert gw.report()['pending'] == 0


def test_round_robin_router(model, prompts, reference):
    gw = _gw(model, replicas=2, router=RoundRobinRouter())
    out = gw.generate(prompts[:4], max_new_tokens=MNT)
    assert out == reference[:4]
    routed = [_counter(gw, 'gateway_route_total', (str(i),))
              for i in range(2)]
    assert routed == [2.0, 2.0]


def test_small_pool_replicas_parity(model, prompts, reference):
    """Replicas whose pool is under the demand (5 usable pages for two
    sequences of up to 4, no prefix cache) block admission on pages and
    still answer with the reference's tokens."""
    gw = _gw(model, replicas=2,
             factory=_factory(model, num_pages=6, prefix_cache=False))
    assert gw.generate(prompts[:6], max_new_tokens=MNT) == reference[:6]


def test_inadmissible_request_raises_not_failover(model):
    """The engines' front-door guard propagates to the submit() caller;
    it must never be mistaken for a transport failure."""
    gw = _gw(model, replicas=2)
    with pytest.raises(ValueError, match='max_len'):
        gw.submit(list(range(1, 30)), max_new_tokens=MNT)  # 29+8-1 > 32
    assert _counter(gw, 'gateway_requests_total') == 0
    assert _counter(gw, 'gateway_failover_total') == 0
    assert all(r.routable() for r in gw.pool)


# ---- failover ---------------------------------------------------------


@pytest.mark.chaos
def test_partition_failover_exact_oracle(model, prompts, reference):
    """THE acceptance test: a Poisson-arrival burst over 2 replicas,
    one partitioned mid-burst. Every request completes, outputs are
    token-identical to the single-engine run, and the failover counter
    equals EXACTLY the partitioned replica's in-flight non-finished
    count at the moment of loss (chaos-oracle style)."""
    gw = _gw(model, replicas=2)
    # seeded Poisson arrival process, quantised to engine steps
    gaps = np.random.RandomState(5).exponential(1.0, size=len(prompts))
    arrival_step = np.floor(np.cumsum(gaps) / 1.5).astype(int)
    kill_at = len(prompts) // 2
    reqs, expected, fault = [], None, None
    ctx = None
    try:
        i = k = 0
        while i < len(prompts) or any(not r.done for r in reqs):
            while i < len(prompts) and arrival_step[i] <= k:
                if i == kill_at:
                    ctx = chaos.partition(gw.pool[1].endpoint)
                    fault = ctx.__enter__()
                    # the oracle: in-flight non-finished on replica 1
                    # the instant the partition lands
                    expected = len([g for g in gw.pool[1].assigned
                                    if len(g.tokens) < MNT])
                reqs.append(gw.submit(prompts[i], max_new_tokens=MNT))
                i += 1
            gw.step()
            k += 1
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)

    assert expected is not None and expected > 0
    assert all(r.done for r in reqs)                    # 100% complete
    assert [r.tokens for r in reqs] == reference        # exact parity
    assert _counter(gw, 'gateway_failover_total') == expected
    # every failover is a re-placement in some request's history
    assert sum(len(r.replica_history) - 1 for r in reqs) == expected
    assert fault.fired >= 1
    assert len(gw.failover_log) == 1
    assert gw.failover_log[0]['replica'] == 1
    assert len(gw.failover_log[0]['requests']) == expected
    # the dead replica is fenced: breaker open, never routable again
    rep = gw.pool[1]
    assert rep.state == DEAD
    assert not rep.routable()
    assert gw.registry.get('gateway_replica_state').labels('1').value() \
        == 2.0
    assert _counter(gw, 'gateway_replicas') == 1
    # no chaos leaked into the next test
    assert chaos.active_faults() == 0


@pytest.mark.chaos
def test_partition_at_submission_retries_elsewhere(model, prompts,
                                                   reference):
    """A partition hit at submit time (no in-flight work yet) is a
    retry, not a failover: the walk places the request on the live
    replica in the same call."""
    gw = _gw(model, replicas=2)
    with chaos.partition(gw.pool[1].endpoint):
        reqs = [gw.submit(p, max_new_tokens=MNT) for p in prompts[:4]]
        gw.run()
    assert [r.tokens for r in reqs] == reference[:4]
    assert _counter(gw, 'gateway_retries_total') == 1.0
    assert _counter(gw, 'gateway_failover_total') == 0
    assert all(r.replica_history == [0] for r in reqs)
    assert gw.pool[1].state == DEAD


def test_kill_replica_threaded_parity(model, prompts, reference):
    """Driver-thread mode: kill a replica while its driver is mid-
    flight; every request completes with exact parity."""
    gw = _gw(model, replicas=2).start()
    try:
        reqs = [gw.submit(p, max_new_tokens=MNT) for p in prompts]
        gw.kill_replica(1)
        for r in reqs:
            assert r.wait(120), r
        assert [r.tokens for r in reqs] == reference
        assert len(gw.failover_log) == 1
        assert gw.failover_log[0]['replica'] == 1
    finally:
        gw.shutdown()
    assert gw.report()['completed'] == len(prompts)


# ---- drain ------------------------------------------------------------


def test_drain_finishes_in_flight_without_failover(model, prompts,
                                                   reference):
    """Graceful drain: the draining replica stops taking NEW work but
    its in-flight requests finish in place (no re-admission)."""
    gw = _gw(model, replicas=2)
    first = [gw.submit(p, max_new_tokens=MNT) for p in prompts[:4]]
    gw.step()
    drained = gw.drain_replica(1)
    assert drained.state == DRAINING
    assert not drained.ready()
    later = [gw.submit(p, max_new_tokens=MNT) for p in prompts[4:]]
    gw.run()
    assert [r.tokens for r in first + later] == reference
    assert _counter(gw, 'gateway_failover_total') == 0
    # nothing submitted after the drain landed on replica 1
    assert all(r.replica_history == [0] for r in later)
    # the drained replica ran dry and stopped
    assert drained.state == STOPPED


def test_replica_readyz_flips_on_drain(model):
    """Satellite integration: a replica's MetricsServer serves 200 on
    /readyz while READY and 503 once draining — with /healthz at 200
    throughout (drain must not look like death to the kubelet)."""
    import json
    import urllib.error
    import urllib.request
    gw = _gw(model, replicas=1)
    rep = gw.pool[0]
    with rep.metrics_server() as srv:
        ok = urllib.request.urlopen(srv.url + '/readyz', timeout=5)
        assert ok.status == 200
        assert json.loads(ok.read().decode())['status'] == 'ready'
        gw.drain_replica(0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + '/readyz', timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())['status'] == 'draining'
        assert urllib.request.urlopen(srv.url + '/healthz',
                                      timeout=5).status == 200
        # the replica's own serving gauges are on this scrape endpoint
        body = urllib.request.urlopen(srv.url + '/metrics',
                                      timeout=5).read().decode()
        assert 'serving_queue_depth' in body


def test_gateway_shutdown_drains_all(model, prompts, reference):
    gw = _gw(model, replicas=2).start()
    reqs = [gw.submit(p, max_new_tokens=MNT) for p in prompts[:6]]
    gw.shutdown()
    assert all(r.done for r in reqs)
    assert [r.tokens for r in reqs] == reference[:6]
    assert all(r.state == STOPPED for r in gw.pool)
    with pytest.raises(Exception):
        # drained engines refuse new work end to end
        gw.pool[0].engine.add_request([1, 2], max_new_tokens=2)


def test_streaming_through_gateway(model, prompts, reference):
    gw = _gw(model, replicas=2).start()
    try:
        req = gw.submit(prompts[0], max_new_tokens=MNT, stream=True)
        got = list(req.stream())
    finally:
        gw.shutdown()
    assert got == reference[0]
    assert req.done


# ---- autoscaler: pure policy with an injectable clock -----------------


def test_slo_burn_rate_windows():
    samples = [(0.0, 0.1), (5.0, 0.9), (10.0, 0.9), (15.0, 0.1)]
    assert slo_burn_rate(samples, 15.0, 0.5, 30.0) == 0.5
    assert slo_burn_rate(samples, 15.0, 0.5, 6.0) == \
        pytest.approx(1.0 / 2.0)      # only t=10,15 in window
    assert slo_burn_rate([], 0.0, 0.5, 30.0) == 0.0
    assert slo_burn_rate(samples, 100.0, 0.5, 10.0) == 0.0


def test_policy_sustained_burn_scales_up():
    pol = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=3.0, cooldown_s=10.0)
    assert pol.decide(0.0, 0.9, 0.9, 4, 2).delta == 0    # just started
    assert pol.decide(1.0, 0.9, 0.9, 4, 2).delta == 0
    d = pol.decide(3.0, 0.9, 0.9, 4, 2)
    assert d.delta == +1 and 'burn' in d.reason
    # immediately after acting: sustain restarts, then cooldown holds
    assert pol.decide(4.0, 0.9, 0.9, 4, 3).delta == 0
    d2 = pol.decide(7.0, 0.9, 0.9, 4, 3)
    assert d2.delta == 0 and 'cooling' in d2.reason
    # cooldown elapsed + still burning -> acts again
    assert pol.decide(13.0, 0.9, 0.9, 4, 3).delta == +1


def test_policy_sustained_idle_scales_down_to_min():
    pol = AutoscalePolicy(slo_ttft_s=0.5, min_replicas=1, sustain_s=2.0,
                          cooldown_s=0.0)
    assert pol.decide(0.0, 0.0, 0.0, 0, 2).delta == 0
    d = pol.decide(2.0, 0.0, 0.0, 0, 2)
    assert d.delta == -1 and 'idle' in d.reason
    # at the floor: idle forever never goes below min_replicas
    assert pol.decide(4.0, 0.0, 0.0, 0, 1).delta == 0
    assert pol.decide(9.0, 0.0, 0.0, 0, 1).delta == 0


def test_policy_flapping_suppressed_by_hysteresis():
    """A burn signal that toggles faster than sustain_s never acts; a
    pool oscillating hot/idle around an action is pinned by cooldown."""
    pol = AutoscalePolicy(slo_ttft_s=0.5, sustain_s=3.0, cooldown_s=20.0)
    for t in range(0, 12, 2):
        burn = 0.9 if (t // 2) % 2 == 0 else 0.0   # toggles every 2 s
        assert pol.decide(float(t), burn, 0.5, 1, 2).delta == 0
    # sustained burn finally acts...
    for t in (12.0, 14.0, 15.0):
        d = pol.decide(t, 0.9, 0.9, 4, 2)
    assert d.delta == +1
    # ...then a hard swing to idle within cooldown cannot flap it back
    for t in (16.0, 17.0, 18.0, 19.0, 20.0):
        assert pol.decide(t, 0.0, 0.0, 0, 3).delta == 0
    assert pol.decide(35.0, 0.0, 0.0, 0, 3).delta == -1


def test_policy_respects_max_replicas():
    pol = AutoscalePolicy(slo_ttft_s=0.5, max_replicas=2, sustain_s=0.0,
                          cooldown_s=0.0)
    d = pol.decide(0.0, 1.0, 1.0, 9, 2)
    assert d.delta == 0 and 'max_replicas' in d.reason


def test_policy_validates_bounds():
    with pytest.raises(ValueError, match='min_replicas'):
        AutoscalePolicy(slo_ttft_s=0.5, min_replicas=0)
    with pytest.raises(ValueError, match='min_replicas'):
        AutoscalePolicy(slo_ttft_s=0.5, min_replicas=4, max_replicas=2)


def test_autoscale_tick_grows_and_drains_pool(model):
    """Gateway integration on a fake clock: sustained burn builds a new
    replica from the factory; sustained idle drains the least-loaded
    one (never kills it)."""
    clock = {'t': 0.0}
    gw = _gw(model, replicas=1, clock=lambda: clock['t'],
             autoscaler=AutoscalePolicy(slo_ttft_s=0.5, sustain_s=2.0,
                                        cooldown_s=5.0, window_s=60.0,
                                        max_replicas=2))
    # synthetic TTFT samples breaching the SLO
    for t in (1.0, 2.0, 3.0):
        gw._ttfts.append((t, 2.0))
    clock['t'] = 4.0
    assert gw.autoscale_tick().delta == 0        # burn timer starts
    clock['t'] = 6.5
    d = gw.autoscale_tick()
    assert d.delta == +1
    assert len(gw.pool) == 2
    assert gw.pool[1].routable()                 # new replica takes work
    assert gw.registry.get('gateway_scale_events_total') \
        .labels('up').value() == 1.0
    assert _counter(gw, 'gateway_slo_burn_rate') == 1.0
    # burn clears, samples age out of the window -> sustained idle
    gw._ttfts.clear()
    clock['t'] = 20.0
    assert gw.autoscale_tick().delta == 0        # idle timer starts
    clock['t'] = 23.0
    d = gw.autoscale_tick()
    assert d.delta == -1
    assert gw.registry.get('gateway_scale_events_total') \
        .labels('down').value() == 1.0
    states = sorted(r.state for r in gw.pool)
    assert DRAINING in states                    # drained, not killed
    gw.run()                                     # runs dry -> stopped
    assert sorted(r.state for r in gw.pool)[-1] == STOPPED


# ---- threaded soak ----------------------------------------------------


def test_threaded_concurrent_submitters(model, prompts, reference):
    """Several caller threads submit concurrently against driver
    threads; everything completes with exact parity."""
    gw = _gw(model, replicas=2).start()
    results = {}
    try:
        def client(base):
            for j, p in enumerate(prompts[base::2]):
                r = gw.submit(p, max_new_tokens=MNT)
                assert r.wait(120)
                results[base + 2 * j] = r.tokens
        ts = [threading.Thread(target=client, args=(b,)) for b in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(180)
        assert not any(t.is_alive() for t in ts)
    finally:
        gw.shutdown()
    assert [results[i] for i in range(len(prompts))] == reference


@pytest.mark.slow
def test_predictor_decode_gateway(model, prompts, tmp_path):
    """The fleet front door reached the inference API: a jit.save'd
    causal LM round-trips into a gateway whose pooled output matches
    the live model's generate()."""
    path = str(tmp_path / 'gpt_lm')
    paddle.jit.save(model, path)
    from paddle_tpu import inference
    pred = inference.create_predictor(inference.Config(path))
    gw = pred.decode_gateway(replicas=2, registry=MetricRegistry(),
                             num_seqs=2, max_len=64, page_size=8,
                             prefill_chunk=8, decode_block=4)
    got = gw.generate(prompts[:3], max_new_tokens=6)
    expect = [[int(t) for t in model.generate(
        paddle.to_tensor([p]), max_new_tokens=6).numpy()[0][len(p):]]
        for p in prompts[:3]]
    assert got == expect
    assert len(gw.pool) == 2
