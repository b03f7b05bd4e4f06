"""Capacity subsystem: workload determinism, trace loaders, the
discrete-event simulator, replay through a real in-proc gateway, and
the sim-vs-real calibration gate (ISSUE 16)."""
import json
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.capacity import simulator, workload


def _poisson_spec(n=64, mean_gap=0.01, seed=0, **kw):
    base = dict(requests=n, seed=seed, vocab_size=512,
                arrival={'process': 'poisson', 'mean_gap_s': mean_gap},
                lengths={'dist': 'ladder', 'lens': [8, 16, 24, 32]},
                output={'dist': 'fixed', 'len': 16})
    base.update(kw)
    return workload.WorkloadSpec(**base)


MODEL = simulator.ServiceModel(prefill_chunk_s=0.002, decode_burst_s=0.004)


# ---------------------------------------------------------------------------
# workload generation


def test_same_spec_same_seed_is_byte_identical():
    a = workload.generate(_poisson_spec())
    b = workload.generate(_poisson_spec())
    assert a.to_jsonl() == b.to_jsonl()
    assert a.prompts() == b.prompts()
    assert a.spec_hash == b.spec_hash


def test_different_seed_different_trace():
    a = workload.generate(_poisson_spec(seed=0))
    b = workload.generate(_poisson_spec(seed=1))
    assert a.to_jsonl() != b.to_jsonl()
    assert a.spec_hash != b.spec_hash  # seed is part of the spec


def test_poisson_matches_retired_bench_generator():
    # the exact formula bench_extra._poisson_arrivals used; stored bench
    # bests depend on this stream staying bit-identical
    gaps = np.random.RandomState(0).exponential(0.01, size=64)
    ref = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tr = workload.generate(_poisson_spec(n=64, mean_gap=0.01))
    assert np.array_equal(tr.arrival, ref)


def test_ladder_prompts_match_retired_bench_generator():
    lens = [8, 16, 24, 32]
    rng = np.random.RandomState(0)
    ref = [[int(t) for t in rng.randint(0, 512, lens[i % 4])]
           for i in range(16)]
    tr = workload.generate(_poisson_spec(n=16))
    assert tr.prompts() == ref


def test_shared_prefix_prompts_match_retired_paged_generator():
    rng = np.random.RandomState(0)
    system = [int(t) for t in rng.randint(0, 512, 32)]
    tails = [4, 8, 12, 16]
    ref = [system + [int(t) for t in rng.randint(0, 512, tails[i % 4])]
           for i in range(12)]
    tr = workload.generate(_poisson_spec(
        n=12, arrival={'process': 'burst'},
        lengths={'dist': 'ladder', 'lens': tails},
        prefix={'len': 32, 'groups': 1, 'prob': 1.0}))
    assert tr.prompts() == ref
    assert tr.arrivals() == [0.0] * 12


def test_heavy_tail_and_diurnal_shapes():
    tr = workload.generate(workload.WorkloadSpec(
        requests=2000, seed=3,
        arrival={'process': 'diurnal', 'mean_gap_s': 0.01,
                 'period_s': 5.0, 'peak_to_trough': 4.0},
        lengths={'dist': 'zipf', 'a': 1.5, 'min': 4, 'max': 512},
        output={'dist': 'lognormal', 'median': 16, 'sigma': 0.7,
                'min': 1, 'max': 128},
        tenants={'mode': 'zipf', 'count': 10, 'a': 1.5}))
    assert len(tr) == 2000
    assert (np.diff(tr.arrival) >= 0).all()
    assert tr.prompt_len.min() >= 4 and tr.prompt_len.max() <= 512
    assert tr.new_tokens.min() >= 1 and tr.new_tokens.max() <= 128
    # zipf tenancy is skewed: the top tenant dominates
    mix = tr.tenant_mix()
    assert max(mix.values()) > 2000 / 10


def test_weighted_tenants_and_burst_rider():
    tr = workload.generate(workload.WorkloadSpec(
        requests=500, seed=1, vocab_size=512,
        arrival={'process': 'poisson', 'mean_gap_s': 0.01,
                 'burst': {'prob': 0.1, 'size': 4, 'jitter_s': 1e-4}},
        lengths={'dist': 'fixed', 'len': 16},
        output={'dist': 'fixed', 'len': 8},
        tenants={'mode': 'weighted', 'tenants': [
            {'name': 'big', 'weight': 9}, {'name': 'small', 'weight': 1}]}))
    assert (np.diff(tr.arrival) >= 0).all()
    mix = tr.tenant_mix()
    assert mix['big'] > mix['small']


# ---------------------------------------------------------------------------
# trace serialization + loaders


def test_jsonl_roundtrip_preserves_everything():
    tr = workload.generate(_poisson_spec(
        n=32, tenants={'mode': 'round_robin', 'tenants': [
            {'name': 'a'}, {'name': 'b'}]}))
    back = workload.Trace.from_jsonl(tr.to_jsonl())
    assert back.to_jsonl() == tr.to_jsonl()
    assert back.tenants() == tr.tenants()
    assert np.array_equal(back.arrival, tr.arrival)


def test_trace_from_wide_events_preserves_order_and_mix():
    # recorded events arrive in completion order, not arrival order —
    # the loader must re-sort and rebase
    events = [
        {'request_id': 'r2', 'arrival_t': 107.0, 'tenant': 'b',
         'prompt_tokens': 8, 'output_tokens': 4, 'finish_t': 110.0},
        {'request_id': 'r0', 'arrival_t': 100.5, 'tenant': 'a',
         'prompt_tokens': 16, 'output_tokens': 8, 'finish_t': 109.0},
        {'request_id': 'r1', 'arrival_t': 103.0, 'tenant': 'a',
         'prompt_tokens': 4, 'output_tokens': 2, 'finish_t': 104.0},
    ]
    tr = workload.trace_from_events(events)
    assert tr.arrivals() == [0.0, 2.5, 6.5]
    assert tr.tenants() == ['a', 'a', 'b']
    assert tr.tenant_mix() == {'a': 2, 'b': 1}
    assert list(tr.prompt_len) == [16, 4, 8]


def test_load_trace_reads_sink_jsonl_and_trace_jsonl(tmp_path):
    tr = workload.generate(_poisson_spec(n=8))
    p = tmp_path / 'trace.jsonl'
    p.write_text(tr.to_jsonl())
    back = workload.load_trace(path=str(p))
    assert back.to_jsonl() == tr.to_jsonl()

    sink = tmp_path / 'sink.jsonl'
    sink.write_text('\n'.join(json.dumps(
        {'request_id': 'r%d' % i, 'arrival_t': 50.0 + i * 0.25,
         'tenant': 't', 'prompt_tokens': 4, 'output_tokens': 2,
         'finish_t': 51.0 + i * 0.25}) for i in range(5)) + '\n')
    loaded = workload.load_trace(path=str(sink))
    assert len(loaded) == 5
    assert loaded.arrivals()[0] == 0.0


# ---------------------------------------------------------------------------
# simulator


def test_simulator_more_replicas_non_increasing_p99():
    tr = workload.generate(_poisson_spec(n=400, mean_gap=0.002))
    p99s = []
    for c in (1, 2, 4, 8):
        res = simulator.simulate(tr, MODEL, replicas=c,
                                 router='round_robin')
        assert (res.finish > 0).all()
        p99s.append(res.ttft_percentiles((99,))[99])
    assert all(a >= b - 1e-9 for a, b in zip(p99s, p99s[1:])), p99s


def test_sweep_reports_min_replicas():
    tr = workload.generate(_poisson_spec(n=400, mean_gap=0.002))
    sweep = simulator.sweep_replicas(tr, MODEL, counts=(1, 2, 4, 8),
                                     slo_ttft_s=0.05)
    assert sweep['min_replicas'] is not None
    first_ok = next(p['replicas'] for p in sweep['points']
                    if p['meets_slo'])
    assert sweep['min_replicas'] == first_ok
    # unreachable SLO -> explicit None, not a wrong answer
    none_sweep = simulator.sweep_replicas(tr, MODEL, counts=(1,),
                                          slo_ttft_s=1e-9)
    assert none_sweep['min_replicas'] is None


def test_simulator_failover_reroutes_and_finishes():
    tr = workload.generate(_poisson_spec(n=200, mean_gap=0.002,
                                         output={'dist': 'fixed',
                                                 'len': 32}))
    res = simulator.simulate(tr, MODEL, replicas=3,
                             kill_at={1: tr.duration_s / 2})
    assert res.failovers.sum() > 0
    assert (res.finish > 0).all()


def test_simulator_autoscaler_policy_scales_up():
    from paddle_tpu.serving.gateway.autoscaler import AutoscalePolicy
    tr = workload.generate(_poisson_spec(
        n=2000, mean_gap=0.002,
        lengths={'dist': 'fixed', 'len': 64},
        output={'dist': 'fixed', 'len': 16}))
    pol = AutoscalePolicy(slo_ttft_s=0.02, min_replicas=1,
                          max_replicas=8, sustain_s=0.5, cooldown_s=1.0,
                          window_s=5.0)
    flat = simulator.simulate(tr, MODEL, replicas=1)
    scaled = simulator.simulate(tr, MODEL, replicas=1, policy=pol)
    assert scaled.max_replicas > 1
    assert (scaled.ttft_percentiles((99,))[99]
            < flat.ttft_percentiles((99,))[99])


def test_simulator_prefix_cache_hits_speed_up():
    spec = _poisson_spec(n=200, mean_gap=0.002,
                         lengths={'dist': 'fixed', 'len': 8},
                         prefix={'len': 64, 'groups': 2, 'prob': 1.0})
    tr = workload.generate(spec)
    res = simulator.simulate(tr, MODEL, replicas=1)
    assert res.prefix_hits.sum() > 0
    # a cold-cache run of the same load (prefix structure stripped)
    cold = workload.Trace(tr.arrival, tr.prompt_len, tr.new_tokens,
                          tr.tenant_id, tr.tenant_names,
                          np.full(len(tr), -1), np.zeros(len(tr)),
                          meta=tr.meta)
    res_cold = simulator.simulate(cold, MODEL, replicas=1)
    assert res.ttft_percentiles((99,))[99] \
        < res_cold.ttft_percentiles((99,))[99]


def test_sim_events_speak_the_wide_schema():
    from paddle_tpu.monitor.events import FIELD_NAMES
    tr = workload.generate(_poisson_spec(n=16))
    ev = simulator.simulate(tr, MODEL, replicas=1).to_events()
    assert len(ev) == 16
    assert set(ev[0]) == set(FIELD_NAMES)
    assert all(e['first_token_t'] >= e['admit_t'] >= e['arrival_t']
               for e in ev)


def test_ks_statistic_and_divergence():
    assert simulator.ks_statistic([1, 2, 3], [1, 2, 3]) == 0.0
    assert simulator.ks_statistic([0, 0, 0], [1, 1, 1]) == 1.0
    div = simulator.ttft_divergence([0.1] * 10, [0.2] * 10)
    assert div['p50_rel_err'] == pytest.approx(0.5)
    with pytest.raises(ValueError):
        simulator.ttft_divergence([], [0.1])


def test_compare_events_per_tenant_skips_small_samples():
    def ev(tenant, ttft, i):
        return {'request_id': i, 'tenant': tenant, 'arrival_t': 0.0,
                'first_token_t': ttft}
    sim = [ev('a', 0.1, i) for i in range(5)] + [ev('b', 0.1, 'x')]
    real = [ev('a', 0.1, i) for i in range(5)] + [ev('b', 0.1, 'y')]
    cmp = simulator.compare_events(sim, real)
    assert cmp['overall']['p50_rel_err'] == 0.0
    assert 'skipped' in cmp['tenants']['b']
    assert cmp['tenants']['a']['ks'] == 0.0


def test_service_model_from_roofline_and_bench_rows():
    m = simulator.ServiceModel.from_roofline(1e8, 2e8, platform='cpu')
    assert m.prefill_chunk_s > 0 and m.decode_burst_s > 0
    rows = [{'metric': 'serving_cb_tokens_per_sec', 'value': 1000.0,
             'num_slots': 8}]
    m2 = simulator.ServiceModel.from_bench_rows(rows)
    assert m2.decode_burst_s == pytest.approx(8 * 8 / 1000.0)
    with pytest.raises(ValueError):
        simulator.ServiceModel.from_bench_rows([])


@pytest.mark.slow
def test_million_request_sweep_is_fast():
    tr = workload.generate(workload.WorkloadSpec(
        requests=1000000, seed=0,
        arrival={'process': 'poisson', 'mean_gap_s': 0.0005},
        lengths={'dist': 'zipf', 'a': 1.8, 'min': 8, 'max': 256},
        output={'dist': 'fixed', 'len': 16}))
    sweep = simulator.sweep_replicas(tr, MODEL, counts=(16, 32),
                                     slo_ttft_s=0.25)
    assert sweep['min_replicas'] is not None
    assert sum(p['sim_wall_s'] for p in sweep['points']) < 60.0


# ---------------------------------------------------------------------------
# replay through the real in-proc gateway + calibration


def _tiny_engine_factory():
    import paddle_tpu as paddle
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return lambda: PagedContinuousBatchingEngine(
        model, num_seqs=4, max_len=48, page_size=8, prefill_chunk=8,
        decode_block=4)


def test_replay_roundtrip_preserves_order_and_tenants():
    from paddle_tpu.capacity.replay import measure
    spec = workload.WorkloadSpec(
        requests=6, seed=0, vocab_size=128,
        arrival={'process': 'poisson', 'mean_gap_s': 0.005},
        lengths={'dist': 'ladder', 'lens': [4, 8]},
        output={'dist': 'fixed', 'len': 8},
        tenants={'mode': 'round_robin', 'tenants': [
            {'name': 'premium'}, {'name': 'batch'}]})
    tr = workload.generate(spec)
    events, res = measure(_tiny_engine_factory(), tr, replicas=1,
                          timeout=120)
    assert res.completed == len(tr)
    assert len(events) == len(tr)
    # arrival order and tenant mix survive the trip through the gateway
    evs = sorted(events, key=lambda e: e['arrival_t'])
    assert [e['tenant'] for e in evs] == tr.tenants()
    got_mix = {}
    for e in events:
        got_mix[e['tenant']] = got_mix.get(e['tenant'], 0) + 1
    assert got_mix == tr.tenant_mix()
    # and the recorded run loads back as a Trace in arrival order
    back = workload.trace_from_events(events)
    assert len(back) == len(tr)
    assert list(back.prompt_len) == [len(p) for p in tr.prompts()]


def test_sim_vs_real_calibration_small_poisson_burst():
    from paddle_tpu.capacity.replay import measure
    spec = workload.WorkloadSpec(
        requests=10, seed=0, vocab_size=128,
        arrival={'process': 'poisson', 'mean_gap_s': 0.01},
        lengths={'dist': 'ladder', 'lens': [4, 8, 12]},
        output={'dist': 'fixed', 'len': 12})
    tr = workload.generate(spec)
    events, _ = measure(_tiny_engine_factory(), tr, replicas=1,
                        timeout=120)
    model = simulator.ServiceModel.from_events(
        events, prefill_chunk=8, decode_block=4, num_slots=4,
        trace=tr, replicas=1)
    res = simulator.simulate(tr, model, replicas=1)
    div = simulator.ttft_divergence(
        res.ttft(), simulator.ttfts_of_events(events))
    # committed thresholds (tools/capacity_report.py defaults): CI boxes
    # are noisy, but the calibrated simulator must stay in the ballpark
    assert div['p50_rel_err'] <= 0.5, div
    assert div['p99_rel_err'] <= 0.5, div


# ---------------------------------------------------------------------------
# the offline gate CLI


def _run_report(*args):
    return subprocess.run(
        [sys.executable, 'tools/capacity_report.py'] + list(args),
        capture_output=True, text=True)


def test_capacity_report_protocol(tmp_path):
    tr = workload.generate(_poisson_spec(n=50))
    tp = tmp_path / 'trace.jsonl'
    tp.write_text(tr.to_jsonl())
    real = tmp_path / 'real.jsonl'
    res = simulator.simulate(tr, MODEL, replicas=1)
    real.write_text('\n'.join(json.dumps(e) for e in res.to_events()))

    ok = _run_report('--trace', str(tp), '--simulate',
                     '--prefill-chunk-s', '0.002',
                     '--decode-burst-s', '0.004', '--real', str(real))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    out = json.loads(ok.stdout.splitlines()[-1])
    assert out['ok'] and out['divergence']['overall']['ks'] == 0.0

    bad = _run_report('--trace', str(tp), '--simulate',
                      '--prefill-chunk-s', '0.05',
                      '--decode-burst-s', '0.1', '--real', str(real))
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert any(json.loads(l).get('problem') == 'ttft_divergence'
               for l in bad.stdout.splitlines() if l.startswith('{'))

    nothing = _run_report()
    assert nothing.returncode == 2

    sweep = _run_report('--trace', str(tp), '--sweep', '1,2,4',
                        '--slo-ms', '100',
                        '--prefill-chunk-s', '0.002',
                        '--decode-burst-s', '0.004')
    assert sweep.returncode == 0, sweep.stdout + sweep.stderr
    out = json.loads(sweep.stdout.splitlines()[-1])
    assert out['sweep']['min_replicas'] is not None
