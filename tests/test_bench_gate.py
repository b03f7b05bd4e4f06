"""Perf-regression gate tests (tools/check_bench_regression.py).

The gate's contract, proven with a deliberate-regression fixture: a new
capture of the SAME effective config that is >10% worse than the stored
best must fail the check, and a capture at (or near) the stored best
must pass. Also exercised against the repo's real in-window logs:
self-comparison is by construction regression-free.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', 'tools'))

import bench
import check_bench_regression as gate

_REPO = os.path.join(os.path.dirname(__file__), '..')


def _row(value, metric='train_tokens_per_sec', **over):
    row = {'metric': metric, 'value': value, 'unit': 'tokens/sec',
           'platform': 'tpu', 'label': over.pop('label', 'fixture'),
           'batch': 8, 'seq': 512, 'scan_steps': 2, 'fused_ce': True,
           'attn_impl': 'flash', 'qkv_split': False}
    row.update(over)
    return row


def test_fails_on_deliberate_regression():
    best = [_row(1000.0, label='stored_best')]
    regressed = [_row(850.0, label='regressed')]       # -15% > 10% bar
    findings = gate.check(regressed, best)
    assert len(findings) == 1
    f = findings[0]
    assert f['direction'] == 'down'
    assert f['ratio'] == pytest.approx(0.85)
    assert f['stored_best'] == 1000.0 and f['new_best'] == 850.0


def test_passes_on_stored_best_and_within_threshold():
    best = [_row(1000.0)]
    assert gate.check(best, best) == []                # identical capture
    assert gate.check([_row(920.0)], best) == []       # -8% inside bar
    assert gate.check([_row(1100.0)], best) == []      # improvement


def test_effective_config_matching_not_literal():
    """A legacy row that omits knob fields and a new row spelling out the
    same defaults are ONE config: the key goes through bench's
    _capture_replay_env + _effective_env canonicalization."""
    legacy = {'metric': 'train_tokens_per_sec', 'value': 1000.0,
              'unit': 'tokens/sec', 'platform': 'tpu', 'batch': 8,
              'seq': 512}
    same = dict(legacy, value=800.0)
    assert gate.config_key(legacy) == gate.config_key(same)
    assert len(gate.check([same], [legacy])) == 1      # -20% caught
    # a DIFFERENT config (other seq) never compares against this best
    other = dict(legacy, value=100.0, seq=1024)
    assert gate.config_key(other) != gate.config_key(legacy)
    assert gate.check([other], [legacy]) == []


def test_untrusted_rows_are_ignored():
    best = [_row(1000.0)]
    for bad in (_row(10.0, degraded=True),
                _row(10.0, suspect=True),
                _row(10.0, platform='cpu'),
                _row(10.0, error='oom'),
                _row('nan')):
        assert not gate.eligible(bad)
        assert gate.check([bad], best) == []
    # and an untrusted stored row can't masquerade as the best
    assert gate.check([_row(500.0)],
                      [_row(10000.0, suspect=True), _row(520.0)]) == []


def test_latency_metrics_regress_upward():
    best = [_row(12.0, metric='decode_step_latency', unit='ms')]
    assert not gate.higher_is_better(best[0])
    assert gate.check([_row(14.0, metric='decode_step_latency',
                            unit='ms')], best)         # +17% slower
    assert gate.check([_row(11.0, metric='decode_step_latency',
                            unit='ms')], best) == []   # faster is fine


def test_compile_seconds_gate_as_derived_rows():
    """A row carrying compile_s_cold/compile_s_warm spawns pseudo-rows
    ('<metric>_compile_s_cold', unit 's') that regress UPWARD, without
    bucket-splitting the carrier row's own config."""
    best = [_row(1000.0, compile_s_cold=8.0, compile_s_warm=0.5)]
    derived = gate.expand_derived(best)
    metrics = sorted(r['metric'] for r in derived)
    assert 'train_tokens_per_sec_compile_s_cold' in metrics
    assert 'train_tokens_per_sec_compile_s_warm' in metrics
    cold = next(r for r in derived
                if r['metric'].endswith('_compile_s_cold'))
    assert cold['value'] == 8.0 and cold['unit'] == 's'
    assert not gate.higher_is_better(cold)             # time regresses UP
    # same throughput, 50% slower cold compile -> exactly one finding,
    # and it is the derived compile row, not the carrier
    slow = [_row(1000.0, compile_s_cold=12.0, compile_s_warm=0.5)]
    findings = gate.check(slow, best)
    assert len(findings) == 1
    assert findings[0]['metric'] == 'train_tokens_per_sec_compile_s_cold'
    assert findings[0]['direction'] == 'up'
    # faster compiles and mfu_est passengers never trip the gate
    fast = [_row(1000.0, compile_s_cold=4.0, compile_s_warm=0.4,
                 mfu_est=0.31, roofline_bound='compute')]
    assert gate.check(fast, best) == []


def test_cache_hit_rate_gates_as_higher_is_better():
    """compile_cache_hit_rate contains 'compile' but must NOT inherit
    the compile-time direction: a warmed persistent cache losing its
    hits is a downward regression, like throughput."""
    best = [_row(1000.0, compile_s_cold=8.0, compile_cache_hit_rate=0.9)]
    derived = gate.expand_derived(best)
    hr = next(r for r in derived if r['metric'].endswith('_hit_rate'))
    assert hr['unit'] == 'ratio' and hr['value'] == 0.9
    assert gate.higher_is_better(hr)
    dropped = [_row(1000.0, compile_s_cold=8.0,
                    compile_cache_hit_rate=0.5)]
    findings = gate.check(dropped, best)
    assert len(findings) == 1
    assert findings[0]['metric'] == \
        'train_tokens_per_sec_compile_cache_hit_rate'
    assert findings[0]['direction'] == 'down'
    improved = [_row(1000.0, compile_s_cold=8.0,
                     compile_cache_hit_rate=0.95)]
    assert gate.check(improved, best) == []


def test_supervisor_mttr_gates_lower_is_better():
    """supervisor_mttr_seconds (bench_extra's elastic-recovery rung)
    regresses UP: a supervisor that takes longer to bring a killed
    shard back is a worse supervisor, regardless of the generic
    throughput default."""
    row = {'metric': 'supervisor_mttr_seconds', 'unit': 's',
           'value': 0.08}
    assert not gate.higher_is_better(row)
    best = [dict(row, platform='tpu', degraded=False)]
    slower = [dict(row, value=0.5, platform='tpu', degraded=False)]
    findings = gate.check(slower, best)
    assert len(findings) == 1 and findings[0]['direction'] == 'up'
    faster = [dict(row, value=0.02, platform='tpu', degraded=False)]
    assert gate.check(faster, best) == []


def test_capacity_divergence_gates_lower_is_better():
    """bench_capacity_calibration's rows regress UP: a simulator whose
    TTFT distribution drifts further from the measured gateway
    (capacity_sim_ttft_divergence, rel_err) is a worse simulator, and a
    sweep that suddenly needs more replicas for the same pinned service
    model (capacity_sweep_min_replicas) is a capacity regression."""
    div = {'metric': 'capacity_sim_ttft_divergence', 'unit': 'rel_err',
           'value': 0.3}
    assert not gate.higher_is_better(div)
    best = [dict(div, platform='tpu', degraded=False)]
    worse = [dict(div, value=0.6, platform='tpu', degraded=False)]
    findings = gate.check(worse, best)
    assert len(findings) == 1 and findings[0]['direction'] == 'up'
    better = [dict(div, value=0.1, platform='tpu', degraded=False)]
    assert gate.check(better, best) == []

    rep = {'metric': 'capacity_sweep_min_replicas', 'unit': 'replicas',
           'value': 16}
    assert not gate.higher_is_better(rep)
    best = [dict(rep, platform='tpu', degraded=False)]
    more = [dict(rep, value=32, platform='tpu', degraded=False)]
    findings = gate.check(more, best)
    assert len(findings) == 1 and findings[0]['direction'] == 'up'
    fewer = [dict(rep, value=8, platform='tpu', degraded=False)]
    assert gate.check(fewer, best) == []


def test_trust_degraded_admits_cpu_rows():
    """The compile-cache rungs are measured on CPU: invisible to the
    default gate (they must never displace real-TPU bests), gated
    against their own baseline under --trust-degraded. Suspect and
    errored rows stay out even when trusted."""
    cpu_best = [_row(100.0, platform='cpu', degraded=True)]
    cpu_new = [_row(80.0, platform='cpu', degraded=True)]
    assert not gate.eligible(cpu_new[0])
    assert gate.check(cpu_new, cpu_best) == []
    findings = gate.check(cpu_new, cpu_best, trust_degraded=True)
    assert len(findings) == 1 and findings[0]['direction'] == 'down'
    assert not gate.eligible(_row(10.0, suspect=True), trust_degraded=True)
    assert not gate.eligible(_row(10.0, error='x'), trust_degraded=True)


def test_cli_trust_degraded_flag(tmp_path):
    best_p = tmp_path / 'best.jsonl'
    new_p = tmp_path / 'new.jsonl'
    best_p.write_text(json.dumps(_row(100.0, platform='cpu')) + '\n')
    new_p.write_text(json.dumps(_row(50.0, platform='cpu')) + '\n')
    script = os.path.join(_REPO, 'tools', 'check_bench_regression.py')
    base = [sys.executable, script, '--new', str(new_p),
            '--baseline', str(best_p)]
    # default: CPU rows are ineligible on both sides -> no findings
    assert subprocess.run(base, capture_output=True,
                          cwd=_REPO).returncode == 0
    # trusted: the -50% regression is caught
    r = subprocess.run(base + ['--trust-degraded'], capture_output=True,
                       text=True, cwd=_REPO)
    assert r.returncode == 1, r.stderr
    assert json.loads(r.stdout.strip().splitlines()[0])['regression']


def test_aux_workload_fields_split_configs():
    """Serving-rung rows at different slot counts are different configs
    even though their knob env is identical."""
    b8 = _row(300.0, metric='serving_tokens_per_sec', num_slots=8)
    b32 = _row(900.0, metric='serving_tokens_per_sec', num_slots=32)
    new8 = _row(280.0, metric='serving_tokens_per_sec', num_slots=8)
    assert gate.config_key(b8) != gate.config_key(b32)
    assert gate.check([new8], [b8, b32]) == []         # -7%: ok vs its own


def test_cli_exit_codes(tmp_path):
    best_p = tmp_path / 'best.jsonl'
    new_ok = tmp_path / 'ok.jsonl'
    new_bad = tmp_path / 'bad.jsonl'
    best_p.write_text(json.dumps(_row(1000.0)) + '\n')
    new_ok.write_text(json.dumps(_row(990.0)) + '\n')
    new_bad.write_text(json.dumps(_row(500.0)) + '\n')
    script = os.path.join(_REPO, 'tools', 'check_bench_regression.py')

    def run(new):
        return subprocess.run(
            [sys.executable, script, '--new', str(new),
             '--baseline', str(best_p)],
            capture_output=True, text=True, cwd=_REPO)

    ok = run(new_ok)
    assert ok.returncode == 0, ok.stderr
    assert json.loads(ok.stdout.strip().splitlines()[-1])['ok'] is True
    bad = run(new_bad)
    assert bad.returncode == 1, bad.stderr
    finding = json.loads(bad.stdout.strip().splitlines()[0])
    assert finding['regression'] and finding['ratio'] == pytest.approx(0.5)
    empty = tmp_path / 'empty.jsonl'
    empty.write_text('')
    assert run(empty).returncode == 2                  # nothing to check


def test_repo_cache_rows_pin_cold_start_win():
    """The committed CPU cache demonstration (docs/bench_cache_cpu.jsonl,
    measured in a cold process with the compile cache at a fresh dir,
    then again at the warmed dir):
    the warm run compiles >=3x faster at full persistent-cache hit rate
    on both measured configs, the rows are invisible to the default
    (TPU-only) gate, and the file self-gates under --trust-degraded."""
    path = os.path.join(_REPO, 'docs', 'bench_cache_cpu.jsonl')
    rows = gate._load_jsonl(path)
    assert rows, 'missing committed cache bench rows'
    assert all(gate.eligible(r, trust_degraded=True) for r in rows)
    assert not any(gate.eligible(r) for r in rows)
    by_label = {r['label']: r for r in rows}
    for cfg in ('plain', 'scan2'):
        cold = by_label['cache_cold_%s' % cfg]
        warm = by_label['cache_warm_%s' % cfg]
        assert warm['compile_cache_hit_rate'] > 0
        assert warm['recompiles'] == 0
        assert cold['compile_s_cold'] >= 3 * warm['compile_s_cold']
    assert gate.check(rows, rows, trust_degraded=True) == []


def test_ingest_metric_directions():
    """The ingest rung's two gated metrics regress in opposite
    directions: examples/s down, data_wait_frac up."""
    eps = _row(50000.0, metric='ingest_examples_per_sec',
               unit='examples/sec')
    frac = _row(0.05, metric='ingest_data_wait_frac', unit='ratio')
    assert gate.higher_is_better(eps)
    assert not gate.higher_is_better(frac)
    slower = [_row(30000.0, metric='ingest_examples_per_sec',
                   unit='examples/sec')]
    assert gate.check(slower, [eps])           # -40% throughput fails
    starved = [_row(0.2, metric='ingest_data_wait_frac', unit='ratio')]
    assert gate.check(starved, [frac])         # 4x more waiting fails
    better = [_row(0.04, metric='ingest_data_wait_frac', unit='ratio')]
    assert gate.check(better, [frac]) == []    # less waiting passes


def test_repo_ingest_rows_pin_async_win():
    """The committed CPU ingest capture (docs/bench_ingest_cpu.jsonl,
    measured by bench_extra.bench_ingest against a synchronous
    random-access DataLoader over the same disk-resident shards): the
    async pipeline holds >=2x throughput with near-zero data_wait, the
    rows are invisible to the default (TPU-only) gate, and the file
    self-gates under --trust-degraded."""
    path = os.path.join(_REPO, 'docs', 'bench_ingest_cpu.jsonl')
    rows = gate._load_jsonl(path)
    assert rows, 'missing committed ingest bench rows'
    assert all(gate.eligible(r, trust_degraded=True) for r in rows)
    assert not any(gate.eligible(r) for r in rows)
    by_metric = {r['metric']: r for r in rows}
    eps = by_metric['ingest_examples_per_sec']
    frac = by_metric['ingest_data_wait_frac']
    assert eps['speedup_vs_dataloader'] >= 2.0
    assert eps['speedup_vs_pipeline_sync'] > 1.0
    assert frac['value'] <= 0.15               # near-zero async data_wait
    assert frac['value'] < frac['pipeline_sync_data_wait_frac']
    assert frac['value'] < frac['dataloader_data_wait_frac']
    # the frac also rides the throughput row for perf_report's table
    assert eps['data_wait_frac'] == frac['value']
    assert gate.check(rows, rows, trust_degraded=True) == []


def test_repo_stored_best_passes_gate():
    """In-suite rung: the stored in-window logs, replayed as a 'new'
    capture against themselves, must pass — if this fails the stored
    best itself is internally inconsistent."""
    paths = [p for p in bench._inwindow_log_paths() if os.path.exists(p)]
    if not paths:
        pytest.skip('no stored in-window capture logs in repo')
    rows = []
    for p in paths:
        rows.extend(gate._load_jsonl(p))
    assert any(gate.eligible(r) for r in rows)
    assert gate.check(rows, rows) == []
