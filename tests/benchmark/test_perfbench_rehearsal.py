"""CPU rehearsal of `benchmarks/run.py`: every cell at a tiny width
through the same drivers, readers and comparisons, and the shape of the
result; a run without a chip at the real size exits non-zero."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_testlib import BENCH, REPO, benchmark_json, rehearse

CELLS = [w['name'] for w in benchmark_json()['workloads']]
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
DEVICE_ONLY = {'device_trace'}


@pytest.mark.parametrize('trace', (0, 1))
@pytest.mark.parametrize('cell', CELLS)
def test_cell_rehearses(cell, trace):
    bm = benchmark_json()
    result, _ = rehearse(cell, seed=2 ** 31 + 17, trace=trace)
    assert list(result)[:5] == ['correct', 'attempted', 'failed', 'metrics',
                                'device']
    assert list(result)[-1] == 'compared'
    assert result['correct'] is True, result['compared']
    assert result['attempted'] > 0 and result['failed'] == 0
    assert set(result['device']) >= {'platform', 'kind', 'count',
                                     'memory_peak_bytes'}
    for name, c in result['compared'].items():
        assert set(c) >= {'value', 'limit'}
    listed = bm['per_layer'] if trace else bm['end_to_end']
    want = {m['name'] for m in listed
            if cell in m.get('workloads', [cell])
            and m['source'] not in (DEVICE_ONLY if trace else ())}
    assert set(result['metrics']) == want
    for name, v in result['metrics'].items():
        assert isinstance(v['value'], float) and v['unit']
    if not trace:
        assert 'setup_s' in result['metrics']
        assert len(result['metrics']) >= 2
    json.dumps(result)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         CELLS[0], '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith('{') for line in p.stdout.splitlines())
    assert 'TPU' in p.stderr


def test_benchmark_json_is_driven_by_files():
    bm = benchmark_json()
    assert set(bm) == {'command', 'paths', 'run_seconds', 'configs',
                       'workloads', 'end_to_end', 'per_layer'}
    e2e = {m['name'] for m in bm['end_to_end']}
    assert 'setup_s' in e2e
    cells = {w['name'] for w in bm['workloads']}
    for c in bm['configs']:
        conf = json.load(open(os.path.join(REPO, c['file'])))
        assert conf['source'] == c['source'] and conf['reduced'] == c[
            'reduced'] and 'assumed' in conf and 'correct' in conf
    for w in bm['workloads']:
        assert os.path.exists(os.path.join(BENCH, 'traffic',
                                           w['traffic'] + '.json'))
        assert w['chips'] in (1, 4) and len(w['why']) <= 200
    layers = set()
    for m in bm['per_layer']:
        assert NAME.match(m['name']) and m['moves'] in e2e
        assert os.path.exists(os.path.join(BENCH, 'metrics',
                                           m['name'] + '.py')), m['name']
        assert set(m.get('workloads', cells)) <= cells
        layers.add(m['layer'])
    for m in bm['end_to_end']:
        assert 0 < m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
    # each cell reports a whole-step share of peak beside its rooflines
    for cell in cells:
        names = [m['name'] for m in bm['per_layer']
                 if cell in m.get('workloads', [cell])]
        assert any('mfu' in n for n in names), cell
        assert any(n.endswith('_roofline') for n in names), cell
        assert any(n.endswith('idle_share') for n in names), cell


def test_offline_rate_runs_over_whole_engine_steps():
    """A burst delivers its tokens together: the rate is taken from the
    start of the window's first step to the end of the one that crosses
    its close, never cut at a fixed instant."""
    result, obs = rehearse('serve-xl.offline-decode', seed=5)
    first, last = obs['steps'][0], obs['steps'][-1]
    assert obs['t0'] <= first[0] and last[0] < obs['t_end']
    assert last[1] > obs['t_end'] - 0.05        # the crossing step is in
    assert obs['span_s'] == last[1] - first[0]
    stamps = [t for r in obs['recs'] if r.stamps for t in r.stamps.t]
    inside = sum(first[0] <= t <= last[1] for t in stamps)
    assert inside == obs['tokens_in_window'] > 0
    assert result['metrics']['serve_tokens_per_s']['value'] == \
        pytest.approx(inside / obs['span_s'])
