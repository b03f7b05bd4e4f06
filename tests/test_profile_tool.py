"""tools/profile_analysis.py contract tests.

Two tiers:
- a synthetic trace fixture (always runs, hardware-free): exercises
  load_trace / device_ops / aggregate end-to-end on the exact
  trace-viewer JSON shape jax.profiler writes;
- a captured on-TPU profile, when one exists locally (docs/tpu_profile_*
  is what a bench.py run under PADDLE_TPU_BENCH_PROFILE writes; the raw
  blobs are gitignored, so CI machines skip this tier).
"""
import glob
import gzip
import json
import os

import pytest

import tools.profile_analysis as pa

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# first profile dir (newest round first) that holds a trace, else None —
# single source of truth for both the skip condition and the test body
_CAPTURED_DIR = next(
    (os.path.join(_ROOT, 'docs', d)
     for d in ('tpu_profile_r5', 'tpu_profile_r4')
     if glob.glob(os.path.join(_ROOT, 'docs', d, '**', '*.trace.json.gz'),
                  recursive=True)),
    None)


def _synthetic_trace(tmp_path, steps=8, step_us=1000.0):
    """A minimal trace-viewer JSON mirroring jax.profiler's layout: a
    device pid with 'XLA Ops' / 'XLA Modules' lanes plus a host pid that
    must be ignored."""
    dev, host = 7, 3
    events = [
        {'ph': 'M', 'pid': dev, 'name': 'process_name',
         'args': {'name': '/device:TPU:0'}},
        {'ph': 'M', 'pid': dev, 'tid': 1, 'name': 'thread_name',
         'args': {'name': 'XLA Ops'}},
        {'ph': 'M', 'pid': dev, 'tid': 2, 'name': 'thread_name',
         'args': {'name': 'XLA Modules'}},
        {'ph': 'M', 'pid': host, 'name': 'process_name',
         'args': {'name': 'host worker'}},
        {'ph': 'M', 'pid': host, 'tid': 1, 'name': 'thread_name',
         'args': {'name': 'XLA Ops'}},  # host lane: must not be counted
    ]
    for s in range(steps):
        t0 = s * step_us
        events.append({'ph': 'X', 'pid': dev, 'tid': 2, 'ts': t0,
                       'dur': step_us, 'name': 'jit_train_step'})
        # one matmul-ish op (flops-heavy) + one copy (bytes-heavy)
        events.append({'ph': 'X', 'pid': dev, 'tid': 1, 'ts': t0,
                       'dur': 600.0, 'name': 'fusion.1',
                       'args': {'model_flops': 2.4e11,
                                'bytes_accessed': 1e7,
                                'hlo_category': 'convolution fusion',
                                'long_name': '%fusion.1 = bf16[...]'}})
        events.append({'ph': 'X', 'pid': dev, 'tid': 1, 'ts': t0 + 600,
                       'dur': 400.0, 'name': 'copy.2',
                       'args': {'model_flops': 0,
                                'bytes_accessed': 3.2e8,
                                'hlo_category': 'copy',
                                'long_name': '%copy.2 = f32[...]'}})
        # host-lane noise with the same name: ignored by device_ops
        events.append({'ph': 'X', 'pid': host, 'tid': 1, 'ts': t0,
                       'dur': 5000.0, 'name': 'fusion.1', 'args': {}})
    pdir = tmp_path / 'prof' / 'plugins' / 'profile' / 'run1'
    pdir.mkdir(parents=True)
    with gzip.open(str(pdir / 'vm.trace.json.gz'), 'wt') as f:
        json.dump({'traceEvents': events}, f)
    return str(tmp_path / 'prof')


def test_busy_time_interval_union(tmp_path):
    # a while/scan parent op's slice covers its body ops; the busy-time
    # union must count that wall span once, not parent + children
    dev = 7
    events = [
        {'ph': 'M', 'pid': dev, 'name': 'process_name',
         'args': {'name': '/device:TPU:0'}},
        {'ph': 'M', 'pid': dev, 'tid': 1, 'name': 'thread_name',
         'args': {'name': 'XLA Ops'}},
        # parent covering [0, 1000)
        {'ph': 'X', 'pid': dev, 'tid': 1, 'ts': 0.0, 'dur': 1000.0,
         'name': 'while.1', 'args': {}},
        # children nested inside the parent's span
        {'ph': 'X', 'pid': dev, 'tid': 1, 'ts': 0.0, 'dur': 600.0,
         'name': 'fusion.a', 'args': {}},
        {'ph': 'X', 'pid': dev, 'tid': 1, 'ts': 600.0, 'dur': 300.0,
         'name': 'fusion.b', 'args': {}},
        # a disjoint op after an idle gap: [1500, 1700)
        {'ph': 'X', 'pid': dev, 'tid': 1, 'ts': 1500.0, 'dur': 200.0,
         'name': 'copy.z', 'args': {}},
    ]
    ops, _ = pa.device_ops({'traceEvents': events})
    assert sum(e['dur'] for e in ops) == pytest.approx(2100.0)  # naive
    assert pa.busy_us(ops) == pytest.approx(1200.0)             # union


def test_synthetic_trace_roundtrip(tmp_path):
    pdir = _synthetic_trace(tmp_path)
    trace, path = pa.load_trace(pdir)
    assert path.endswith('.trace.json.gz')
    ops, n_modules = pa.device_ops(trace)
    # 8 steps x 2 device ops; the 8 host events must be excluded
    assert len(ops) == 16
    assert n_modules == 8
    rows = pa.aggregate(ops)
    assert set(rows) == {'fusion.1', 'copy.2'}
    f = rows['fusion.1']
    assert f['n'] == 8 and f['dur_us'] == pytest.approx(4800.0)
    assert f['flops'] == pytest.approx(2.4e11)
    assert f['cat'] == 'convolution fusion'
    c = rows['copy.2']
    assert c['bytes'] == pytest.approx(3.2e8)
    # per-step totals: (600+400) us
    steps = 8
    tot_ms = sum(r['dur_us'] for r in rows.values()) / 1e3 / steps
    assert tot_ms == pytest.approx(1.0)


@pytest.mark.skipif(_CAPTURED_DIR is None,
                    reason='no locally captured profile (raw blobs are '
                           'gitignored; a profiled bench run writes them)')
def test_parses_captured_profile():
    trace, _ = pa.load_trace(_CAPTURED_DIR)
    ops, _ = pa.device_ops(trace)
    assert ops, 'no device ops found'
    rows = pa.aggregate(ops)
    import collections
    steps = collections.Counter(r['n'] for r in rows.values()).most_common(
        1)[0][0]
    # a profiled run covers multiple steps: step inference must detect the
    # repetition, not collapse to 1 (which would inflate every per-step
    # total this tool reports)
    assert steps >= 2
    tot_ms = sum(r['dur_us'] for r in rows.values()) / 1e3 / steps
    assert tot_ms > 10, tot_ms
    tot_bytes = sum(r['bytes'] * r['n'] for r in rows.values()) / steps
    # a real BERT-base training step moves tens of GB
    assert tot_bytes > 1e10
