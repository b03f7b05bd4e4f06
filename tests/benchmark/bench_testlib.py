"""Shared by the benchmark's tests: the benchmark's modules on the path
and the tiny stand-in cells (`tiny/`) the CPU rehearsals run."""
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, 'benchmarks')
TINY = os.path.join(HERE, 'tiny')
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    'perfbench_run', os.path.join(BENCH, 'run.py'))
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

TINY_CONFIG = {'gpt2-large-train-1chip': 'tiny-train',
               'gpt2-xl-serve-1chip': 'tiny-serve'}
TINY_TRAFFIC = {'seq1k-ingest': 'tiny-ingest',
                'offline-decode': 'tiny-offline',
                'prefix-turns': 'tiny-turns'}


def benchmark_json():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        return json.load(f)


def tiny_benchmark():
    """BENCHMARK.json with every cell pointed at its tiny stand-in: the
    same drivers, readers and comparisons at toy widths."""
    bm = copy.deepcopy(benchmark_json())
    for c in bm['configs']:
        c['file'] = os.path.join(TINY, 'configs',
                                 TINY_CONFIG[c['name']] + '.json')
    for w in bm['workloads']:
        w['traffic'] = TINY_TRAFFIC[w['traffic']]
    return bm


def rehearse(workload, seed=7, seconds=1.0, trace=0, benchmark=None):
    result, obs = bench_run.run_cell(
        benchmark or tiny_benchmark(), TINY, workload, seed, seconds, trace,
        require_chip=False)
    return result, obs
