"""The four readers of the program's compile log: appended after every
entry the benchmark had, with the keys `compile_s` has and the six cells
of their PR listed; a float in a serving and a training rehearsal, inside what
`compile_s` sums; None with the tracer off or on a program that keeps
no log."""
import time

import pytest

from bench_testlib import bench_run, benchmark_json, rehearse
from paddle_tpu.monitor import tracing
from paddle_tpu.monitor.perf import watchdog

NEW = ('compile_trace_lower_s', 'compile_backend_s',
       'compile_step_programs_s', 'compile_programs_built')
# the cells there were when the four came: each reads in all of them
CELLS = {'train-large.seq1k-ingest', 'serve-xl.offline-decode',
         'serve-xl.prefix-turns', 'serve-olmo-hybrid.long-docs',
         'serve-kimi-linear.long-answers', 'serve-trinity.window-docs'}


def test_the_four_entries_are_appended_with_compile_s_keys():
    """And a list of cells that holds the six: a metric with none would
    read in a stand-in cell too, whose metrics a test holds to a fixed
    set. A cell added later may be appended to the lists."""
    bm = benchmark_json()
    per_layer = bm['per_layer']
    names = [m['name'] for m in per_layer]
    first = names.index(NEW[0])
    assert tuple(names[first:first + len(NEW)]) == NEW
    assert names.index('answers_expert_rows_per_pair') == first - 1
    base = next(m for m in per_layer if m['name'] == 'compile_s')
    for m in per_layer[first:first + len(NEW)]:
        assert set(m) == set(base) | {'workloads'}
        assert CELLS <= set(m['workloads'])
        assert (m['layer'], m['moves'], m['better'], m['source']) == \
            (base['layer'], 'setup_s', 'lower', 'program_counter')
        assert m['unit'] == ('count' if m['name'] == NEW[-1] else 's')


@pytest.mark.parametrize('cell', ['serve-xl.offline-decode',
                                  'train-large.seq1k-ingest'])
def test_each_reader_gives_a_float_inside_compile_s(cell):
    result, obs = rehearse(cell, seed=2 ** 31 + 37, trace=1)
    got = {n: result['metrics'][n]['value'] for n in NEW}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values())
    assert got['compile_programs_built'] >= 1.0
    built = got['compile_trace_lower_s'] + got['compile_backend_s']
    assert 0.0 < got['compile_step_programs_s'] <= built
    assert built <= result['diag']['compile_s'] + 1e-9
    # the readers took the same window the run's own did
    for n in NEW:
        assert bench_run.load_reader(n)(obs) == got[n]


@pytest.mark.parametrize('name', NEW)
@pytest.mark.parametrize('kind', ['serve', 'train'])
def test_nothing_to_read_gives_none(name, kind, monkeypatch):
    read = bench_run.load_reader(name)
    obs = {'kind': kind, 't0': time.monotonic(), 'steps_done': 1}
    tracer = tracing.default_tracer()
    tracer.disable()
    try:
        assert read(obs) is None                    # the tracer off
    finally:
        tracer.enable()
    no_start = {'serve': {'t0': None}, 'train': {'steps_done': 0}}[kind]
    assert read(dict(obs, **no_start)) is None      # no window's start
    monkeypatch.delattr(watchdog, 'compile_log')    # a parent's program
    assert read(obs) is None
