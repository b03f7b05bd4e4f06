"""The readers of the program's own spans (`benchlib/program_spans.py`):
a float from every one in the CPU rehearsal of its cell, None with the
tracer off, and no span of an earlier run of the same process counted."""
import pytest

from bench_testlib import benchmark_json, bench_run, rehearse
from benchlib import program_spans as P
from paddle_tpu.monitor import tracing

NEW = ('turns_step_prefill_ms_p50', 'turns_step_self_ms_p50',
       'turns_prefill_calls_per_step', 'turns_admit_to_first_token_ms_p90',
       'turns_admit_blocked_share', 'turns_prefill_idle_share',
       'offline_step_prefill_ms_p50', 'offline_step_self_ms_p50',
       'offline_blocked_on_pages_share', 'offline_burst_idle_share',
       'train_dispatch_ms_p50')
ENTRIES = {m['name']: m for m in benchmark_json()['per_layer']
           if m['name'] in NEW}
TURNS, OFFLINE = 'serve-xl.prefix-turns', 'serve-xl.offline-decode'


@pytest.fixture(scope='module')
def runs():
    """One traced rehearsal per cell, in the order a worker would make
    them, and a second one of the open-loop cell after the rest."""
    assert tracing.default_tracer().enabled
    cells = [w['name'] for w in benchmark_json()['workloads']]
    out = {cell: rehearse(cell, seed=2 ** 31 + 29, trace=1)
           for cell in cells}
    out['again'] = rehearse(TURNS, seed=2 ** 31 + 31, trace=1)
    return out


def test_entries_are_appended_and_name_one_cell():
    assert set(ENTRIES) == set(NEW)
    names = [m['name'] for m in benchmark_json()['per_layer']]
    assert names[-len(NEW):] == list(NEW)
    for m in ENTRIES.values():
        assert len(m['workloads']) == 1
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}


@pytest.mark.parametrize('name', NEW)
def test_reader_gives_a_float_in_its_cell_and_none_elsewhere(runs, name):
    entry = ENTRIES[name]
    read = bench_run.load_reader(name)
    result, obs = runs[entry['workloads'][0]]
    value = read(obs)
    if entry['source'] == 'device_trace':
        # joined with the device's runs: nothing to join on the CPU
        assert obs['reduced'] is None and value is None
        assert name not in result['metrics']
    else:
        assert isinstance(value, float) and value >= 0.0
        assert result['metrics'][name] == {'value': value,
                                           'unit': entry['unit']}
    # the tracer off: nothing to read, and no reader raises
    tracer = tracing.default_tracer()
    tracer.disable()
    try:
        assert read(obs) is None
    finally:
        tracer.enable()
    # another cell's run has no such spans in its window
    for cell, (_, other) in runs.items():
        if cell not in (entry['workloads'][0], 'again') and \
                other['kind'] != obs['kind']:
            assert read(other) is None


def test_windows_keep_the_runs_of_one_process_apart(runs):
    for key in (TURNS, OFFLINE, 'again'):
        _, obs = runs[key]
        steps = P.window_spans(obs, P.STEP)
        # the program's steps are the benchmark's: one span a step, each
        # inside the step the harness timed from outside
        assert len(steps) == len(obs['steps']) > 0
        for span, (ts, te, *_) in zip(steps, obs['steps']):
            assert ts <= span['start_mono'] <= span['end_mono'] <= te
        program = sum(P.seconds(s) for s in steps)
        outside = sum(te - ts for ts, te, *_ in obs['steps'])
        assert 0.5 * outside < program <= outside
    first, again = runs[TURNS][1], runs['again'][1]
    assert first['t_end'] < again['t0']
    ids = lambda obs: {s['span_id'] for s in P.window_spans(obs, P.STEP)}
    assert not ids(first) & ids(again)
    # a request is counted where it was admitted, once
    got = [s['span_id'] for obs in (first, again)
           for s, _ in P._admitted(obs)]
    assert len(got) == len(set(got)) > 0


def test_training_takes_the_windows_own_calls(runs):
    _, obs = runs['train-large.seq1k-ingest']
    got = P.ring()[0]
    mine = [s for s in got if s['name'] == P.TRAIN_STEP]
    n = obs['steps_done']
    # set-up's steps (compared and warm-up) and the window's first call
    # come before the n the reader takes
    assert len(mine) > n
    idx = [s['tags']['step'] for s in mine[-n:]]
    assert idx == list(range(idx[0], idx[0] + n))
    assert P.train_dispatch_ms_p50(obs) == pytest.approx(
        bench_run.load_reader('train_dispatch_ms_p50')(obs))
    assert P.train_dispatch_ms_p50(dict(obs, steps_done=10 ** 6)) is None


def test_a_wrapped_ring_counts_only_where_it_cut_the_window(runs):
    _, obs = runs['again']
    recorder = tracing.default_tracer().recorder
    assert P.window_spans(obs, P.STEP)
    spans = recorder.spans()
    before = recorder._dropped
    try:
        recorder._dropped = before + 5
        # the oldest span left is older than the window: still whole
        if spans[0].get('end_mono', obs['t0']) < obs['t0']:
            assert P.window_spans(obs, P.STEP)
        # ... and younger than its start: spans of the window are gone
        late = dict(obs, t0=spans[0]['start_mono'] - 1.0)
        assert P.window_spans(late, P.STEP) is None
        assert P.step_self_ms_p50(late) is None
    finally:
        recorder._dropped = before


def test_a_program_without_the_spans_reads_nothing(runs):
    """The parent commit's spans carry no monotonic stamp: every reader
    finds nothing and none raises."""
    _, obs = runs[TURNS]
    recorder = tracing.default_tracer().recorder
    kept = recorder.spans()
    recorder.clear()
    try:
        for s in kept:
            old = {k: v for k, v in s.items()
                   if k not in ('start_mono', 'end_mono')}
            recorder.record(old)
        for name in NEW:
            for _, o in runs.values():
                assert bench_run.load_reader(name)(o) is None
    finally:
        recorder.clear()
        for s in kept:
            recorder.record(s)


# ---- tools/span_gaps.py -----------------------------------------------------

def _span_gaps():
    import importlib.util
    import os
    from bench_testlib import BENCH
    spec = importlib.util.spec_from_file_location(
        'perfbench_span_gaps', os.path.join(BENCH, 'tools', 'span_gaps.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_idle_gaps_land_on_the_innermost_program_span():
    G = _span_gaps()
    ms = 1000000
    trace = {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Ops', 'events': [
                ['%fusion.1 = f32[] fusion()', 10 * ms, 20 * ms],
                ['%fusion.1 = f32[] fusion()', 40 * ms, 10 * ms],
                ['%copy.2 = f32[] copy()', 60 * ms, 30 * ms]]}]},
        {'name': '/host:CPU', 'lines': [{'name': 'python', 'events': [
            ['bench.window', 0, 100 * ms],
            ['bench.engine_step', 5 * ms, 90 * ms],
            ['serving.step', 6 * ms, 88 * ms],
            ['serving.step.prefill', 8 * ms, 45 * ms],
            ['serving.prefill_call', 8 * ms, 24 * ms],
            ['serving.prefill_call', 38 * ms, 14 * ms],
            ['serving.decode_burst', 58 * ms, 33 * ms],
            ['$python_frame', 0, 100 * ms]]}]}]}
    got = G.program_gaps(trace)
    assert got['window_s'] == pytest.approx(0.1)
    assert got['idle_s'] == pytest.approx(0.04)
    gaps = {k: round(1e3 * v, 3) for k, v in got['gaps'].items()}
    # 0-10 and 90-100 outside every span; 30-40 between the two calls:
    # the prefill phase's own; 50-60 after it: the step's
    assert gaps == {G.NO_SPAN: 20.0, 'serving.step.prefill': 10.0,
                    'serving.step': 10.0}
    assert got['on_program_spans_share'] == pytest.approx(50.0)
    assert G.host_span_names(trace) == {
        'serving.step': 1, 'serving.step.prefill': 1,
        'serving.prefill_call': 2, 'serving.decode_burst': 1}


def test_scope_is_the_innermost_name_of_the_closed_set():
    G = _span_gaps()
    assert G.scope_of('jit(_decode_fn)/jit(main)/while/body/'
                      'gpt.attn.paged_gather/gather') == \
        'gpt.attn.paged_gather'
    assert G.scope_of('jit(pure_step)/transpose(jvp(gpt.attn.core))/'
                      'flash.bwd/pallas_call') == 'flash.bwd'
    assert G.scope_of('jit(f)/vmap(serving.pick_token)/sort') == \
        'serving.pick_token'
    assert G.scope_of('jit(pure_step)/optimizer.adamw/mul') == \
        'optimizer.adamw'
    assert G.scope_of('jit(f)/jit(main)/add') is None
    assert G.scope_of('paddle_tpu/text/models/gpt.py:342') is None
    assert G.scope_of('gpt.wte.weight') is None
    assert G.scope_of(None) is None


def test_a_traced_run_holds_the_programs_spans_in_the_host_plane():
    from bench_testlib import TINY, tiny_benchmark
    G = _span_gaps()
    rep = G.report(tiny_benchmark(), TINY, TURNS, 2 ** 31 + 37, 1.0,
                   require_chip=False)
    # the CPU has no device plane: nothing to put gaps on, no scopes
    assert rep['program_gaps'] is None and rep['scopes'] is None
    assert set(rep['host_plane_spans']) >= {
        'serving.step', 'serving.step.admit', 'serving.step.prefill',
        'serving.prefill_call', 'serving.decode_burst'}
    steps = rep['steps']
    assert steps['ring']['spans'] > 0
    assert steps['serving.step_ms_mean'] <= steps['outside_step_ms_mean']
    parts = steps['parts_ms_mean']
    assert sum(parts[k] for k in ('serving.step.admit',
                                  'serving.step.prefill',
                                  'serving.decode_burst', 'self')) == \
        pytest.approx(steps['serving.step_ms_mean'])
    rep = G.report(tiny_benchmark(), TINY, 'train-large.seq1k-ingest', 3,
                   1.0, require_chip=False)
    assert rep['host_plane_spans']['train.step'] > 0
    assert 0 < rep['steps']['train.step_ms_mean'] < \
        rep['steps']['outside_step_ms_mean']
