"""The Olmo-Hybrid family behind the benchmark: its cell is `correct`
on the CPU at a tiny size from files alone, the control and each planted
fault come out as not correct there, its counts at the published widths
are the numbers a hand computes, and the replay that sized its backlog
(`tools/replay.py`) is the engine's own schedule."""
import importlib.util
import json
import os

import numpy as np
import pytest

from bench_testlib import BENCH, TINY, bench_run, benchmark_json, rehearse
from benchlib import peaks, reference, serve
from benchlib import traffic as T

CELL = 'serve-olmo-hybrid.long-docs'
CONFIG = 'olmo-hybrid-7b-serve-1chip'


def load(path):
    with open(path) as f:
        return json.load(f)


def tool(name):
    spec = importlib.util.spec_from_file_location(
        'perfbench_tool_' + name, os.path.join(BENCH, 'tools', name + '.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def published():
    cfg = load(os.path.join(BENCH, 'configs', CONFIG + '.json'))
    return cfg, bench_run.load_family(cfg)


@pytest.fixture(scope='module')
def tiny_run():
    return rehearse(CELL, seed=2 ** 31 + 23)


def test_the_cell_is_correct_at_a_tiny_size_from_files_alone(tiny_run):
    result, obs = tiny_run
    assert obs['family'].__file__ == os.path.join(BENCH, 'families',
                                                  'olmo_hybrid.py')
    assert result['correct'] is True, result['compared']
    c = result['compared']
    assert c['logit_gap_max']['value'] <= c['logit_gap_max']['limit']
    assert c['tokens_compared']['value'] >= 20
    assert c['bad_answers'] == {'value': 0, 'limit': 0}
    assert result['attempted'] > 0 and result['failed'] == 0
    # the backlog outlasts the window, and prompts cross several chunks
    assert any(r.req is None for r in obs['recs'])
    chunk = obs['engine']['prefill_chunk']
    sample = [len(obs['trace_obj'].prompts[r.idx]) for r in obs['recs'][:16]]
    assert max(sample) > 3 * chunk and min(sample) % chunk
    assert set(result['metrics']) == {'serve_tokens_per_s', 'setup_s'}


@pytest.mark.parametrize('fault', ['state_not_carried', 'padded_tail',
                                   'beta_half'])
def test_each_planted_fault_reads_above_the_limit(fault):
    with tool('faults').planted(fault):
        result, _ = rehearse(CELL, seed=2 ** 31 + 23)
    c = result['compared']['logit_gap_max']
    assert result['correct'] is False and c['value'] > 10 * c['limit'], c
    # ... and nothing stays planted behind it
    result, _ = rehearse(CELL, seed=2 ** 31 + 23)
    assert result['correct'] is True


@pytest.mark.parametrize('seed', (3, 2 ** 31 + 4))
def test_the_control_reads_above_the_limit(seed):
    """float8 in the projections' place, at the tiny configuration: far
    above the limit (bf16, the step below the float32 stated there,
    flips too few of these positions to read anything on some seeds)."""
    cfg = load(os.path.join(TINY, 'configs', 'tiny-olmo-hybrid.json'))
    m, F = cfg['model'], bench_run.load_family(cfg)
    rng = T.stream(seed, 'control')
    draw = lambda n: [int(t) for t in rng.integers(0, 500, n)]
    seqs = [(draw(40), draw(80)) for _ in range(4)]
    with reference.highest():
        stacked = F.make_stacked(m, seed, 'float32')
        gaps, cgaps = F.served_gaps(stacked, m, seqs, 'fp8')
    assert [len(g) for g in cgaps] == [80] * 4 == [len(g) for g in gaps]
    fp8 = max(float(g.max()) for g in cgaps)
    limit = cfg['correct']['logit_gap_max']
    assert fp8 > 3 * limit
    assert not serve.is_correct(
        {'logit_gap_max': {'value': fp8, 'limit': limit}})


def test_counts_at_the_published_widths_are_the_hand_written_ones(published):
    cfg, F = published
    m = cfg['model']
    d, f, vocab = 3840, 11008, 100352
    linear = d * (2880 + 2880 + 5760 + 5760) + 5760 * d + 3 * d * f \
        + 2 * d * 30 + 4 * 11520 + 30 + 30 + 192 + 2 * d
    full = 4 * d * d + 3 * d * f + 4 * d
    assert (linear, full) == (215570172, 185809920)
    assert F.n_params(m) == 12 * linear + 4 * full + 2 * vocab * d + d \
        == 4100788944
    assert F.weight_bytes(m) == 8201577888                     # 8.20 GB
    assert F.kv_bytes_per_token(m) == 2 * 4 * d * 2 == 61440
    state = 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert F.state_bytes_per_resident(m) == state == 27371520  # 27.37 MB
    # the published depth: 7.43 B
    whole = dict(m, num_hidden_layers=32, layer_types=m['layer_types'] * 2)
    assert F.n_params(whole) == 7430870688
    # one decoded token against 3 000 held: 2 per multiplied parameter,
    # attention in 4 layers, the rule's three products in 12
    matmul = 12 * (linear - 4 * 11520 - 60 - 192 - 2 * d) \
        + 4 * (full - 4 * d) + d * vocab
    flops = 2 * matmul + 4 * 4 * d * 3000 + 12 * 3 * 2 * 30 * 96 * 192
    assert F.serve_flops_token(m, 3000) == flops == 7653580800
    # 14 residents of 3 000 tokens on a v5e: the weights once, the K/V
    # held, each resident's state read and written; bandwidth bounds it
    flops_s, bytes_s = peaks.TPU_PEAKS['TPU v5 lite']
    nbytes = 8201577888 + 61440 * 14 * 3000 + 2 * state * 14
    seconds, bound = F.decode_step_least_seconds(m, [3000] * 14, flops_s,
                                                 bytes_s)
    assert bound == 'bandwidth'
    assert seconds == pytest.approx(nbytes / 819e9) == \
        pytest.approx(0.0141007, rel=1e-5)
    assert 14 * flops / flops_s < seconds
    # a 512-token chunk is bound by its operations
    seconds, bound = F.prefill_call_least_seconds(m, 1024, 512, flops_s,
                                                  bytes_s)
    assert bound == 'compute' and seconds == pytest.approx(
        sum(2 * matmul + 16 * d * (p + 1) + 12 * 3317760
            for p in range(1024, 1536)) / 197e12)


def test_the_configuration_file_holds_the_published_row(published):
    cfg, _ = published
    entry = next(c for c in benchmark_json()['configs']
                 if c['name'] == CONFIG)
    assert cfg['source'] == entry['source'] and \
        cfg['reduced'] == entry['reduced'] == ['num_hidden_layers',
                                               'layer_types']
    m = cfg['model']
    widths = {'vocab_size': 100352, 'hidden_size': 3840,
              'intermediate_size': 11008, 'num_attention_heads': 30,
              'num_key_value_heads': 30, 'max_position_embeddings': 65536,
              'linear_num_key_heads': 30, 'linear_num_value_heads': 30,
              'linear_key_head_dim': 96, 'linear_value_head_dim': 192,
              'linear_conv_kernel_dim': 4, 'rms_norm_eps': 1e-06}
    assert {k: m[k] for k in widths} == widths
    assert m['num_hidden_layers'] == 16 == len(m['layer_types'])
    assert m['layer_types'] == ['linear_attention'] * 3 + [
        'full_attention'] + m['layer_types'][:12]
    # two readers, neither this PR's to edit, want the published keys in
    # two places: the harness builds from `model` (`benchlib/serve.py`),
    # the driver's check of a catalog row compares the top level
    assert all(cfg[k] == v for k, v in m.items() if k != 'initializer_range')
    eng = cfg['engine']
    assert not eng['prefix_cache'] and eng['spec_k'] == 0
    assert cfg['deployment'] and all(cfg['assumed'].values())


def test_every_request_of_the_mix_fits_the_engine(published):
    cfg, _ = published
    tcfg = T.load('long-docs', BENCH)
    trace = T.serve_trace(tcfg, 2 ** 32 + 5, 45)
    eng = cfg['engine']
    lens = [len(p) for p in trace.prompts]
    assert len(trace) == 48 * tcfg['arrival']['repeats'] >= 144
    assert min(lens) >= 1024 and max(lens) <= 6144
    assert all(n + o <= eng['max_len']
               for n, o in zip(lens, trace.outputs))
    assert max(max(p) for p in trace.prompts) < 100352
    # the same job for every seed; the pool, not the slots, admits
    again = T.serve_trace(tcfg, 9, 45)
    assert list(map(len, again.prompts)) == lens
    pages = lambda n, o: -(-(n + o - 1) // eng['page_size'])
    need = sorted(pages(n, o) for n, o in zip(lens[:48], trace.outputs[:48]))
    assert sum(need[:eng['num_seqs']]) > eng['num_pages'] - 1 or \
        np.mean(need) * eng['num_seqs'] > eng['num_pages'] - 1


def _replay_of(obs, warmup_steps, tool_replay):
    trace, eng = obs['trace_obj'], obs['engine']
    steps = tool_replay.replay(
        [len(p) for p in trace.prompts], [int(o) for o in trace.outputs],
        eng['num_seqs'], eng['num_pages'], eng['page_size'],
        eng['prefill_chunk'], eng['decode_block'])
    return steps, tool_replay.first_mismatch(
        steps, warmup_steps, [[s[2], s[3]] for s in obs['steps']])


def test_the_replay_is_the_engines_schedule_at_a_tiny_size(tiny_run):
    """Slots and pages in use after every engine step of the window, as
    the benchmark sampled them from the engine, are the replay's."""
    _, obs = tiny_run
    warm = load(os.path.join(TINY, 'traffic', 'tiny-long-docs.json'))[
        'arrival']['warmup_steps']
    assert len(obs['steps']) >= 10
    assert len({tuple(s[2:4]) for s in obs['steps']}) >= 5
    steps, mismatch = _replay_of(obs, warm, tool('replay'))
    assert mismatch is None
    # ... and it is a comparison: another pool is another schedule
    obs = dict(obs, engine=dict(obs['engine'], num_pages=40))
    assert _replay_of(obs, warm, tool('replay'))[1] is not None


def test_the_replay_is_a_chip_runs_step_log_and_the_backlog_is_deep(
        published):
    """`data/long_docs_steps.json`: slots and pages in use after each of
    the 237 engine steps of one window on the chip at the cell's own
    size (`replay.py --record`; my chip run, PR 30). The replay has the
    same in every step, so what it says of the steps no window reaches
    can be believed: the queue holds requests until an engine step
    under a quarter of the chip's (175 ms p50, the same run's)."""
    cfg, _ = published
    R = tool('replay')
    logged = load(os.path.join(BENCH, 'data', 'long_docs_steps.json'))
    assert logged['workload'] == CELL and \
        logged['num_pages'] == cfg['engine']['num_pages']
    engine, tcfg, run_seconds = R.load_cell(CELL)
    assert engine == cfg['engine'] and run_seconds == 45
    steps = R.replay_cell(engine, tcfg)
    warm = tcfg['arrival']['warmup_steps']
    assert len(logged['steps']) >= 200
    assert R.first_mismatch(steps, warm, logged['steps']) is None
    out = R.summary(steps, warm, run_seconds)
    assert out['queue_empty_from_step'] == 1623 and \
        out['engine_steps'] == 1720
    assert out['dry_below_step_ms'] < 175.0 / 4
    assert 9.5 < out['residents_mean'] < 10.5 < \
        max(s[0] for s in logged['steps'])
    # three dealings would not do: the cell would run dry under 59 ms
    shallow = R.summary(R.replay_cell(engine, tcfg, repeats=3), warm,
                        run_seconds)
    assert shallow['dry_below_step_ms'] > 175.0 / 4


# the entries PR 26 appended (`test_perfbench_program_spans.py`)
PR26 = ('turns_step_prefill_ms_p50', 'turns_step_self_ms_p50',
        'turns_prefill_calls_per_step', 'turns_admit_to_first_token_ms_p90',
        'turns_admit_blocked_share', 'turns_prefill_idle_share',
        'offline_step_prefill_ms_p50', 'offline_step_self_ms_p50',
        'offline_blocked_on_pages_share', 'offline_burst_idle_share',
        'train_dispatch_ms_p50')
LONGDOCS = ('longdocs_mfu_pct', 'longdocs_device_idle_share',
            'longdocs_decode_step_roofline',
            'longdocs_prefill_call_roofline', 'longdocs_step_prefill_ms_p50',
            'longdocs_prefill_calls_per_step', 'longdocs_batch_occupancy',
            'longdocs_pages_in_use_peak', 'longdocs_blocked_on_pages_share',
            'longdocs_state_bytes_peak', 'longdocs_decode_burst_ms_p50',
            'longdocs_burst_idle_share', 'longdocs_step_self_ms_p50')


def test_earlier_entries_keep_their_place_and_this_prs_follow_them():
    """`test_entries_are_appended_and_name_one_cell` asserts that PR
    26's eleven entries END `per_layer`, which no PR that appends can
    keep: it fails from this PR on, until a `benchmark` PR compares the
    order instead (PERF.md section 7). What it held besides, held here:
    the eleven are all there, in their order, with nothing between
    them, one cell and the same keys each; and this PR's entries are
    the tail, each naming the new cell alone."""
    per_layer = benchmark_json()['per_layer']
    names = [m['name'] for m in per_layer]
    first = names.index(PR26[0])
    assert tuple(names[first:first + len(PR26)]) == PR26
    assert tuple(names[first + len(PR26):]) == LONGDOCS
    keys = {'name', 'unit', 'better', 'source', 'layer', 'moves',
            'workloads'}
    for m in per_layer[first:]:
        assert set(m) == keys and len(m['workloads']) == 1
    for m in per_layer[first + len(PR26):]:
        assert m['workloads'] == [CELL] and \
            m['moves'] == 'serve_tokens_per_s'
        assert os.path.exists(os.path.join(BENCH, 'metrics',
                                           m['name'] + '.py'))
