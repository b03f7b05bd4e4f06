"""The Kimi-Linear family behind the benchmark: its cell is `correct` on
the CPU at a tiny size from files alone, the control and each planted
fault come out as not correct there, its configuration keeps the
published keys, its counts at the published widths are the numbers a
hand computes, and the replay (`tools/replay.py`) bears out what the
traffic file says of the backlog."""
import importlib.util
import json
import os

import pytest

from bench_testlib import BENCH, TINY, bench_run, benchmark_json, rehearse
from benchlib import reference, serve
from benchlib import traffic as T

CELL = 'serve-kimi-linear.long-answers'
CONFIG = 'kimi-linear-48b-serve-1chip'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


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
                                                  'kimi_linear.py')
    assert result['correct'] is True, result['compared']
    c = result['compared']
    assert c['logit_gap_max']['value'] <= c['logit_gap_max']['limit']
    assert c['tokens_compared']['value'] >= 20
    assert c['bad_answers'] == {'value': 0, 'limit': 0}
    assert result['attempted'] > 0 and result['failed'] == 0
    # the backlog outlasts the window; prompts take one or two chunks
    assert any(r.req is None for r in obs['recs'])
    chunk = obs['engine']['prefill_chunk']
    sample = [len(obs['trace_obj'].prompts[r.idx]) for r in obs['recs'][:16]]
    assert 2 * chunk > max(sample) > chunk > min(sample)
    assert set(result['metrics']) == {'serve_tokens_per_s', 'setup_s'}


def test_the_routing_counters_reach_the_readers():
    """A traced rehearsal (no device plane on the CPU): the readers of
    the program's spans and counters give numbers."""
    result, _ = rehearse(CELL, seed=2 ** 31 + 29, trace=1)
    got = {k: v['value'] for k, v in result['metrics'].items()}
    # 4 of 16 experts held, 4 choices a token
    assert 15.0 < got['answers_held_pick_share'] < 35.0
    assert 0.0 < got['answers_experts_touched_share'] <= 100.0
    assert got['answers_expert_load_max_over_mean'] >= 1.0
    assert got['answers_blocked_on_slots_share'] == 100.0
    assert got['answers_state_bytes_peak'] == 4 * (
        4 * (4 * 8 * 8 * 4 + 3 * 96 * 4))
    assert got['answers_batch_occupancy'] > 50.0


# the window is a second on the clock, so which tokens are compared
# depends on the machine's load: the skipped latent norm read 8 to 37
# times the limit over windows of 0.15 to 1 s (the driver's run of the
# whole suite caught it under 10), the other three far more
@pytest.mark.parametrize('fault, times', [
    ('state_not_carried', 10), ('decay_scalar', 10), ('renorm_off', 10),
    ('latent_norm_skipped', 3)],
    ids=['state_not_carried', 'decay_scalar', 'renorm_off',
         'latent_norm_skipped'])
def test_each_planted_fault_reads_above_the_limit(fault, times):
    with tool('faults_kimi_linear').planted(fault):
        result, _ = rehearse(CELL, seed=2 ** 31 + 23)
    c = result['compared']['logit_gap_max']
    assert result['correct'] is False and c['value'] > times * c['limit'], c
    # ... and nothing stays planted behind it
    result, _ = rehearse(CELL, seed=2 ** 31 + 23)
    assert result['correct'] is True


@pytest.mark.parametrize('seed', (3, 2 ** 31 + 4))
def test_the_control_reads_above_the_limit(seed):
    """float8 in the projections', the attention products' and the
    experts' place, at the tiny configuration: far above the limit."""
    cfg = load(os.path.join(TINY, 'configs', 'tiny-kimi-linear.json'))
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


def test_the_configuration_keeps_the_published_keys(published):
    """Top level and `model` agree key by key; every key of the
    catalog's row is there unchanged but the four listed in `reduced`,
    of which none is a width and whose group keeps its widths."""
    cfg, _ = published
    m = cfg['model']
    for key, value in m.items():
        assert cfg[key] == value, key
    assert cfg['reduced'] == ['num_hidden_layers', 'linear_attn_config',
                              'num_experts', 'vocab_size']
    entry = next(c for c in benchmark_json()['configs']
                 if c['name'] == CONFIG)
    assert entry['reduced'] == cfg['reduced'] and \
        entry['source'] == cfg['source']
    assert (m['num_hidden_layers'], m['num_experts'], m['vocab_size']) == \
        (9, 64, 40960)
    assert (m['num_experts_published'], m['experts_held'],
            m['vocab_size_published']) == (256, [0, 64], 163840)
    lac = m['linear_attn_config']
    assert (lac['kda_layers'], lac['full_attn_layers']) == \
        ([1, 2, 3, 5, 6, 7, 9], [4, 8])
    if not os.path.exists(CATALOG):
        pytest.skip('the catalog is not on this machine')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Kimi-Linear-48B-A3B-Instruct')
    assert cfg['source'] == row['source_url']
    for key, value in row['config'].items():
        if key == 'linear_attn_config':
            assert {k: v for k, v in lac.items() if not k.endswith(
                '_layers')} == {k: v for k, v in value.items()
                                if not k.endswith('_layers')}
        elif key not in cfg['reduced']:
            assert m[key] == value, key


def test_counts_at_the_published_widths_are_the_hand_written_ones(published):
    cfg, F = published
    m = cfg['model']
    d, vocab = 2304, 40960
    expert = 3 * d * 1024
    kda = 3 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32 + 4 * 12288 \
        + 4096 * d
    mla = d * 6144 + d * 576 + 512 * 8192 + 4096 * d
    assert (expert, kda, mla) == (7077888, 39510016, 29114368)
    kda_vec, mla_vec = 32 + 4096 + 128 + 2 * d, 512 + 2 * d
    sparse = expert + d * 256 + 256          # shared, router, correction
    n = (kda + kda_vec) * 7 + (mla + mla_vec) * 2 + 3 * d * 9216 \
        + 8 * sparse + 8 * 64 * expert + 2 * vocab * d + d
    assert F.n_params(m) == n == 4272540512
    # bf16, the router and its correction float32: 8.55 GB
    assert F.weight_bytes(m) == 2 * n + 2 * 8 * (d * 256 + 256) == 8554522304
    assert F.kv_bytes_per_token(m) == 2 * 576 * 2 == 2304
    state = 7 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert F.state_bytes_per_resident(m) == state == 15196160   # 15.2 MB
    # one decoded token against 1 000 held: 2 per parameter outside the
    # routed experts (without the vectors and the embedding's read), the
    # 2 pairs of 8 that fall on this chip's quarter in each of 8 layers,
    # attention in the latent in 2 layers, the rule's products in 7
    fixed = 7 * (kda - 4 * 12288) + 2 * mla + 3 * d * 9216 \
        + 8 * (expert + d * 256) + d * vocab
    flops = 2 * fixed + 2 * 8 * 2 * expert \
        + 2 * 2 * 32 * (2 * 512 + 64) * 1000 + 7 * 3 * 2 * 32 * 128 * 128
    assert F.serve_flops_token(m, 1000) == flops == 1495515136
    # 128 residents of 900 tokens on a v5e: the bytes decide. 98.3% of a
    # layer's 64 held experts are touched
    touched = 64 * (1 - (1 - 8 / 256) ** 128)
    assert 62.8 < touched < 63.0
    nbytes = 2 * (n - 8 * 64 * expert) + 2 * 8 * (d * 256 + 256) \
        + 8 * touched * expert * 2 + 2304 * 128 * 900 + 2 * state * 128
    least, bound = F.decode_step_least_seconds(m, [900] * 128, 197e12, 819e9)
    assert bound == 'bandwidth' and least == pytest.approx(nbytes / 819e9)
    assert 0.0150 < least < 0.0158
    # a whole first chunk: the weights it touches, read once, still decide
    least, bound = F.prefill_call_least_seconds(m, 0, 512, 197e12, 819e9)
    assert bound == 'bandwidth' and 0.0100 < least < 0.0110
    # the uncut model: 48 B
    whole = dict(m, num_hidden_layers=27, num_experts=256,
                 experts_held=[0, 256], vocab_size=163840,
                 linear_attn_config=dict(
                     m['linear_attn_config'],
                     kda_layers=[i for i in range(1, 28)
                                 if i % 4 and i != 27],
                     full_attn_layers=[4, 8, 12, 16, 20, 24, 27]))
    assert 48.0e9 < F.n_params(whole) < 49.5e9


def test_a_steps_least_time_is_the_sum_over_its_rows(published):
    """The driver asks for these between engine steps with the device
    idle, so the family answers from two coefficients and the rows'
    sum: the same numbers as the sum over the rows, for either section
    in turn."""
    cfg, F = published
    m = cfg['model']
    other = dict(m, num_hidden_layers=5,
                 linear_attn_config=dict(m['linear_attn_config'],
                                         kda_layers=[1, 2, 3, 5],
                                         full_attn_layers=[4]))
    contexts = [137 + 19 * i for i in range(128)]
    for section in (m, other, m):
        token = F.serve_flops_token(section, 0)
        row = F.serve_flops_token(section, 1) - token
        assert row == 2 * 32 * (2 * 512 + 64) * F.layer_kinds(
            section).count(F.MLA)
        assert F.serve_flops_token(section, 2500) == token + 2500 * row
        flops = sum(F.serve_flops_token(section, c) for c in contexts)
        # no bandwidth to wait for: the operations decide
        least, bound = F.decode_step_least_seconds(section, contexts,
                                                   197e12, 1e30)
        assert bound == 'compute'
        assert least == pytest.approx(flops / 197e12, rel=1e-12)
    assert F.serve_flops_token(other, 0) < F.serve_flops_token(m, 0)


def test_the_family_builds_the_programs_own_model(published):
    """Shapes only (`nn.skip_init`): the program's class, told its
    share."""
    cfg, F = published
    from paddle_tpu import nn
    from paddle_tpu.text.models import KimiLinearConfig
    m = cfg['model']
    conf = KimiLinearConfig(num_experts=F.published_experts(m),
                            experts_held=F.held(m),
                            **{k: m[k] for k in F.PUBLISHED_KEYS})
    assert conf.experts_held == (0, 64) and conf.num_experts == 256
    assert conf.layer_kinds == ['kda'] * 3 + ['mla'] + ['kda'] * 3 \
        + ['mla', 'kda']
    assert conf.max_position_embeddings == 1048576
    with nn.skip_init():
        from paddle_tpu.text.models import KimiLinearForCausalLM
        model = KimiLinearForCausalLM(conf)
    table = F.leaf_table(m)
    assert set(dict(model.named_parameters())) == set(table)
    assert table['model.layers.1.mlp.gate_proj'][0] == (64, 1024, 2304)
    assert table['model.layers.1.mlp.router'][0] == (2304, 256)
    specs = model.cache_specs()
    assert [type(s).__name__ for s in specs].count('PagedLatentSpec') == 2
    assert specs[3].width == 576


def test_the_backlog_is_as_deep_as_the_traffic_file_says():
    """`tools/replay.py` from the files alone: all 128 slots are filled
    by the first engine step, the window (32 warm-up steps, 45 s) never
    sees an empty queue unless an engine step falls under the figure the
    file's `why` gives, and slots, not pages, are what fills."""
    R = tool('replay')
    engine, tcfg, run_seconds = R.load_cell(CELL)
    warm = tcfg['arrival']['warmup_steps']
    steps = R.replay_cell(engine, tcfg)
    assert steps[0]['slots'] == engine['num_seqs'] == 128
    assert warm == 32 and warm % 8 == 0
    out = R.summary(steps, warm, run_seconds)
    assert out['pages_in_use_max'] < engine['num_pages'] - 1
    assert out['residents_mean'] > 127.0
    assert 0.5 < out['prefill_calls_per_step'] < 1.5
    assert ('%.1f ms' % out['dry_below_step_ms']) in tcfg['why']
    n = tcfg['arrival']['requests'] * tcfg['arrival']['repeats']
    assert ('%d requests' % n) in tcfg['why']


ANSWERS = ('answers_mfu_pct', 'answers_device_idle_share',
           'answers_decode_step_roofline', 'answers_prefill_call_roofline',
           'answers_burst_idle_share', 'answers_step_self_ms_p50',
           'answers_step_prefill_ms_p50', 'answers_batch_occupancy',
           'answers_state_bytes_peak', 'answers_pages_in_use_peak',
           'answers_blocked_on_slots_share', 'answers_held_pick_share',
           'answers_expert_load_max_over_mean',
           'answers_experts_touched_share')


def test_this_prs_entries_follow_the_earlier_ones_and_name_the_new_cell():
    """In their order, with nothing between them, after every entry the
    benchmark had; what a later PR appends comes after and breaks
    nothing here."""
    bm = benchmark_json()
    per_layer = bm['per_layer']
    names = [m['name'] for m in per_layer]
    first = names.index(ANSWERS[0])
    assert tuple(names[first:first + len(ANSWERS)]) == ANSWERS
    assert names.index('longdocs_step_self_ms_p50') == first - 1
    keys = {'name', 'unit', 'better', 'source', 'layer', 'moves',
            'workloads'}
    for m in per_layer[first:first + len(ANSWERS)]:
        assert set(m) == keys and m['workloads'] == [CELL] \
            and m['moves'] == 'serve_tokens_per_s'
        assert os.path.exists(os.path.join(BENCH, 'metrics',
                                           m['name'] + '.py'))
    cell = next(w for w in bm['workloads'] if w['name'] == CELL)
    assert cell['chips'] == 1 and len(cell['why']) <= 200
    assert [w['name'] for w in bm['workloads']].index(CELL) == 4
    assert [c['name'] for c in bm['configs']].index(CONFIG) == 3
    rate = next(m for m in bm['end_to_end']
                if m['name'] == 'serve_tokens_per_s')
    assert rate['workloads'][:3] == ['serve-xl.offline-decode',
                                     'serve-olmo-hybrid.long-docs', CELL]
