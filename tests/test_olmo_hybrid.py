"""Olmo-Hybrid: the gated delta rule (chunked against the recurrence),
the model against the plain reference of `benchmarks/families/
olmo_hybrid.py`, and the model behind PagedContinuousBatchingEngine with
its second kind of state: per-slot arrays that every token rewrites.

Sizes: hidden 64, 8 layers in the published 3:1 pattern, key and value
head sizes in the published 1:2 ratio, float32. Tolerances, each with
its reason, are beside the comparisons; `test_the_tolerance_*` show that
they fail a state kept in bf16 and projections below float32.
"""
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.monitor import MetricRegistry
from paddle_tpu.monitor.registry import set_default_registry
from paddle_tpu.monitor.tracing import (FlightRecorder, Tracer,
                                        set_default_tracer)
from paddle_tpu.serving import PagedContinuousBatchingEngine, kv_cache
from paddle_tpu.text.models import cache as cache_mod
from paddle_tpu.text.models import olmo_hybrid as O

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, 'benchmarks')

M = {'vocab_size': 211, 'hidden_size': 64, 'intermediate_size': 128,
     'num_hidden_layers': 8,
     'layer_types': [O.LINEAR, O.LINEAR, O.LINEAR, O.FULL] * 2,
     'num_attention_heads': 4, 'num_key_value_heads': 4,
     'hidden_act': 'silu', 'max_position_embeddings': 256,
     'attention_bias': False, 'rms_norm_eps': 1e-6,
     'tie_word_embeddings': False, 'linear_num_key_heads': 4,
     'linear_num_value_heads': 4, 'linear_key_head_dim': 8,
     'linear_value_head_dim': 16, 'linear_conv_kernel_dim': 4,
     'linear_allow_neg_eigval': True,
     'rope_parameters': {'rope_theta': None}, 'initializer_range': 0.02}

# float32 program against a float32 reference on the CPU: the two differ
# in the order of their sums alone (chunked against token by token,
# pages against one sequence). 8 layers leave logits that reach 0.7
# within 2e-5 of each other; a state kept in bf16, or projections in
# bf16, read a hundred times that (below).
LOGIT_TOL = 1e-4
# a served token may be another than the reference's first only where
# two reference logits lie within the program's own error of each other
GAP_TOL = 1e-4


@pytest.fixture(scope='module')
def family():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        'olmo_hybrid_family', os.path.join(BENCH, 'families',
                                           'olmo_hybrid.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def served(family):
    """(the program's model holding seeded weights, the same weights
    stacked for the reference)."""
    model = family.build_model(
        M, 'float32', family.program_leaves(
            family.make_stacked(M, 11, 'float32')))
    model.eval()
    return model, family.make_stacked(M, 11, 'float32')


def _engine(model, **kw):
    args = dict(num_seqs=3, max_len=96, page_size=8, prefill_chunk=16,
                decode_block=4, prefix_cache=False)
    args.update(kw)
    return PagedContinuousBatchingEngine(model, **args)


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, M['vocab_size'], n)]
            for n in lengths]


def _widest_gap(family, stacked, prompts, outs):
    from benchlib import reference
    with reference.highest():
        gaps, _ = family.served_gaps(stacked, M, list(zip(prompts, outs)))
    return max(float(g.max()) for g in gaps)


# ---- the rule ---------------------------------------------------------------

def _rule_inputs(seed, b, t, h=3, dk=8, dv=16):
    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.randn(*s).astype(np.float32)
    return (unit(f(b, t, h, dk)) * dk ** -0.5, unit(f(b, t, h, dk)),
            f(b, t, h, dv), -0.7 * rng.rand(b, t, h).astype(np.float32),
            2.0 * rng.rand(b, t, h).astype(np.float32), f(b, h, dk, dv))


def _recurrence(q, k, v, g, beta, state):
    outs = []
    for i in range(q.shape[1]):
        o, state = O.delta_rule_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                     beta[:, i], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize('length,cuts', [
    (37, ()), (64, ()), (150, ()), (200, (70,)), (150, (1, 64, 65, 149))])
def test_chunked_rule_equals_the_recurrence(length, cuts):
    """Lengths that are not multiples of 64, the state carried over chunk
    boundaries inside a call and over calls cut anywhere. Float32, the
    same products in another order: 1e-5 on outputs of order 0.3."""
    *x, s0 = _rule_inputs(length, 2, length)
    want_o, want_s = _recurrence(*x, s0)
    got, state = [], s0
    for lo, hi in zip((0,) + cuts, cuts + (length,)):
        o, state = O.chunked_delta_rule(*(a[:, lo:hi] for a in x), state)
        got.append(o)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want_o, rtol=0,
                               atol=1e-5)
    np.testing.assert_allclose(state, want_s, rtol=0, atol=1e-5)


def test_a_token_with_shut_gates_leaves_the_state():
    """beta 0 and log alpha 0 is how a padded tail is masked: whatever
    q, k, v it carries."""
    q, k, v, g, beta, s0 = _rule_inputs(3, 2, 100)
    real = np.arange(100)[None, :, None] < np.asarray([60, 0])[:, None, None]
    g, beta = O._mask_gates(g, beta, real[..., 0])
    _, state = O.chunked_delta_rule(q, k, v, g, beta, s0)
    _, want = O.chunked_delta_rule(q[:1, :60], k[:1, :60], v[:1, :60],
                                   g[:1, :60], beta[:1, :60], s0[:1])
    np.testing.assert_allclose(state[0], want[0], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(state[1], s0[1])


# ---- the model against the plain reference ---------------------------------

def test_forward_equals_the_plain_reference(family, served):
    from benchlib import reference
    model, stacked = served
    ids = np.asarray(_prompts(5, [70, 70]), np.int32)
    got = model(paddle.to_tensor(ids)).numpy()
    with reference.highest():
        want = np.asarray(family.forward_logits(stacked, M, jnp.asarray(ids)))
    assert got.shape == want.shape == (2, 70, M['vocab_size'])
    assert np.abs(want).max() > 0.5       # the tolerance is 2e-4 of that
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL)


def test_no_leaf_is_trivial_and_alpha_spans_its_range(family):
    stacked = family.make_stacked(M, 2 ** 31 + 5, 'float32')
    for kind, arr in stacked.items():
        a = np.asarray(arr, np.float64)
        assert a.std() > 0 and not np.all(a == a.flat[0]), kind
    alpha = np.exp(-np.exp(np.asarray(stacked['lin.A_log']))
                   * np.log1p(np.exp(np.asarray(stacked['lin.dt_bias']))))
    lo, hi = family.ALPHA_SPAN
    assert lo - 1e-3 < alpha.min() and alpha.max() < hi + 1e-4
    assert alpha.min() < 0.7 and alpha.max() > 0.99


def test_masked_positions_and_frozen_rows_through_the_mixer(served):
    """One linear layer, three rows of one 24-token call: a row that
    takes 9 of them gives the state of a 9-token call, a row that takes
    none keeps its arrays bit for bit, a row at length 0 starts from
    zeros whatever its slot held."""
    model, _ = served
    mixer = model.model.layers[0].mixer
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(3, 24, 64).astype(np.float32))
    spec = mixer.cache_spec('float32')
    held = tuple(jnp.asarray(rng.randn(3, *shape).astype(dtype))
                 for shape, dtype in spec.arrays)
    lengths = jnp.asarray([5, 7, 0], jnp.int32)
    _, new = mixer(x, cache=cache_mod.RecurrentCache(
        held, lengths, jnp.asarray([9, 0, 24], jnp.int32)))
    _, nine = mixer(x[0:1, :9], cache=cache_mod.RecurrentCache(
        tuple(a[0:1] for a in held), lengths[0:1],
        jnp.asarray([9], jnp.int32)))
    _, fresh = mixer(x[2:3], cache=cache_mod.RecurrentCache(
        tuple(jnp.zeros_like(a[2:3]) for a in held), lengths[2:3],
        jnp.asarray([24], jnp.int32)))
    for got, a, b, old in zip(new.arrays, nine.arrays, fresh.arrays, held):
        np.testing.assert_allclose(got[0], a[0], rtol=0, atol=1e-6)
        np.testing.assert_array_equal(got[1], old[1])
        np.testing.assert_allclose(got[2], b[0], rtol=0, atol=1e-6)


# ---- behind the paged engine ------------------------------------------------

def test_chunked_prefill_then_decode_equals_the_full_forward(family, served):
    """Prompts that are not multiples of the prefill chunk (16), more
    requests than slots (a slot is reused after a retirement), budgets
    that run out inside a burst of 4 beside lanes that go on. Served
    tokens are the reference's own first choice up to GAP_TOL, and the
    program's own greedy continuation exactly."""
    model, stacked = served
    prompts = _prompts(3, [37, 21, 9, 33, 50, 16])
    budgets = [9, 3, 6, 10, 5, 7]
    eng = _engine(model)
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    eng.run()
    outs = [r.tokens for r in reqs]
    assert [len(o) for o in outs] == budgets
    assert eng.trace_counts == {'prefill': 1, 'decode': 1, 'verify': 0}
    assert _widest_gap(family, stacked, prompts, outs) <= GAP_TOL
    # one forward of every prompt with its tokens behind it (padded to
    # one length: causal, so what follows a row's tokens changes nothing)
    ids = np.zeros((len(prompts), 64), np.int32)
    for row, (p, out) in zip(ids, zip(prompts, outs)):
        row[:len(p) + len(out)] = p + out
    picks = model(paddle.to_tensor(ids)).numpy().argmax(-1)
    for row, p, out in zip(picks, prompts, outs):
        assert list(row[len(p) - 1:len(p) - 1 + len(out)]) == out


def test_a_preempted_request_recomputes_to_the_same_tokens(family, served):
    model, stacked = served
    prompts = _prompts(8, [30, 19, 25])
    eng = _engine(model, num_seqs=2, preempt=True)
    want = eng.generate(prompts, max_new_tokens=12)
    r0 = eng.add_request(prompts[0], max_new_tokens=12, priority=0)
    r1 = eng.add_request(prompts[1], max_new_tokens=12, priority=0)
    while min(len(r0.tokens), len(r1.tokens)) < 3:
        eng.step()                       # both residents mid-decode
    r2 = eng.add_request(prompts[2], max_new_tokens=12, priority=1)
    eng.run()
    assert eng.scheduler.preempted == 1
    assert [r0.tokens, r1.tokens, r2.tokens] == want
    assert eng.trace_counts == {'prefill': 1, 'decode': 1, 'verify': 0}
    assert _widest_gap(family, stacked, prompts, want) <= GAP_TOL


def test_prefix_reuse_and_speculation_are_refused_with_the_reason(served):
    model, _ = served
    with pytest.raises(ValueError, match='prefix hit would start'):
        _engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match='cannot take back'):
        _engine(model, spec_k=2)


def test_the_tolerance_fails_a_state_kept_in_bf16(family, served,
                                                  monkeypatch):
    model, stacked = served
    spec = O.OlmoHybridLinearAttention.cache_spec

    def bf16_state(self, dtype):
        s = spec(self, dtype)
        return s._replace(arrays=((s.arrays[0][0], 'bfloat16'),
                                  s.arrays[1]))
    monkeypatch.setattr(O.OlmoHybridLinearAttention, 'cache_spec',
                        bf16_state)
    prompts = _prompts(3, [37, 21, 50])
    eng = _engine(model)
    assert eng._pools[0][0].dtype == jnp.bfloat16
    outs = eng.generate(prompts, max_new_tokens=24)
    assert _widest_gap(family, stacked, prompts, outs) > 10 * GAP_TOL


def test_the_tolerance_fails_projections_below_float32(family):
    """The same weights rounded to bf16 and served in bf16, against the
    float32 reference of the float32 weights."""
    f32 = family.make_stacked(M, 11, 'float32')
    lower = family.Stacked({k: v.astype(jnp.bfloat16)
                            for k, v in f32.items()})
    lower.m = M
    model = family.build_model(M, 'bfloat16', family.program_leaves(lower))
    model.eval()
    prompts = _prompts(3, [37, 21, 50])
    outs = _engine(model).generate(prompts, max_new_tokens=24)
    assert _widest_gap(family, f32, prompts, outs) > 10 * GAP_TOL


# ---- the cache interface ----------------------------------------------------

def test_the_model_names_what_each_layer_keeps(served):
    model, _ = served
    specs = kv_cache.cache_specs(model)
    assert [type(s).__name__ for s in specs] == \
        ['RecurrentSpec'] * 3 + ['PagedKVSpec'] + \
        ['RecurrentSpec'] * 3 + ['PagedKVSpec']
    assert specs[3] == cache_mod.PagedKVSpec(4, 16, 'float32')
    assert specs[0].arrays == (((4, 8, 16), 'float32'),
                               ((3, 4 * (8 + 8 + 16)), 'float32'))
    assert kv_cache.kv_row_bytes(specs) == 2 * 2 * 64 * 4
    assert kv_cache.state_bytes_per_seq(specs) == \
        6 * (4 * 8 * 16 + 3 * 128) * 4
    state = kv_cache.build_paged_pools(model, 5, 8, num_seqs=3)
    assert [tuple(a.shape for a in s) for s in state[2:4]] == [
        ((3, 4, 8, 16), (3, 3, 128)), ((1, 40, 128), (1, 40, 128))]


def test_gpt2_goes_through_the_same_interface():
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=128, dropout=0.0))
    specs = kv_cache.cache_specs(gpt)
    assert specs == [cache_mod.PagedKVSpec(4, 16, 'float32')] * 2
    assert kv_cache.state_bytes_per_seq(specs) == 0
    # ... and nothing under serving/ reads a model's attributes by name
    serving = os.path.join(REPO, 'paddle_tpu', 'serving')
    needle = re.compile(r'model\.gpt\b|\.gpt\.(h|wte)\b|GPTPagedCache|'
                        r'[Oo]lmo')
    hits = [(f, i + 1) for f in sorted(os.listdir(serving))
            if f.endswith('.py')
            for i, line in enumerate(open(os.path.join(serving, f)))
            if needle.search(line) and 'OlmoHybridForCausalLM' not in line]
    assert not hits
    with pytest.raises(TypeError, match='cache_specs'):
        kv_cache.cache_specs(nn.Linear(2, 2))


def test_skip_init_builds_shapes_and_reading_one_raises():
    with nn.skip_init():
        layer = nn.Linear(3, 5)
    assert layer.weight.shape == [3, 5] and layer.bias.shape == [5]
    assert isinstance(layer.weight._data, jax.ShapeDtypeStruct)
    with pytest.raises(Exception):
        layer(paddle.to_tensor(np.zeros((2, 3), np.float32)))
    assert isinstance(nn.Linear(3, 5).weight._data, jnp.ndarray)


# ---- what the engine says of the second kind of state -----------------------

@pytest.fixture
def traced(tmp_path):
    """A fresh registry and tracer, the process defaults while the
    engine under test is built."""
    reg = MetricRegistry()
    tracer = Tracer(registry=reg, recorder=FlightRecorder(
        capacity=1024, dump_dir=str(tmp_path / 'flight'), cooldown=3600.0,
        registry=reg))
    prev = set_default_registry(reg), set_default_tracer(tracer)
    yield tracer
    set_default_registry(prev[0])
    set_default_tracer(prev[1])


def test_spans_gauge_and_scopes_of_the_state(served, traced):
    model, _ = served
    eng = _engine(model)
    per_seq = kv_cache.state_bytes_per_seq(eng._specs)
    prompts = _prompts(4, [70, 9])
    eng.generate(prompts, max_new_tokens=12)
    spans = traced.recorder.spans()
    steps = [s for s in spans if s['name'] == 'serving.step']
    assert steps[0]['tags']['state_slots_in_use'] == 0
    assert max(s['tags']['state_slots_in_use'] for s in steps) == 2
    assert max(s['tags']['state_bytes'] for s in steps) == 2 * per_seq
    calls = [s['tags'] for s in spans if s['name'] == 'serving.prefill_call']
    first = [c for c in calls if c['slot'] == 0]
    assert [c['start'] for c in first] == [0, 16, 32, 48, 64]
    assert [c['tokens'] for c in first] == [16, 16, 16, 16, 6]
    assert all(c['kv_read'] == 'gather' for c in calls)
    gauge = eng.metrics.registry.get('serving_state_bytes')
    assert gauge.value() == 0                 # everybody has retired
    text = eng._decode_jit.lower(*eng._decode_args).compile().as_text()
    scopes = ['olmo.embed', 'olmo.norm', 'olmo.mlp', 'olmo.lm_head',
              'serving.pick_token']
    scopes += ['olmo.attn.' + s for s in (
        'qkv', 'qk_norm', 'paged_write', 'paged_gather', 'mask', 'core',
        'out')]
    scopes += ['olmo.gdn.' + s for s in (
        'proj', 'conv', 'gates', 'core', 'state_write', 'norm_gate', 'out')]
    for scope in scopes:
        assert scope in text, scope
    # (a jitted helper first traced under another model keeps that file's
    # NAME in the frame table: only a scope counts)
    assert not re.search(r'gpt\.(?!py\b)', text)
    assert eng.trace_counts == {'prefill': 1, 'decode': 1, 'verify': 0}
