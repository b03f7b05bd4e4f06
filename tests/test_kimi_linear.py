"""Kimi-Linear: the per-channel gated delta rule (chunked against the
recurrence), latent attention (in the latent against expanded K and V),
the expert layer that holds a share of its experts (the shares add up to
the uncut layer; nothing is dropped), each layer and the whole model
against the plain reference of `benchmarks/families/kimi_linear.py`, and
the model behind PagedContinuousBatchingEngine with its three kinds of
state in one program: per-slot arrays (KDA), a latent page pool (MLA),
and device counters of the routing.

Sizes: hidden 64, 5 layers (K K K M K, the first with a dense MLP), 16
experts of which 4 are held, float32. Tolerances, each with its reason,
are beside the comparisons.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.monitor.tracing import (FlightRecorder, Tracer,
                                        set_default_tracer)
from paddle_tpu.serving import PagedContinuousBatchingEngine, kv_cache
from paddle_tpu.text.models import cache as cache_mod
from paddle_tpu.text.models import kimi_linear as K

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, 'benchmarks')

M = {'vocab_size': 211, 'hidden_size': 64, 'intermediate_size': 128,
     'num_hidden_layers': 5, 'num_attention_heads': 4,
     'num_key_value_heads': 4, 'head_dim': 16, 'hidden_act': 'silu',
     'rms_norm_eps': 1e-5, 'tie_word_embeddings': False,
     'model_max_length': 256,
     'linear_attn_config': {'kda_layers': [1, 2, 3, 5],
                            'full_attn_layers': [4], 'head_dim': 8,
                            'num_heads': 4, 'short_conv_kernel_size': 4},
     'kv_lora_rank': 16, 'q_lora_rank': None, 'qk_nope_head_dim': 8,
     'qk_rope_head_dim': 4, 'v_head_dim': 8, 'mla_use_nope': True,
     'rope_theta': 10000, 'rope_scaling': None, 'first_k_dense_replace': 1,
     'moe_layer_freq': 1, 'moe_intermediate_size': 32, 'num_experts': 4,
     'num_experts_published': 16, 'experts_held': [4, 8],
     'num_experts_per_token': 4, 'num_shared_experts': 1,
     'moe_renormalize': True, 'moe_router_activation_func': 'sigmoid',
     'routed_scaling_factor': 2.446, 'num_expert_group': 1, 'topk_group': 1,
     'use_grouped_topk': True, 'num_nextn_predict_layers': 0,
     'model_type': 'kimi_linear', 'initializer_range': 0.02}

# float32 program against a float32 reference on the CPU: the two differ
# in the order of their sums alone (chunked against token by token, the
# latent against expanded K and V, pages against one sequence, one dense
# product over the held experts against a loop). 5 layers leave logits
# that reach 0.7 within 2e-6 of each other.
LOGIT_TOL = 2e-5
# a served token may be another than the reference's first only where
# two reference logits lie within the program's own error of each other
GAP_TOL = 2e-5


@pytest.fixture(scope='module')
def family():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        'kimi_linear_family', os.path.join(BENCH, 'families',
                                           'kimi_linear.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def served(family):
    """(the program's model holding seeded weights, the same weights for
    the reference)."""
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


def _leaves(stacked, layer, pre):
    """A layer's leaves under `pre` ('mixer.' | 'mlp.'), by their names
    inside it, as the reference's pieces take them."""
    head = 'model.layers.%d.%s' % (layer, pre)
    return {k[len(head):]: v for k, v in stacked.items()
            if k.startswith(head)}


# ---- the rule ---------------------------------------------------------------

def _rule_inputs(seed, b, t, h=3, dk=8, dv=8, decay=0.7):
    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.randn(*s).astype(np.float32)
    return (unit(f(b, t, h, dk)) * dk ** -0.5, unit(f(b, t, h, dk)),
            f(b, t, h, dv),
            -decay * rng.rand(b, t, h, dk).astype(np.float32),
            rng.rand(b, t, h).astype(np.float32), f(b, h, dk, dv))


def _recurrence(q, k, v, g, beta, state):
    outs = []
    for i in range(q.shape[1]):
        o, state = K.kda_step(q[:, i], k[:, i], v[:, i], g[:, i],
                              beta[:, i], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize('length,cuts', [
    (64, ()), (150, ()), (7, ()), (200, (64, 130)), (96, (5, 17, 80))])
def test_chunked_rule_equals_the_recurrence(length, cuts):
    """... over whole chunks, a ragged tail, fewer tokens than a
    sub-block, and in several calls with the state carried between them.
    float32, sums in another order: 1e-5 on outputs of order one."""
    q, k, v, g, beta, s0 = _rule_inputs(length, 2, length)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    got, s = [], s0
    for lo, hi in zip((0,) + cuts, cuts + (length,)):
        o, s = K.chunked_kda_rule(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                                  g[:, lo:hi], beta[:, lo:hi], s)
        got.append(o)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want_o, atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5)


def test_chunked_rule_survives_channels_that_decay_to_nothing():
    """alpha = 0.05 on some channels of every token of two whole chunks
    (and 0.999 on others): exp(-G) of such a channel passes float32's
    largest after 30 tokens, so a chunk worked with one reference point
    gives inf or nan; in sub-blocks no exponent is positive. Finite, and
    the recurrence's numbers."""
    q, k, v, g, beta, s0 = _rule_inputs(3, 2, 128)
    g = np.broadcast_to(np.where(np.arange(8) % 2, np.log(0.05),
                                 np.log(0.999)).astype(np.float32), g.shape)
    assert float(np.cumsum(g, axis=1).min()) < -350     # e^350: no float32
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    o, s = K.chunked_kda_rule(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5)


def test_a_padded_tail_is_masked_through_its_gates():
    """Positions past `valid` take no part: the state after 37 real
    tokens padded to 48 is the state after the 37."""
    q, k, v, g, beta, s0 = _rule_inputs(5, 2, 48)
    real = jnp.arange(48)[None, :] < jnp.asarray([37, 48])[:, None]
    gm, bm = K._mask_gates(jnp.asarray(g), jnp.asarray(beta), real)
    _, s = K.chunked_kda_rule(q, k, v, gm, bm, s0)
    _, want = K.chunked_kda_rule(q[:1, :37], k[:1, :37], v[:1, :37],
                                 g[:1, :37], beta[:1, :37], s0[:1])
    np.testing.assert_allclose(s[:1], want, atol=1e-5)


# ---- latent attention -------------------------------------------------------

def test_attending_in_the_latent_equals_expanded_keys_and_values():
    """One query a row against 40 held rows, rows as wide as a pool
    (zeros past [c; k_pe]): q_nope folded through W_kvb's key half and
    the weighted sum of c lifted by its value half, against K and V
    expanded for every row."""
    rng = np.random.RandomState(2)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    heads, nope, rope, dv, lora, held = 4, 8, 4, 8, 16, 40
    q = f(3, 1, heads, nope + rope)
    rows = jnp.pad(f(3, held, lora + rope), ((0, 0), (0, 0), (0, 12)))
    w = f(lora, heads, nope + dv) * 0.3
    mask = jnp.where(jnp.arange(held)[None, None] <= jnp.asarray(
        [39, 12, 0])[:, None, None], 0.0, -1e9)
    a, b = (K._latent_attend(q, rows, w, mask, nope, lora, absorb)
            for absorb in (True, False))
    assert a.shape == (3, 1, heads, dv)
    np.testing.assert_allclose(a, b, atol=2e-5)


# ---- the expert layer -------------------------------------------------------

def _moe_layer(family, stacked, layer, experts_held, published=16):
    """The program's expert layer holding `experts_held` of the uncut
    layer's weights `stacked` (a full set of `published` experts)."""
    cfg = K.KimiLinearConfig(**dict(
        {k: M[k] for k in family.PUBLISHED_KEYS},
        num_experts=published, experts_held=experts_held))
    moe = K.KimiSparseMoe(cfg)
    lo, hi = experts_held
    for name, p in moe.named_parameters():
        arr = stacked['model.layers.%d.mlp.%s' % (layer, name)]
        p._data = arr[lo:hi] if arr.shape[0] == published and \
            name in ('gate_proj', 'up_proj', 'down_proj') else arr
    return moe


@pytest.fixture(scope='module')
def uncut(family):
    """Weights of the model with ALL 16 experts held."""
    whole = dict(M, num_experts=16, experts_held=[0, 16])
    return whole, family.make_stacked(whole, 5, 'float32')


def test_the_four_shares_add_up_to_the_uncut_layer(family, uncut):
    """The routed parts that experts 0-3, 4-7, 8-11 and 12-15 give, plus
    the shared expert once, are the uncut reference layer's output; and
    each share alone is the reference's for that share."""
    from benchlib import reference
    whole, stacked = uncut
    x = jnp.asarray(np.random.RandomState(0).randn(2, 24, 64), jnp.float32)
    p = _leaves(stacked, 2, 'mlp.')
    with reference.highest():
        want = family.expert_layer(x, p, family.sizes(whole))
    total = 0.0
    for lo in (0, 4, 8, 12):
        moe = _moe_layer(family, stacked, 2, (lo, lo + 4))
        routed, counters = moe._routed(
            x, jnp.ones((2, 24), bool),
            *(w._data for w in (moe.router, moe.e_score_correction_bias,
                                moe.gate_proj, moe.up_proj, moe.down_proj)))
        with reference.highest():
            share = dict(p, **{k: p[k][lo:lo + 4] for k in
                               ('gate_proj', 'up_proj', 'down_proj')})
            ref = family.expert_layer(
                x, share, family.sizes(whole, (lo, lo + 4)), shared=False)
        np.testing.assert_allclose(routed, ref, atol=2e-6)
        total = total + routed
        assert int(counters['moe_pairs']) == 2 * 24 * 4
    shared = moe.shared(paddle.to_tensor(np.asarray(x)))._data
    np.testing.assert_allclose(total + shared, want, atol=5e-6)
    # ... and over the four shares every chosen pair was counted once
    held = sum(int(_moe_layer(family, stacked, 2, (lo, lo + 4))._routed(
        x, jnp.ones((2, 24), bool), *(stacked['model.layers.2.mlp.' + n]
                                      [slice(lo, lo + 4) if n.endswith(
                                          '_proj') else slice(None)]
                                      for n in ('router',
                                                'e_score_correction_bias',
                                                'gate_proj', 'up_proj',
                                                'down_proj')))[1]
        ['moe_pairs_held']) for lo in (0, 4, 8, 12))
    assert held == 2 * 24 * 4


def test_no_token_is_dropped_when_every_choice_falls_on_one_expert(
        family, uncut):
    """The correction sends all 4 choices of every token to experts 4-7,
    and this layer holds expert 5 alone: it takes all 48 tokens (12
    times an even share; a capacity would drop most) and gives the
    reference's numbers."""
    from benchlib import reference
    whole, stacked = uncut
    stacked = dict(stacked)
    bias = np.zeros(16, np.float32)
    bias[4:8] = 10.0
    stacked['model.layers.2.mlp.e_score_correction_bias'] = jnp.asarray(bias)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 24, 64), jnp.float32)
    moe = _moe_layer(family, stacked, 2, (5, 6))
    y, counters = moe(paddle.to_tensor(np.asarray(x)),
                      jnp.ones((2, 24), bool))
    assert {k: int(v) for k, v in counters.items()} == {
        'moe_pairs': 192, 'moe_pairs_held': 48, 'moe_experts_touched': 1,
        'moe_load_max': 48}
    p = _leaves(stacked, 2, 'mlp.')
    p.update({k: p[k][5:6] for k in ('gate_proj', 'up_proj', 'down_proj')})
    with reference.highest():
        want = family.expert_layer(x, p, family.sizes(whole, (5, 6)))
    np.testing.assert_allclose(y._data, want, atol=5e-6)


def test_a_token_none_of_whose_experts_is_held_gets_the_shared_alone(
        family, uncut):
    whole, stacked = uncut
    stacked = dict(stacked)
    bias = np.zeros(16, np.float32)
    bias[8:12] = 10.0
    stacked['model.layers.2.mlp.e_score_correction_bias'] = jnp.asarray(bias)
    x = paddle.to_tensor(np.random.RandomState(1).randn(1, 6, 64)
                         .astype(np.float32))
    moe = _moe_layer(family, stacked, 2, (0, 4))
    y, counters = moe(x, jnp.ones((1, 6), bool))
    assert int(counters['moe_pairs_held']) == 0 == \
        int(counters['moe_experts_touched'])
    np.testing.assert_allclose(y._data, moe.shared(x)._data, atol=1e-7)
    # the normal path gives the same, and no counters
    np.testing.assert_allclose(moe(x)._data, y._data, atol=1e-7)


# ---- each layer and the whole model against the reference -------------------

@pytest.mark.parametrize('layer,kind', [(0, 'kda'), (3, 'mla')])
def test_a_mixer_equals_the_reference(family, served, layer, kind):
    from benchlib import reference
    model, stacked = served
    x = np.random.RandomState(layer).randn(2, 70, 64).astype(np.float32)
    got = model.model.layers[layer].mixer(paddle.to_tensor(x))._data
    ref = {'kda': family.kda_mixer, 'mla': family.mla_mixer}[kind]
    with reference.highest():
        want = ref(jnp.asarray(x), _leaves(stacked, layer, 'mixer.'),
                   family.sizes(M))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_model_equals_the_reference(family, served):
    """Whole forward, the normal path (`caches=None`)."""
    from benchlib import reference
    model, stacked = served
    ids = np.random.RandomState(1).randint(0, 211, (2, 90)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._data
    with reference.highest():
        want = family.forward_logits(stacked, M, jnp.asarray(ids))
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


def test_config_refuses_what_is_not_built():
    base = {k: M[k] for k in M if k not in (
        'num_experts_published', 'experts_held')}
    for key, value in (('q_lora_rank', 32), ('mla_use_nope', False),
                       ('num_expert_group', 2), ('moe_layer_freq', 2)):
        with pytest.raises(NotImplementedError):
            K.KimiLinearConfig(**dict(base, **{key: value}))
    with pytest.raises(ValueError):
        K.KimiLinearConfig(**dict(base, experts_held=(2, 9)))
    with pytest.raises(ValueError):       # layer 4 named by neither list
        K.KimiLinearConfig(**dict(base, linear_attn_config=dict(
            M['linear_attn_config'], full_attn_layers=[])))


# ---- behind the engine ------------------------------------------------------

def test_cache_specs_and_the_state_they_build(served):
    model, _ = served
    specs = model.cache_specs()
    kinds = [type(s).__name__ for s in specs]
    assert kinds == ['RecurrentSpec'] * 3 + ['PagedLatentSpec',
                                             'RecurrentSpec']
    assert specs[3] == cache_mod.PagedLatentSpec(20, 'float32')
    assert kv_cache.kv_row_bytes(specs) == 20 * 4 == \
        kv_cache.latent_row_bytes(specs)
    assert kv_cache.state_bytes_per_seq(specs) == \
        4 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    pools = kv_cache.build_paged_pools(model, 6, 8, num_seqs=3)
    assert pools[3][0].shape == (1, 48, 128)      # 20 -> whole lanes
    assert pools[0][0].shape == (3, 4, 8, 8) and \
        pools[0][1].shape == (3, 3, 96)


def test_kv_row_bytes_at_the_published_widths():
    specs = [cache_mod.PagedLatentSpec(576, 'bfloat16'),
             cache_mod.RecurrentSpec(
                 arrays=(((32, 128, 128), 'float32'),
                         ((3, 12288), 'bfloat16')))]
    assert kv_cache.kv_row_bytes(specs) == 1152
    assert kv_cache.state_bytes_per_seq(specs) == 2097152 + 73728
    assert cache_mod.latent_pool_shape(576, 20480, 16) == (1, 327680, 640)


@pytest.mark.parametrize('batch,capacity,rows,read', [
    (24, 1024, 512 * 16, 'pool'), (1, 1024, 512 * 16, 'gather'),
    (16, 8192, 2432 * 16, 'pool'), (1, 8192, 2432 * 16, 'gather'),
    (8, 64, 8 * 8 * 8 + 8, 'gather')])
def test_the_kv_read_of_the_other_models_is_chosen_as_before(
        batch, capacity, rows, read):
    """GPT-2 XL's and Olmo-Hybrid's decode batches attend over the pool,
    their one-row prefill chunks gather: the latent read (always a
    sequence's own rows) did not touch the rule."""
    assert cache_mod.paged_kv_read(batch, capacity, rows) == read


def test_prefill_in_several_calls_then_decode_equals_the_full_forward(
        family, served):
    """Prompts that are no multiple of the chunk (1, 2 and 3 calls), more
    requests than slots, unequal budgets: every served token is the
    reference's first at its position, through three kinds of state."""
    model, stacked = served
    eng = _engine(model)
    prompts = _prompts(0, (5, 23, 37, 16, 40))
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, (9, 12, 7, 10, 5))]
    eng.run()
    outs = [r.tokens for r in reqs]
    assert [len(o) for o in outs] == [9, 12, 7, 10, 5]
    assert _widest_gap(family, stacked, prompts, outs) <= GAP_TOL
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}
    assert eng.kv_read == {'prefill': 'gather', 'decode': 'gather'}
    # ... and equals the model's own full forward, token for token
    for p, o in zip(prompts, outs):
        lg = np.asarray(model(paddle.to_tensor(
            np.asarray([p + o], np.int32)))._data)[0]
        want = lg[len(p) - 1:len(p) + len(o) - 1].argmax(-1)
        assert [int(t) for t in want] == o


def test_the_engine_refuses_prefix_cache_and_speculation(served):
    model, _ = served
    with pytest.raises(ValueError, match='prefix_cache=True with a '
                                         'recurrent layer'):
        _engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match='spec_k=2 with a recurrent layer'):
        _engine(model, spec_k=2)


def test_a_step_says_what_the_routing_did(served):
    """`serving.step` carries `latent_bytes` at entry and, after a decode
    burst, the device counters of the expert layers, fetched with the
    tokens; the gauges hold the same."""
    model, _ = served
    tracer = Tracer(recorder=FlightRecorder(capacity=512), enabled=True)
    prev = set_default_tracer(tracer)
    try:
        eng = _engine(model)
        eng.generate(_prompts(3, (9, 20, 30)), max_new_tokens=9)
    finally:
        set_default_tracer(prev)
    steps = [s for s in tracer.recorder.spans() if s['name'] == 'serving.step']
    burst = [s['tags'] for s in steps if 'moe_pairs' in s['tags']]
    assert burst and all('latent_bytes' in s['tags'] for s in steps)
    assert max(s['tags']['latent_bytes'] for s in steps) \
        == max(s['tags']['pages_in_use'] for s in steps) * 8 * 20 * 4
    for t in burst:
        # 4 expert layers, 4 choices a token; 4 of 16 experts held
        assert t['moe_pairs'] % 16 == 0 and 0 < t['moe_pairs'] <= 3 * 4 * 16
        assert 0 <= t['moe_pairs_held'] <= t['moe_pairs']
        assert t['moe_experts_touched'] <= 4 * 4 * 4
        assert 0 <= t['moe_load_max'] <= 3
    total = lambda k: sum(t[k] for t in burst)
    assert 0.1 < total('moe_pairs_held') / total('moe_pairs') < 0.4
    gauge = eng.metrics.registry.get('serving_layer_counter')
    assert gauge.labels('moe_pairs').value() == burst[-1]['moe_pairs']
    assert eng.metrics.registry.get('serving_latent_bytes').value() == 0
    # the scopes the program's device ops are named by
    text = eng._decode_jit.lower(*eng._decode_args).compile().as_text()
    pre = eng._prefill_jit.lower(
        eng._params, eng._bufs, eng._pools, eng.scheduler.block_tables[:1],
        np.zeros((1,), np.int32), np.zeros((1, 16), np.int32), np.int32(1),
        np.zeros((2,), np.uint32), np.float32(1), np.int32(0),
        np.asarray(False), np.int32(0)).compile().as_text()
    for scope in ('kda.conv', 'kda.step', 'kda.out', 'mla.proj',
                  'mla.absorb', 'mla.core', 'moe.route', 'moe.experts',
                  'moe.shared'):
        assert 'kimi.' + scope in text, scope
    assert 'kimi.kda.rule' in pre and 'kimi.kda.step' not in pre
    assert 'kimi.mla.expand' in pre and 'kimi.mla.absorb' not in pre


def test_a_frozen_lane_keeps_its_state_and_counts_nothing(served):
    """One request decoding beside two empty slots: the counters count
    its tokens alone."""
    model, _ = served
    eng = _engine(model)
    req = eng.add_request(_prompts(4, (12,))[0], max_new_tokens=6)
    eng.run()
    assert len(req.tokens) == 6
    # the last burst: at most 4 steps of ONE real token, 4 expert layers
    assert eng._burst_counters['moe_pairs'] <= 4 * 4 * 4
