"""Trinity-Large: rotary positions on the sliding layers only, grouped K/V
heads in the shared paged read and in the window's ring, the ring itself
(wrapping in prefill and in decode, frozen rows left bit for bit), each
layer and the whole model against the plain reference of
`benchmarks/families/trinity.py`, and the model behind
PagedContinuousBatchingEngine: logits of prefill then decode through the
rings and the pages against the reference's full forward, preemption by
recomputation, and the counters of what a window layer holds and reads.

Sizes: hidden 64, 5 layers (S S S S F, the first with a dense MLP), 6
query heads of 16 on 1 K/V head (6:1) or on 6 (1:1), a window of 16
with prefill chunks of 8 and prompts of up to 40, so that the ring wraps
in prefill and in decode; 16 experts of which 4 are held, float32.
Tolerances, each with its reason, are beside the comparisons.
"""
import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import PagedContinuousBatchingEngine, kv_cache
from paddle_tpu.text.models import cache as cache_mod
from paddle_tpu.text.models import trinity as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, 'benchmarks')
S, F = T.SLIDING, T.FULL
W = 16

M = {'vocab_size': 211, 'hidden_size': 64, 'intermediate_size': 128,
     'num_hidden_layers': 5, 'num_attention_heads': 6,
     'num_key_value_heads': 1, 'head_dim': 16, 'hidden_act': 'silu',
     'rms_norm_eps': 1e-5, 'tie_word_embeddings': False,
     'max_position_embeddings': 256, 'layer_types': [S, S, S, S, F],
     'global_attn_every_n_layers': 4, 'sliding_window': W,
     'rope_theta': 10000, 'rope_scaling': None, 'num_dense_layers': 1,
     'moe_intermediate_size': 32, 'num_experts': 4,
     'num_experts_published': 16, 'experts_held': [4, 8],
     'num_experts_per_tok': 4, 'num_shared_experts': 1, 'route_norm': True,
     'route_scale': 2.448, 'score_func': 'sigmoid', 'n_group': 1,
     'topk_group': 1, 'num_expert_groups': 1, 'num_limited_groups': 1,
     'mup_enabled': True, 'use_grouped_mm': True,
     'load_balance_coeff': 5e-5, 'model_type': 'afmoe',
     'initializer_range': 0.02}
GROUPED = {'6:1': 1, '1:1': 6}

# float32 program against a float32 reference on the CPU: the two differ
# in the order of their sums alone (ring rows against a causal block,
# pages against one sequence, one dense product over the held experts
# against a loop, the grouped read against repeated heads). Logits reach
# 0.5; 5 layers leave them within 3e-6 of each other.
LOGIT_TOL = 2e-5
# a served token may be another than the reference's first only where
# two reference logits lie within the program's own error of each other
GAP_TOL = 2e-5


@pytest.fixture(scope='module')
def family():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        'trinity_family', os.path.join(BENCH, 'families', 'trinity.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module', params=sorted(GROUPED))
def served(family, request):
    """(the program's model holding seeded weights, the same weights for
    the reference, the model section), 6:1 and 1:1."""
    m = dict(M, num_key_value_heads=GROUPED[request.param])
    model = family.build_model(
        m, 'float32', family.program_leaves(
            family.make_stacked(m, 11, 'float32')))
    model.eval()
    return model, family.make_stacked(m, 11, 'float32'), m


def _engine(model, **kw):
    args = dict(num_seqs=3, max_len=96, page_size=8, prefill_chunk=8,
                decode_block=4, prefix_cache=False)
    args.update(kw)
    return PagedContinuousBatchingEngine(model, **args)


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, M['vocab_size'], n)]
            for n in lengths]


def _leaves(stacked, layer, pre):
    head = 'model.layers.%d.%s' % (layer, pre)
    return {k[len(head):]: v for k, v in stacked.items()
            if k.startswith(head)}


# ---- positions and the attention layers -------------------------------------

def test_rope_equals_the_reference(family):
    """The rotate-half form over all channels, at absolute positions:
    the program's at positions 0 .. T-1 of each row is the reference's."""
    x = jnp.asarray(np.random.RandomState(0).randn(2, 300, 3, 16),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(300), (2, 300))
    np.testing.assert_allclose(T.rope(x, pos, 10000.0),
                               family.rope(x, 10000.0), atol=2e-5)
    # ... and a row that starts at 200 takes the angles of 200 on
    got = T.rope(x[:, 200:], pos[:, 200:], 10000.0)
    np.testing.assert_allclose(got, family.rope(x, 10000.0)[:, 200:],
                               atol=2e-5)


@pytest.mark.parametrize('layer', [0, 4], ids=['sliding', 'full'])
def test_an_attention_layer_equals_the_reference_and_rotates_only_sliding(
        family, served, layer):
    """The normal path of a sliding and of the full layer against the
    reference's; over 12 tokens, inside the window, the two kinds differ
    by the rotation alone, and the reference with the other kind's
    positions is far from the program."""
    from benchlib import reference
    model, stacked, m = served
    sliding = m['layer_types'][layer] == S
    x = np.random.RandomState(layer).randn(2, 12, 64).astype(np.float32)
    got = model.model.layers[layer].self_attn(paddle.to_tensor(x))._data
    p = _leaves(stacked, layer, 'self_attn.')
    with reference.highest():
        want, other = (family.attention(jnp.asarray(x), p, family.sizes(m),
                                        kind) for kind in (sliding,
                                                           not sliding))
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(got - other).max()) > 100 * 2e-6


def test_the_model_equals_the_reference(family, served):
    """Whole forward, the normal path (`caches=None`), 70 tokens: past
    the window."""
    from benchlib import reference
    model, stacked, m = served
    ids = np.random.RandomState(1).randint(0, 211, (2, 70)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._data
    with reference.highest():
        want = family.forward_logits(stacked, m, jnp.asarray(ids))
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


def test_config_refuses_what_is_not_built():
    base = {k: M[k] for k in M if k not in (
        'num_experts_published', 'experts_held')}
    for key, value in (('rope_scaling', {'type': 'yarn'}), ('n_group', 2),
                       ('score_func', 'softmax'),
                       ('tie_word_embeddings', True)):
        with pytest.raises(NotImplementedError):
            T.TrinityConfig(**dict(base, **{key: value}))
    with pytest.raises(ValueError):
        T.TrinityConfig(**dict(base, experts_held=(2, 19), num_experts=16))
    with pytest.raises(ValueError):
        T.TrinityConfig(**dict(base, num_key_value_heads=4))
    # the published pattern: three sliding layers, then a full one
    assert T.TrinityConfig().layer_types[:8] == [S, S, S, F] * 2


# ---- the shared paged read with grouped heads -------------------------------

def _packed(rows):
    """`[R, heads, 16]` -> the pool `[1, R, 128]` (`paged_pool_shape`:
    heads of 16 side by side in a row of 128 lanes, zeros after)."""
    r, h, dh = rows.shape
    return jnp.pad(rows, ((0, 0), (0, 8 - h), (0, 0))).reshape(1, r, 8 * dh)


def test_grouped_heads_read_the_pool_as_repeated_heads_would():
    """6 query heads on 2 K/V heads through `paged_attention`, both reads
    (the pool in place, each row's gathered view), against the same K/V
    repeated to 6 heads in a pool of 6: the pool holds each K/V head
    once, and the four outputs agree."""
    rng = np.random.RandomState(3)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    b, n, page = 3, 5, 8
    q, k, v = f(b, n, 6, 16), f(b, n, 2, 16), f(b, n, 2, 16)
    lens = jnp.asarray([0, 9, 17], jnp.int32)
    bt = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 9]], jnp.int32)
    held = f(16 * page, 2, 16), f(16 * page, 2, 16)
    outs = []
    for pages, read in ((12, 'pool'), (16, 'gather')):
        for rep in (1, 3):
            rows = [jnp.repeat(x[:pages * page], rep, axis=1) for x in held]
            pools = [_packed(x) for x in rows]
            assert pools[0].shape == cache_mod.paged_pool_shape(
                2 * rep, 16, pages, page)
            cache = cache_mod.PagedKVCache(
                paddle.to_tensor(pools[0]), paddle.to_tensor(pools[1]), bt,
                lens, page)
            out, new = cache_mod.paged_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                cache, 'test')
            assert new.kv_read == read
            outs.append(np.asarray(out._data))
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], atol=1e-5)


@pytest.mark.parametrize('block', [None, 1], ids=['one-block', 'page-blocks'])
def test_the_held_pages_read_equals_the_gathered_view(monkeypatch, block):
    """A decode call of `paged_attention(..., held=True)` through the
    Pallas kernel (the interpreter here) against the same call's gathered
    view: rows of 0, 17 and 63 tokens (the last at capacity), a page two
    rows share, scratch entries past a row's pages; 12 query heads on 2
    K/V heads of 128; a row's pages in one block of the kernel, or a
    block a page (the softmax carried across blocks). Values are whole bfloat16s, so the kernel's cast of
    K and V to bfloat16 changes nothing and the two agree to float32's
    order of sums. The pools come back as the write left them. Where no
    kernel runs (the CPU without the interpreter) the shape rule
    chooses, as without `held`."""
    rng = np.random.RandomState(4)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(jnp.bfloat16)
                               .astype(np.float32))
    b, page, pages = 3, 16, 13
    q, k, v = f(b, 1, 12, 128), f(b, 1, 2, 128), f(b, 1, 2, 128)
    lens = jnp.asarray([0, 17, 63], jnp.int32)
    bt = jnp.asarray([[3, 0, 0, 0], [3, 5, 0, 0], [7, 8, 9, 10]], jnp.int32)
    pools = f(2, pages * page, 128), f(2, pages * page, 128)
    cache = lambda: cache_mod.PagedKVCache(
        paddle.to_tensor(pools[0]), paddle.to_tensor(pools[1]), bt, lens,
        page)
    out, new = cache_mod.paged_attention(q, k, v, cache(), 'test', held=True)
    assert new.kv_read == 'gather'
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    if block:
        monkeypatch.setattr(cache_mod, '_block_pages', lambda nb: block)
    cache_mod._paged_call.clear_cache()     # traced anew, with the block
    held, new_held = cache_mod.paged_attention(q, k, v, cache(), 'test',
                                               held=True)
    assert new_held.kv_read == 'pages'
    np.testing.assert_allclose(np.asarray(held._data),
                               np.asarray(out._data), atol=1e-5)
    for a, c in ((new.k, new_held.k), (new.v, new_held.v)):
        np.testing.assert_array_equal(np.asarray(a._data),
                                      np.asarray(c._data))


# ---- the window's ring -----------------------------------------------------

def _rings(seed, b, g=2, dh=16):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, W, g * dh).astype(np.float32))
                 for _ in range(2))


@pytest.mark.parametrize('n', [8, 1], ids=['chunk', 'decode'])
def test_rows_past_valid_leave_the_ring_bit_for_bit(n):
    """A call's real rows (i < valid) land at (t + i) mod W; every other
    row of the ring, and the whole ring of a lane that took nothing, is
    as it was, bit for bit."""
    rng = np.random.RandomState(5)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    t = np.asarray([5, 20, 30], np.int32)
    valid = np.asarray([min(3, n), 0, n], np.int32)
    q, k, v = f(3, n, 6, 16), f(3, n, 2, 16), f(3, n, 2, 16)
    kr, vr = _rings(6, 3)
    cache = cache_mod.RecurrentCache((kr, vr), jnp.asarray(t),
                                     jnp.asarray(valid))
    _, new = cache_mod.window_attention(q, k, v, cache, 'test')
    for ring, old, new_rows in ((new.arrays[0], kr, k),
                                (new.arrays[1], vr, v)):
        ring, old = np.asarray(ring), np.asarray(old)
        for lane in range(3):
            written = {(t[lane] + i) % W: i for i in range(valid[lane])}
            for row in range(W):
                if row in written:
                    np.testing.assert_array_equal(
                        ring[lane, row], np.asarray(new_rows)[
                            lane, written[row]].reshape(-1))
                else:
                    np.testing.assert_array_equal(ring[lane, row],
                                                  old[lane, row])
    counted = {k: int(v) for k, v in new.counters.items()}
    assert counted['window_rows_held_max'] == W
    assert counted['window_rows_read_max'] == W + n


def test_a_chunk_longer_than_the_ring_keeps_its_last_window():
    """24 real tokens into a ring of 16: rows hold the last 16 of them,
    and what a query reads is the keys of the last W positions."""
    rng = np.random.RandomState(7)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q, k, v = f(1, 24, 2, 16), f(1, 24, 2, 16), f(1, 24, 2, 16)
    kr, vr = _rings(8, 1)
    cache = cache_mod.RecurrentCache((kr, vr), jnp.asarray([3]),
                                     jnp.asarray([24]))
    out, new = cache_mod.window_attention(q, k, v, cache, 'test')
    ring = np.asarray(new.arrays[0])[0]
    for i in range(8, 24):
        np.testing.assert_array_equal(ring[(3 + i) % W],
                                      np.asarray(k)[0, i].reshape(-1))
    # query 23 (position 26) sees positions 11 .. 26: tokens 8 .. 23
    s = np.einsum('hd,mhd->hm', np.asarray(q)[0, 23],
                  np.asarray(k)[0, 8:]) / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum('hm,mhd->hd', p, np.asarray(v)[0, 8:])
    np.testing.assert_allclose(np.asarray(out._data)[0, 23], want,
                               atol=1e-5)


# ---- behind the engine ------------------------------------------------------

def test_cache_specs_and_the_state_they_build(served):
    model, _, m = served
    g = m['num_key_value_heads']
    specs = model.cache_specs()
    assert specs[:4] == [cache_mod.WindowKVSpec(g, 16, W, 'float32')] * 4
    assert specs[4] == cache_mod.PagedKVSpec(g, 16, 'float32')
    # the rings are per-slot state, the full layer's rows are in pages
    assert kv_cache.state_bytes_per_seq(specs) == 4 * 2 * W * g * 16 * 4
    assert kv_cache.kv_row_bytes(specs) == 2 * g * 16 * 4
    pools = kv_cache.build_paged_pools(model, 6, 8, num_seqs=3)
    assert [a.shape for a in pools[0]] == [(3, W, g * 16)] * 2
    assert pools[4][0].shape == cache_mod.paged_pool_shape(g, 16, 6, 8)


def _served_logits(model, ids, prompt, chunk=8, page=8):
    """One sequence through the cache interface as the engine drives it:
    the first `prompt` ids in chunks of `chunk` (the last one padded),
    then one id a call; the logits at every position."""
    specs = kv_cache.cache_specs(model)
    pools = kv_cache.build_paged_pools(model, 16, page, num_seqs=1)
    bt = jnp.arange(1, 15, dtype=jnp.int32)[None]
    out, t = [], 0
    while t < len(ids):
        n = chunk if t < prompt else 1
        part = ids[t:min(t + n, prompt) if t < prompt else t + 1]
        x = np.zeros((1, n), np.int32)
        x[0, :len(part)] = part
        caches = kv_cache.layer_caches(specs, pools, bt, jnp.asarray([t]),
                                       jnp.asarray([len(part)]), page)
        lg, new = model(paddle.to_tensor(x), caches=caches)
        pools = kv_cache.layer_state(pools, new)
        out.append(np.asarray(lg._data)[0, :len(part)])
        t += len(part)
    return np.concatenate(out)


def test_prefill_then_decode_logits_equal_the_full_forward(family, served):
    """A prompt of 37 in five chunks of 8, the last of 5 real and 3
    padded (the ring of 16 wraps inside the prefill), then 30 tokens one
    at a time (and wraps again): every logit against the reference's
    full forward over the 67 tokens."""
    from benchlib import reference
    model, stacked, m = served
    ids = _prompts(2, (67,))[0]
    got = _served_logits(model, ids, 37)
    with reference.highest():
        want = np.asarray(family.forward_logits(
            stacked, m, jnp.asarray([ids])))[0]
    assert got.shape == want.shape == (67, 211)
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


def test_the_full_layer_decodes_through_the_kernel(family, monkeypatch):
    """Heads of 128 lanes, 6:1, the Pallas interpreter: the decode calls'
    full layer reads the pages it holds through the kernel, and every
    logit of prefill then decode is the reference's. The kernel reads K
    and V as bfloat16 (2^-9 of a value): decode logits move 1.4e-4 here
    where the float32 reads move 5e-7; a wrong page or row moves them by
    a tenth."""
    from benchlib import reference
    m = dict(M, head_dim=128)
    model = family.build_model(m, 'float32', family.program_leaves(
        family.make_stacked(m, 11, 'float32')))
    model.eval()
    stacked = family.make_stacked(m, 11, 'float32')
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    took = []
    kernel = cache_mod._held_pages
    monkeypatch.setattr(cache_mod, '_held_pages',
                        lambda *a: took.append(1) or kernel(*a))
    cache_mod._paged_call.clear_cache()
    ids = _prompts(2, (67,))[0]
    got = _served_logits(model, ids, 37)
    assert took
    with reference.highest():
        want = np.asarray(family.forward_logits(
            stacked, m, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got[:37], want[:37], atol=LOGIT_TOL)
    np.testing.assert_allclose(got[37:], want[37:], atol=1e-3)


def test_the_engine_serves_the_reference(family, served):
    """Prompts that pass the window (up to 40, chunks of 8), more
    requests than slots, unequal budgets: every served token is the
    reference's first at its position and the model's own argmax; the
    counters say what a window layer held and read."""
    from benchlib import reference
    model, stacked, m = served
    eng = _engine(model)
    prompts = _prompts(0, (40, 23, 9, 33, 17))
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, (24, 12, 20, 10, 5))]
    eng.run()
    outs = [r.tokens for r in reqs]
    assert [len(o) for o in outs] == [24, 12, 20, 10, 5]
    with reference.highest():
        gaps, _ = family.served_gaps(stacked, m, list(zip(prompts, outs)))
    assert max(float(g.max()) for g in gaps) <= GAP_TOL
    for p, o in zip(prompts, outs):
        lg = np.asarray(model(paddle.to_tensor(
            np.asarray([p + o], np.int32)))._data)[0]
        assert [int(t) for t in lg[len(p) - 1:-1].argmax(-1)] == o
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}
    assert eng.kv_read == {'prefill': 'gather', 'decode': 'gather'}
    counted = eng._burst_counters
    assert 0 < counted['window_rows_held_max'] <= W
    assert 0 < counted['window_rows_read_max'] <= W + 1
    assert counted['moe_pairs'] > 0


def test_a_preempted_request_recomputes_to_the_same_tokens(family, served):
    """Two residents mid-decode, their rings past the window; a higher
    priority arrival takes a slot: the victim comes back, recomputes
    its prompt and what it had made into a fresh ring, and every request
    gets what an uninterrupted run gave."""
    model, stacked, m = served
    prompts = _prompts(8, [30, 19, 25])
    eng = _engine(model, num_seqs=2, preempt=True)
    want = eng.generate(prompts, max_new_tokens=20)
    r0 = eng.add_request(prompts[0], max_new_tokens=20, priority=0)
    r1 = eng.add_request(prompts[1], max_new_tokens=20, priority=0)
    while min(len(r0.tokens), len(r1.tokens)) < 3:
        eng.step()                       # both residents mid-decode
    r2 = eng.add_request(prompts[2], max_new_tokens=20, priority=1)
    eng.run()
    assert eng.scheduler.preempted == 1
    assert [r0.tokens, r1.tokens, r2.tokens] == want
    assert eng.trace_counts == {'prefill': 1, 'decode': 1, 'verify': 0}


def test_the_engine_refuses_prefix_cache_and_speculation(served):
    model, _, _ = served
    with pytest.raises(ValueError, match='prefix_cache=True'):
        _engine(model, prefix_cache=True)
    with pytest.raises(ValueError, match='spec_k=2'):
        _engine(model, spec_k=2)


def test_the_decode_step_names_its_scopes(served):
    """The device ops of both programs carry the closed set's names."""
    model, _, _ = served
    eng = _engine(model)
    eng.generate(_prompts(3, (20, 9)), max_new_tokens=6)
    text = eng._decode_jit.lower(*eng._decode_args).compile().as_text()
    for scope in ('attn.qkv', 'attn.qk_norm', 'attn.rope',
                  'attn.window.core', 'attn.window.write',
                  'attn.full.paged_write', 'attn.full.core', 'attn.gate',
                  'moe.route', 'moe.experts', 'moe.shared', 'mlp',
                  'lm_head'):
        assert 'trinity.' + scope in text, scope


def test_the_spans_say_which_product_the_expert_layers_took(served):
    """Each program's spans carry `experts`, the product its expert layers
    took, which the shape rule gives for the program's rows alone (top 4
    of 16: a chunk of 16 touches nearly every held expert, a decode step
    of 3 slots about 58%), and a burst's counters the rows it
    multiplied."""
    from paddle_tpu.monitor.tracing import (FlightRecorder, Tracer,
                                            set_default_tracer)
    from paddle_tpu.text.models import moe
    model, _, _ = served
    tracer = Tracer(recorder=FlightRecorder(capacity=1024), enabled=True)
    prev = set_default_tracer(tracer)
    try:
        eng = _engine(model, prefill_chunk=16)
        eng.generate(_prompts(5, (20, 9)), max_new_tokens=6)
    finally:
        set_default_tracer(prev)
    want = {'prefill': 'dense', 'decode': 'grouped'}
    assert want == {p: moe.expert_path(n, 4, 16)
                    for p, n in (('prefill', 16), ('decode', 3))}
    assert eng.experts == want
    spans = tracer.recorder.spans()
    for name, program in (('serving.prefill_call', 'prefill'),
                          ('serving.decode_burst', 'decode')):
        tags = [s['tags'] for s in spans if s['name'] == name]
        assert tags and all(t['experts'] == want[program] for t in tags)
    # the grouped product's rows are whole tiles of 8
    rows = eng._burst_counters['moe_expert_rows']
    assert rows > 0 and rows % 8 == 0
