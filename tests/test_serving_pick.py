"""How the serving engine picks a token (serving/engine.py): a batch in
which no advancing row samples takes the argmax and nothing else, and a
row that samples finds its top-k threshold by exact selection — no
program sorts a vocabulary.

`GPTForCausalLM.generate()` keeps its own sorted top-k: tests/
test_serving.py compares the engine against it request by request. Here
the pieces are held one by one, against the sorted statement written out
below.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.monitor import MetricRegistry
from paddle_tpu.monitor.registry import set_default_registry
from paddle_tpu.monitor.tracing import (FlightRecorder, Tracer,
                                        set_default_tracer)
from paddle_tpu.serving import PagedContinuousBatchingEngine
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


def _sorted_threshold(lt, topk):
    """The semantics, by the sort the engine used to run."""
    v = lt.shape[-1]
    kth = jnp.sort(lt, axis=-1)[jnp.clip(v - topk, 0, v - 1)]
    return jnp.where(topk > 0, kth, -jnp.inf)


def _sorted_pick(lg, key, temp, topk, sample):
    """One row's pick as the engine made it before: both sides computed,
    one selected."""
    lg = lg.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lt = lg / jnp.maximum(temp, 1e-6)
    lt = jnp.where(lt >= _sorted_threshold(lt, topk), lt, -1e30)
    sampled = jax.random.categorical(key, lt).astype(jnp.int32)
    return jnp.where(sample, sampled, greedy)


_BOTH = jax.jit(lambda lt, k: (engine_mod._topk_threshold(lt, k),
                               _sorted_threshold(lt, k)))


def _row(v, k):
    """A row of `v` logits with ties planted AT the k-th largest value
    (and a few of either zero, infinities and a subnormal elsewhere)."""
    rng = np.random.RandomState(v % 1000 + min(k, 99))
    lt = (rng.standard_normal(v) * 4).astype(np.float32)
    lt[rng.choice(v, 6, replace=False)] = [0.0, -0.0, 1e-40, -1e-40,
                                            3e38, -3e38]
    kth = np.sort(lt)[np.clip(v - k, 0, v - 1)]
    lt[rng.choice(v, 5, replace=False)] = kth
    return lt


@pytest.mark.parametrize('v', [50257, 100352])
@pytest.mark.parametrize('k', [0, 1, 2, 5, 50, 'V-1', 'V', 'V+7'])
def test_threshold_by_selection_is_the_sorted_one_bit_for_bit(v, k):
    k = {'V-1': v - 1, 'V': v, 'V+7': v + 7}.get(k, k)
    lt = _row(v, k)
    got, want = _BOTH(lt, np.int32(k))
    assert np.asarray(got).view(np.uint32) == np.asarray(want).view(
        np.uint32), (got, want)
    if k == 0:
        assert got == -np.inf
    else:
        # ties at the threshold are kept: at least k survive `>=`
        assert (lt >= np.asarray(got)).sum() >= min(k, v)
        assert (lt > np.asarray(got)).sum() < min(k, v)


@pytest.mark.parametrize('flags', [(0, 0, 0, 0), (1, 1, 1, 1),
                                   (0, 1, 0, 1)],
                         ids=['greedy', 'sampled', 'mixed'])
def test_batch_pick_equals_the_sorted_pick_row_by_row(flags):
    """Same keys, same temperature and top-k: the tokens the parent's
    pick gives — whichever branch the batch takes, a greedy row in a
    sampling batch still gets its argmax."""
    rng = np.random.RandomState(11)
    lg = (rng.standard_normal((4, 211)) * 3).astype(np.float32)
    lg[:, 7] = lg[:, 9]                       # ties inside a row
    keys = np.asarray(jax.vmap(jax.random.PRNGKey)(np.arange(4)))
    temps = np.asarray([1.0, 0.7, 1.3, 0.9], np.float32)
    topks = np.asarray([0, 3, 0, 300], np.int32)
    sample = np.asarray(flags, bool)
    pick = jax.jit(engine_mod._pick_tokens)
    for _ in range(5):                        # five draws a row
        ks = jax.vmap(jax.random.split)(keys)
        keys, subs = np.asarray(ks[:, 0]), ks[:, 1]
        got = pick(lg, subs, temps, topks, sample)
        want = jax.vmap(_sorted_pick)(lg, subs, temps, topks, sample)
        assert np.array_equal(got, want)
        assert np.array_equal(np.asarray(got)[~sample],
                              lg.argmax(-1)[~sample])


@pytest.fixture(scope='module')
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def tracer():
    """A fresh tracer as the process default while the engine is built."""
    reg = MetricRegistry()
    tr = Tracer(registry=reg, recorder=FlightRecorder(registry=reg))
    prev_reg = set_default_registry(reg)
    prev_tr = set_default_tracer(tr)
    yield tr
    set_default_tracer(prev_tr)
    set_default_registry(prev_reg)


def _engine(model, **kw):
    return PagedContinuousBatchingEngine(model, num_seqs=2, max_len=64,
                                         page_size=8, prefill_chunk=8,
                                         decode_block=2, **kw)


def _sequential(model, prompt, mnt, **kw):
    out = model.generate(paddle.to_tensor([prompt]), max_new_tokens=mnt,
                         **kw)
    return [int(t) for t in out.numpy()[0][len(prompt):]]


def test_a_retired_lanes_stale_flag_does_not_choose_the_branch(
        model, tracer, monkeypatch):
    """A sampled request beside a greedy one that outlives it: while it
    advances the batch samples, each request equal to its own sequential
    run; once it has retired its lane still says `_sample` — and neither
    the program (the sampling side runs no more: counted where it runs)
    nor the `pick` tag takes notice."""
    ran = []
    inner = engine_mod._sample_token

    def counted(lg, *rest):
        jax.debug.callback(lambda _: ran.append(1), lg[0])
        return inner(lg, *rest)

    monkeypatch.setattr(engine_mod, '_sample_token', counted)
    eng = _engine(model)
    sampled = dict(do_sample=True, temperature=0.8, top_k=5, seed=42)
    short, long_ = [3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8]
    reqs = [eng.add_request(short, max_new_tokens=4, **sampled),
            eng.add_request(long_, max_new_tokens=12)]
    while not reqs[0].done:
        eng.step()
    jax.effects_barrier()
    assert ran
    slot = [s for s in range(2) if s not in eng._requests][0]
    assert eng._sample[slot] and not eng._active[slot]     # stale
    before = len(tracer.recorder.spans())
    del ran[:]
    eng.run()
    jax.effects_barrier()
    assert not ran
    assert reqs[0].tokens == _sequential(model, short, 4, **sampled)
    assert reqs[1].tokens == _sequential(model, long_, 12)
    spans = tracer.recorder.spans()
    picks = [s['tags']['pick'] for s in spans
             if s['name'] == 'serving.decode_burst']
    late = [s['tags']['pick'] for s in spans[before:]
            if s['name'] == 'serving.decode_burst']
    assert late and set(late) == {'argmax'}
    assert picks[0] == 'sample' and picks == sorted(picks, reverse=True)
    calls = {s['tags']['slot']: s['tags']['pick'] for s in spans
             if s['name'] == 'serving.prefill_call' and s['tags']['final']}
    assert sorted(calls.values()) == ['argmax', 'sample']
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}


@pytest.mark.parametrize('program', ['prefill', 'decode'])
def test_lowered_programs_sort_nothing_and_branch_on_the_batch(
        model, program):
    eng = _engine(model)
    eng.generate([[2, 7, 1, 8, 2, 8]], max_new_tokens=3)
    if program == 'decode':
        lowered = eng._decode_jit.lower(*eng._decode_args)
    else:
        lowered = eng._prefill_jit.lower(
            eng._params, eng._bufs, eng._pools,
            eng.scheduler.block_tables[:1], np.zeros((1,), np.int32),
            np.zeros((1, 8), np.int32), np.int32(8),
            np.zeros((2,), np.uint32), np.float32(1.0), np.int32(0),
            np.asarray(False))
    text = lowered.as_text()
    assert 'stablehlo.sort' not in text
    assert 'stablehlo.case' in text or 'stablehlo.if' in text
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}
