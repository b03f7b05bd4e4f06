"""Continuous-batching serving engine tests (paddle_tpu/serving/).

The two load-bearing assertions from the engine's contract:
  1. greedy tokens through the engine are IDENTICAL to sequential
     model.generate() for mixed-length prompts — continuous batching
     must not buy throughput with output drift, with prefix sharing and
     speculative decoding on or off, and with a pool under the demand;
  2. the compiled program set is FIXED and traces once per program
     across an arbitrary admit/retire workload — churn must never
     retrace (prefill + decode, or prefill + verify under speculation).
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (PageAllocator, PagedContinuousBatchingEngine,
                                PagedScheduler, ServingMetrics,
                                SlotAllocator)
from paddle_tpu.serving.metrics import percentile
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


@pytest.fixture(scope='module')
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope='module')
def prompts():
    rng = np.random.RandomState(3)
    # >= 8 mixed lengths, deliberately non-monotonic so admission order
    # and slot layout differ from length order
    return [[int(t) for t in rng.randint(0, 211, n)]
            for n in (3, 17, 7, 12, 5, 21, 9, 4, 14, 6)]


def _sequential(model, prompt, mnt, **kw):
    out = model.generate(paddle.to_tensor([prompt]), max_new_tokens=mnt,
                         **kw)
    return [int(t) for t in out.numpy()[0][len(prompt):]]


def _engine(model, **kw):
    """The engine at the suite's small shape; `kw` overrides."""
    args = dict(num_seqs=2, max_len=64, page_size=8, prefill_chunk=8,
                decode_block=4)
    args.update(kw)
    return PagedContinuousBatchingEngine(model, **args)


def _pools(small):
    """The default pool (every sequence at max_len: num_seqs * blocks + 1
    pages, prefix cache on) and one of `small` pages, under the test's
    demand, so that slots AND pages turn over and admission blocks on
    pages."""
    return pytest.mark.parametrize(
        'pool', [dict(), dict(num_pages=small, prefix_cache=False)],
        ids=['default_pool', 'small_pool_no_prefix'])


@_pools(10)
def test_greedy_parity_and_bounded_compilation(model, prompts, pool):
    """The acceptance bar: token-identical to generate() for mixed
    lengths with sequences << requests (forces admit/retire churn), the
    program set stays at the fixed prefill/decode pair, and every page
    returns to the free list or the prefix cache when the workload
    drains."""
    mnt = 11
    expect = [_sequential(model, p, mnt) for p in prompts]
    eng = _engine(model, num_seqs=3, **pool)
    reqs = [eng.add_request(p, max_new_tokens=mnt) for p in prompts]
    eng.run()
    assert [r.tokens for r in reqs] == expect
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}
    # every slot cycled through several occupants
    assert eng.allocator.in_use == 0
    assert eng.scheduler.pending == 0
    if eng.prefix is not None:
        # only prefix-cache references may outlive the requests
        assert eng.pages.in_use == len(eng.prefix)
    else:
        assert eng.pages.in_use == 0
        assert eng.num_pages - 1 < eng.num_slots * eng.num_blocks
        assert any('pages' in r._admit_waits for r in reqs)


@_pools(5)     # 4 usable pages: the 17-token prompt alone takes 3 of them
def test_sampling_stream_parity(model, prompts, pool):
    """Per-request PRNG streams mirror generate(): same seed, same
    temperature/top-k, same sampled tokens — page indirection must not
    perturb logits or key order."""
    mnt = 8
    kw = dict(do_sample=True, temperature=0.8, top_k=5, seed=42)
    expect = [_sequential(model, p, mnt, **kw) for p in prompts[:4]]
    eng = _engine(model, **pool)
    got = eng.generate(prompts[:4], max_new_tokens=mnt, **kw)
    assert got == expect


def test_per_request_sampling_params(model, prompts):
    """Requests with DIFFERENT sampling configs share the batch; each
    must match its own sequential run (the vectorized pick must not mix
    rows)."""
    specs = [dict(do_sample=False),
             dict(do_sample=True, temperature=0.7, top_k=3, seed=1),
             dict(do_sample=True, temperature=1.3, top_k=0, seed=9),
             dict(do_sample=False)]
    mnt = 7
    expect = [_sequential(model, p, mnt, **kw)
              for p, kw in zip(prompts, specs)]
    eng = _engine(model, num_seqs=4)
    reqs = [eng.add_request(p, max_new_tokens=mnt, **kw)
            for p, kw in zip(prompts, specs)]
    eng.run()
    assert [r.tokens for r in reqs] == expect


@pytest.mark.parametrize('page_size', [8, 16])
def test_slot_reuse_no_crosstalk(model, prompts, page_size):
    """A slot's next occupant sees none of the previous one: running the
    same workload at 2 slots (heavy reuse) and at 8 slots (no reuse)
    yields identical outputs."""
    mnt = 6
    outs = []
    for slots in (2, 8):
        eng = _engine(model, num_seqs=slots, page_size=page_size,
                      prefix_cache=False)
        outs.append(eng.generate(prompts[:8], max_new_tokens=mnt))
    assert outs[0] == outs[1]


def test_varied_budgets_and_immediate_finish(model, prompts):
    """max_new_tokens=1 finishes at prefill; longer budgets coexist in
    the same burst and each stops exactly at its own budget."""
    budgets = [1, 3, 9, 2]
    eng = _engine(model, num_seqs=4)
    reqs = [eng.add_request(p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    eng.run()
    for req, b, p in zip(reqs, budgets, prompts):
        assert len(req.tokens) == b
        assert req.tokens == _sequential(model, p, b)


def test_stream_yields_all_tokens(model, prompts):
    eng = _engine(model)
    req = eng.add_request(prompts[0], max_new_tokens=9, stream=True)
    streamed = list(eng.stream(req))
    assert streamed == req.tokens
    assert streamed == _sequential(model, prompts[0], 9)


@pytest.mark.slow
def test_thread_safe_front_door(model, prompts):
    """Several threads submit and drive concurrently; every request
    still matches its sequential run (the lock serializes steps, the
    outputs prove no cross-talk)."""
    mnt = 5
    expect = [_sequential(model, p, mnt) for p in prompts[:6]]
    eng = _engine(model, num_seqs=3)
    results = [None] * 3
    def worker(i):
        results[i] = eng.generate(prompts[2 * i:2 * i + 2],
                                  max_new_tokens=mnt)
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = [tok for pair in results for tok in pair]
    assert got == expect
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}


def test_admission_validation(model):
    eng = _engine(model, max_len=32, decode_block=2)
    with pytest.raises(ValueError, match='empty prompt'):
        eng.add_request([], max_new_tokens=4)
    with pytest.raises(ValueError, match='max_new_tokens'):
        eng.add_request([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match='cache rows'):
        eng.add_request(list(range(30)), max_new_tokens=8)   # 30+8-1 > 32
    # prompt + budget fit but the PADDED last prefill chunk might not
    # (a prefix hit mid-chunk shifts the chunk grid: up to 24 + 7 > 30):
    # a clamped write would silently corrupt rows
    eng30 = _engine(model, max_len=30, decode_block=2)
    with pytest.raises(ValueError, match='cache rows'):
        eng30.add_request(list(range(24)), max_new_tokens=2)
    # capacity errors must not wedge the queue for valid requests
    req = eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run()
    assert len(req.tokens) == 2


def test_front_door_rejects_unservable_worst_case(model):
    """The submission-time guard: a request whose worst case (prompt +
    budget - 1) exceeds max_len gets a clear ValueError naming max_len
    at add_request, instead of wedging the queue head forever."""
    eng = _engine(model, max_len=32, decode_block=2)
    with pytest.raises(ValueError, match='max_len=32'):
        eng.add_request(list(range(1, 20)), max_new_tokens=20)  # 38 > 32
    # the guard is exact: worst case == max_len still admits and runs
    req = eng.add_request(list(range(1, 20)), max_new_tokens=14)  # == 32
    eng.run()
    assert len(req.tokens) == 14
    assert eng.scheduler.pending == 0


def test_engine_cap_exceeds_model_positions(model):
    with pytest.raises(ValueError, match='max_position_embeddings'):
        PagedContinuousBatchingEngine(model, num_seqs=2, max_len=4096)


def test_slot_allocator():
    a = SlotAllocator(3)
    s0, s1 = a.alloc('r0'), a.alloc('r1')
    assert (s0, s1) == (0, 1)           # lowest-first, deterministic
    a.free(s0)
    assert a.alloc('r2') == 0           # reuse the lowest freed slot
    assert a.in_use == 2 and a.available == 1
    assert a.occupancy == pytest.approx(2 / 3)
    assert a.owner_of(1) == 'r1'
    with pytest.raises(ValueError):
        a.free(2)                       # never allocated
    assert a.alloc('r3') == 2
    assert a.alloc('r4') is None        # full


def test_scheduler_chunk_plan():
    from paddle_tpu.serving.scheduler import Request
    a = SlotAllocator(2)
    s = PagedScheduler(a, PageAllocator(9), max_len=32, prefill_chunk=8,
                       page_size=8)
    r = Request(list(range(1, 12)), max_new_tokens=4)   # 11 tokens
    s.submit(r)
    assert s.admit() == [(0, r)]
    plan = s.prefill_plan()
    assert len(plan) == 1
    req, start, ids, valid, final = plan[0]
    assert (start, valid, final) == (0, 8, False)
    assert ids == list(range(1, 9))
    s.mark_prefilled(req, 8)
    req, start, ids, valid, final = s.prefill_plan()[0]
    assert (start, valid, final) == (8, 3, True)
    assert ids == [9, 10, 11, 0, 0, 0, 0, 0]            # zero-padded to C


def test_metrics_report():
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    m.on_arrival('a')
    t[0] = 0.5
    m.on_tokens('a', 1)            # ttft 0.5s
    t[0] = 0.9
    m.on_tokens('a', 4)            # 0.4s burst over 4 tokens
    m.on_step(2, 4)
    m.on_step(4, 4)
    rep = m.report()
    assert rep['tokens'] == 5
    assert rep['tok_per_s'] == pytest.approx(5 / 0.9)
    assert rep['ttft_p50_ms'] == pytest.approx(500.0)
    assert rep['occupancy_mean'] == pytest.approx(0.75)
    assert rep['latency_p99_ms'] <= 500.0
    assert percentile([], 50) is None
    assert percentile([3.0], 99) == 3.0
    assert percentile([1.0, 2.0], 50) == pytest.approx(1.5)


def test_percentile_is_linear_interpolation_not_nearest_rank():
    """The docstring/behavior contract: linear interpolation between
    closest ranks (numpy's default method). Nearest-rank would return a
    member of the input for every q; interpolation doesn't."""
    # empty and singleton
    assert percentile([], 0) is None
    assert percentile([], 100) is None
    assert percentile([7.0], 0) == 7.0
    assert percentile([7.0], 50) == 7.0
    assert percentile([7.0], 100) == 7.0
    # q = 0 / 100 are exact extremes regardless of order
    xs = [5.0, 1.0, 3.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 5.0
    # two elements: q interpolates linearly between them
    assert percentile([1.0, 3.0], 0) == 1.0
    assert percentile([1.0, 3.0], 25) == pytest.approx(1.5)
    assert percentile([1.0, 3.0], 50) == pytest.approx(2.0)
    assert percentile([1.0, 3.0], 75) == pytest.approx(2.5)
    assert percentile([1.0, 3.0], 100) == 3.0
    # parity with numpy's default ('linear') on a bigger sample —
    # including a q where nearest-rank and interpolation disagree
    rng = np.random.RandomState(0)
    vals = rng.rand(17).tolist()
    for q in (0, 10, 33.3, 50, 90, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert percentile([1.0, 2.0, 4.0], 75) == pytest.approx(3.0)  # not 2/4


def test_paged_prefix_sharing_parity_and_reduced_prefill(model):
    """Requests sharing a system prompt hit the prefix cache (> 0 hit
    rate), skip the shared blocks' prefill (fewer prefilled tokens than
    a cache-off engine on the same workload) and still match
    sequential generate() token-for-token."""
    rng = np.random.RandomState(11)
    system = [int(t) for t in rng.randint(0, 211, 16)]
    prompts = [system + [int(t) for t in rng.randint(0, 211, 3)]
               for _ in range(6)]
    mnt = 8
    expect = [_sequential(model, p, mnt) for p in prompts]
    kw = dict(num_seqs=2, max_len=64, page_size=8, prefill_chunk=8,
              decode_block=4)
    shared = PagedContinuousBatchingEngine(model, **kw)
    got = shared.generate(prompts, max_new_tokens=mnt)
    assert got == expect
    rep = shared.metrics.report()
    assert rep['prefix_hits'] > 0
    assert rep['prefix_hit_rate'] > 0
    cold = PagedContinuousBatchingEngine(model, prefix_cache=False, **kw)
    assert cold.generate(prompts, max_new_tokens=mnt) == expect
    cold_rep = cold.metrics.report()
    assert cold_rep['prefix_hits'] == 0
    # the hit-rate win is real work not done: strictly fewer prompt
    # tokens went through the prefill program
    assert rep['prefill_tokens'] < cold_rep['prefill_tokens']


def test_paged_spec_decode_parity(model, prompts):
    """Draft-and-verify emits the exact greedy sequence (the accept rule
    only keeps drafts equal to the model's own argmax picks), reports
    its acceptance counters, and the overall program set stays within
    the four-program bound."""
    mnt = 11
    expect = [_sequential(model, p, mnt) for p in prompts[:6]]
    eng = PagedContinuousBatchingEngine(model, num_seqs=3, max_len=64,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4, spec_k=3)
    got = eng.generate(prompts[:6], max_new_tokens=mnt)
    assert got == expect
    rep = eng.metrics.report()
    assert rep['spec_proposed'] > 0
    assert 0.0 <= rep['spec_accept_rate'] <= 1.0
    traces = eng.compiled_sizes()
    assert traces == {'prefill': 1, 'decode': 0, 'verify': 1}
    assert sum(1 for v in traces.values() if v) <= 4
    # greedy-only: the accept rule compares against argmax picks
    with pytest.raises(ValueError, match='greedy-only'):
        eng.add_request(prompts[0], max_new_tokens=4, do_sample=True)


@pytest.mark.slow
def test_predictor_decode_engine(model, prompts, tmp_path):
    """The serving front door reached the inference API: a jit.save'd
    causal LM round-trips into an engine whose output matches the live
    model's generate()."""
    path = str(tmp_path / 'gpt_lm')
    paddle.jit.save(model, path)
    from paddle_tpu import inference
    pred = inference.create_predictor(inference.Config(path))
    eng = pred.decode_engine(num_seqs=2, max_len=64, page_size=8,
                             prefill_chunk=8, decode_block=4)
    assert isinstance(eng, PagedContinuousBatchingEngine)
    got = eng.generate(prompts[:3], max_new_tokens=6)
    assert got == [_sequential(model, p, 6) for p in prompts[:3]]
    # one engine, no switch: `paged=` fails as any unknown keyword does
    with pytest.raises(TypeError, match='paged'):
        pred.decode_engine(num_seqs=2, paged=True)


def test_predictor_decode_engine_rejects_non_lm(tmp_path):
    from paddle_tpu import nn
    m = nn.Sequential(nn.Linear(4, 4))
    m.eval()
    path = str(tmp_path / 'mlp')
    paddle.jit.save(m, path)
    from paddle_tpu import inference
    pred = inference.create_predictor(inference.Config(path))
    with pytest.raises(TypeError, match='causal-LM'):
        pred.decode_engine()
