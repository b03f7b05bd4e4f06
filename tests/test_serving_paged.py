"""Paged-KV host bookkeeping + engine lifecycle tests.

Parity of the paged/prefix/speculative MODEL paths lives in
tests/test_serving.py; this file covers the host side the engine stands
on — page refcounts, prefix-cache
hashing/eviction, page-aware admission — plus the lifecycle edges:
allocator double-free strictness, FIFO fairness under sustained full
occupancy, shutdown semantics, and page-leak-free churn.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (NGramProposer,
                                PagedContinuousBatchingEngine,
                                PagedScheduler, SlotAllocator)
from paddle_tpu.serving.kv_cache import (SCRATCH_PAGE, PageAllocator,
                                         PrefixCache)
from paddle_tpu.serving.scheduler import Request
from paddle_tpu.text.models import cache as cache_mod
from paddle_tpu.text.models import gpt
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


@pytest.fixture(scope='module')
def model():
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ---- allocators -------------------------------------------------------


def test_slot_allocator_double_free_raises():
    a = SlotAllocator(2)
    s = a.alloc('r0')
    a.free(s)
    with pytest.raises(ValueError, match='double-free'):
        a.free(s)
    with pytest.raises(ValueError, match='not allocated'):
        a.free(1)                       # never allocated
    # the raise must not corrupt the free list: both slots still usable
    assert sorted([a.alloc('r1'), a.alloc('r2')]) == [0, 1]
    assert a.alloc('r3') is None


def test_page_allocator_basics():
    a = PageAllocator(5)                # pages 1..4 allocatable
    assert a.alloc() == 1               # lowest-first, page 0 reserved
    assert a.alloc() == 2
    assert a.refcount(1) == 1
    assert a.in_use == 2 and a.available == 2
    assert a.occupancy == pytest.approx(0.5)
    assert a.decref(1) is True          # freed at zero
    assert a.alloc() == 1               # reuses the lowest freed page
    with pytest.raises(ValueError, match='num_pages'):
        PageAllocator(1)                # no room beyond the scratch page


def test_page_allocator_refcounts_and_double_free():
    a = PageAllocator(4)
    p = a.alloc()
    a.incref(p)                         # second owner (e.g. prefix cache)
    assert a.refcount(p) == 2
    assert a.decref(p) is False         # still held
    assert a.decref(p) is True          # last owner: back on free list
    with pytest.raises(ValueError, match='double-free'):
        a.decref(p)
    with pytest.raises(ValueError, match='not allocated'):
        a.incref(p)
    with pytest.raises(ValueError, match='scratch'):
        a.decref(SCRATCH_PAGE)
    with pytest.raises(ValueError, match='not allocated'):
        a.free(3)                       # never allocated


# ---- prefix cache -----------------------------------------------------


def test_prefix_cache_chain_match_and_publish():
    a = PageAllocator(16)
    pc = PrefixCache(4, a)
    prompt = list(range(11))            # blocks [0-3], [4-7]; tail 8-10
    assert pc.match(prompt) == []       # cold: both full blocks miss
    assert (pc.hits, pc.misses) == (0, 2)
    p0, p1 = a.alloc(), a.alloc()
    assert pc.publish(prompt, 0, p0)
    assert pc.publish(prompt, 1, p1)
    assert a.refcount(p0) == 2          # cache holds its own reference
    assert pc.match(prompt) == [p0, p1]
    # a whole-prompt-covering match is forbidden: >= 1 token must
    # prefill so the final chunk's logits can seed generation
    assert pc.match(prompt[:8]) == [p0]
    # chain hashing: same block content after a DIFFERENT prefix is a
    # different key — block 1's page must not leak to a mismatched head
    other = [99, 99, 99, 99] + prompt[4:]
    assert pc.match(other) == []
    # duplicate publish is a no-op and takes no extra reference
    assert not pc.publish(prompt, 0, p0)
    assert a.refcount(p0) == 2


def test_prefix_cache_evicts_lru_and_skips_referenced_pages():
    a = PageAllocator(16)
    pc = PrefixCache(2, a)
    prompts = [[i, i, 0, 0, 0] for i in range(3)]
    pages = []
    for pr in prompts:
        p = a.alloc()
        pc.publish(pr, 0, p)
        a.decref(p)                     # publisher retired: cache-only ref
        pages.append(p)
    pc.match(prompts[0])                # refresh entry 0: now most-recent
    a.incref(pages[1])                  # a live sequence maps entry 1
    assert pc.evict(2) == 2             # entry 2 (LRU) + entry 0
    assert len(pc) == 1                 # the referenced entry survived
    assert a.refcount(pages[1]) == 2
    assert pc.match(prompts[1]) == [pages[1]]
    pc.clear()
    a.decref(pages[1])
    assert a.in_use == 0 and a.available == 15


# ---- page-aware scheduling --------------------------------------------


def _mk_sched(num_seqs=2, num_pages=9, max_len=32, chunk=4, page=4,
              prefix=True):
    pages = PageAllocator(num_pages)
    pc = PrefixCache(page, pages) if prefix else None
    sched = PagedScheduler(SlotAllocator(num_seqs), pages, max_len, chunk,
                           page, pc)
    return sched, pages


def test_paged_scheduler_reserves_all_pages_up_front():
    sched, pages = _mk_sched()
    r = Request(list(range(6)), max_new_tokens=4)   # needs 9 rows -> 3 pages
    sched.submit(r)
    assert [req for _, req in sched.admit()] == [r]
    assert pages.in_use == 3
    row = sched.block_tables[r.slot]
    assert (row[:3] > SCRATCH_PAGE).all()
    assert (row[3:] == SCRATCH_PAGE).all()
    sched.mark_prefilled(r, 6)
    sched.release(r)
    # full release: pages either free or held ONLY by the prefix cache
    assert pages.in_use == len(sched.prefix)
    assert (sched.block_tables[0] == SCRATCH_PAGE).all()


def test_paged_scheduler_head_blocking_keeps_fifo():
    sched, pages = _mk_sched(num_seqs=2, num_pages=9)
    big = Request(list(range(20)), max_new_tokens=9)    # 7 pages
    small = Request([1, 2], max_new_tokens=2)           # 1 page
    hog = Request(list(range(12)), max_new_tokens=5)    # 4 pages
    sched.submit(hog)
    assert len(sched.admit()) == 1
    sched.submit(big)
    sched.submit(small)
    # 5 pages remain: big (7) cannot reserve — and small must NOT jump
    # the queue past it, or big could starve behind a stream of smalls
    assert sched.admit() == []
    assert pages.in_use == 4
    sched.mark_prefilled(hog, 12)
    sched.release(hog)
    admitted = [req for _, req in sched.admit()]
    assert admitted[0] is big                           # FIFO restored
    assert small in admitted


def test_paged_scheduler_submit_validation():
    sched, _ = _mk_sched(max_len=16, num_pages=5)
    with pytest.raises(ValueError, match='empty prompt'):
        sched.submit(Request([], max_new_tokens=2))
    with pytest.raises(ValueError, match='max_new_tokens'):
        sched.submit(Request([1], max_new_tokens=0))
    with pytest.raises(ValueError, match='cache rows'):
        sched.submit(Request(list(range(14)), max_new_tokens=8))
    with pytest.raises(ValueError, match='pages'):
        # fits max_len rows but not the 4-page pool
        _mk_sched(max_len=32, num_pages=5)[0].submit(
            Request(list(range(15)), max_new_tokens=14))


def test_paged_scheduler_1k_churn_leaks_no_pages():
    """The page-leak satellite, at the bookkeeping layer where 1000
    requests are cheap: after arbitrary admit/prefill/retire churn with
    prefix publishing on, every page is back on the free list except
    the prefix cache's own bounded references."""
    rng = np.random.RandomState(5)
    sched, pages = _mk_sched(num_seqs=4, num_pages=33, max_len=32)
    system = [7, 8, 9, 10]                      # one shareable block
    live = []
    for i in range(1000):
        n0 = int(rng.randint(1, 10))
        r = Request(system + [int(t) for t in rng.randint(0, 99, n0)],
                    max_new_tokens=int(rng.randint(1, 8)))
        sched.submit(r)
        for _, req in sched.admit():
            live.append(req)
        if live and rng.rand() < 0.7:
            req = live.pop(int(rng.randint(len(live))))
            sched.mark_prefilled(req, len(req.prompt))
            sched.release(req)
    for req in live:
        sched.mark_prefilled(req, len(req.prompt))
        sched.release(req)
    while sched.queue:
        for _, req in sched.admit():
            sched.mark_prefilled(req, len(req.prompt))
            sched.release(req)
    assert pages.in_use == len(sched.prefix)
    sched.prefix.clear()
    assert pages.in_use == 0
    assert pages.available == 32
    assert (sched.block_tables == SCRATCH_PAGE).all()


# ---- engine lifecycle -------------------------------------------------


def test_engine_fifo_fairness_under_full_occupancy(model):
    """Sustained full occupancy with Poisson arrivals: admission is
    FIFO (no request overtakes an earlier one) and nobody starves —
    every request finishes within a wait bounded by the generation
    lengths ahead of it."""
    rng = np.random.RandomState(4)
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=4)
    admitted = []
    orig = eng.scheduler.admit
    eng.scheduler.admit = lambda: [
        (s, (admitted.append(r.id), r)[1]) for s, r in orig()]
    n_req, due = 12, [0] + list(np.cumsum(
        rng.poisson(1.0, size=11)))       # arrival step of each request
    prompts = [[int(t) for t in rng.randint(0, 211, 1 + i % 5)]
               for i in range(n_req)]
    reqs, i, steps = [], 0, 0
    while i < n_req or eng.scheduler.pending:
        while i < n_req and due[i] <= steps:
            reqs.append(eng.add_request(prompts[i], max_new_tokens=6))
            i += 1
        eng.step()
        steps += 1
        assert steps < 300              # no starvation: bounded total
    assert admitted == [r.id for r in reqs]          # FIFO, no overtakes
    assert all(len(r.tokens) == 6 for r in reqs)
    # load was sustained: most steps ran with some occupancy
    assert eng.metrics.report()['occupancy_mean'] > 0.25


def test_shutdown_rejects_new_requests_but_drains(model):
    eng = _paged(model)
    req = eng.add_request([1, 2, 3], max_new_tokens=3)
    eng.shutdown()
    with pytest.raises(RuntimeError, match='shut down'):
        eng.add_request([4, 5], max_new_tokens=2)
    eng.run()                           # in-flight work still completes
    assert len(req.tokens) == 3
    assert eng.scheduler.pending == 0


def test_shutdown_races_active_stream_consumers(model):
    """shutdown() lands WHILE stream() consumers are cooperatively
    driving the engine: the front door closes, but every consumer's
    stream still terminates cleanly with its full token budget (the
    retire/churn half of this contract is covered above)."""
    import threading
    eng = _paged(model)
    reqs = [eng.add_request(p, max_new_tokens=6, stream=True)
            for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
    got = {i: [] for i in range(len(reqs))}
    errs = []

    def consume(i):
        try:
            for tok in eng.stream(reqs[i]):
                got[i].append(tok)
        except Exception as e:        # noqa: BLE001 — the assertion
            errs.append(e)

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    eng.shutdown()                    # races the consumers' step() calls
    with pytest.raises(RuntimeError, match='shut down'):
        eng.add_request([1], max_new_tokens=2)
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    assert errs == []
    for i, r in enumerate(reqs):
        assert got[i] == r.tokens
        assert len(got[i]) == 6
    assert eng.scheduler.pending == 0


def test_engine_retire_releases_pages(model):
    """Engine-level leak check: after churning many requests through few
    sequences, only the prefix cache still references pages, and
    disabling it drains the pool to empty."""
    rng = np.random.RandomState(9)
    prompts = [[int(t) for t in rng.randint(0, 211, 1 + i % 7)]
               for i in range(12)]
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2, prefix_cache=False)
    eng.generate(prompts, max_new_tokens=4)
    assert eng.pages.in_use == 0
    assert eng.pages.available == eng.num_pages - 1
    assert (eng.scheduler.block_tables == SCRATCH_PAGE).all()


# ---- speculative proposer ---------------------------------------------


def test_ngram_proposer():
    p = NGramProposer(2)
    # trailing bigram (3, 4) occurred earlier: propose its continuation
    assert p.propose([1, 3, 4, 7, 8, 3, 4], 3) == [7, 8, 3]
    # no earlier occurrence: repeat the last token
    assert p.propose([1, 2, 3], 2) == [3, 3]
    # continuation shorter than k: pad by repeating its last token
    assert p.propose([5, 6, 9, 5, 6], 4) == [9, 5, 6, 6]
    # single-token history cannot form an n-gram; still drafts k tokens
    assert p.propose([4], 3) == [4, 4, 4]
    with pytest.raises(ValueError):
        NGramProposer(0)


def test_paged_capacity_validation(model):
    with pytest.raises(ValueError, match='max_position_embeddings'):
        PagedContinuousBatchingEngine(model, num_seqs=2, max_len=4096)
    eng = PagedContinuousBatchingEngine(model, num_seqs=2, max_len=32,
                                        page_size=8, prefill_chunk=8,
                                        decode_block=2)
    with pytest.raises(ValueError, match='cache rows'):
        eng.add_request(list(range(30)), max_new_tokens=8)
    # capacity errors must not wedge later valid requests
    req = eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run()
    assert len(req.tokens) == 2


# ---- the step explains itself: spans, counters, front-door stamps ------


from paddle_tpu.monitor import MetricRegistry                 # noqa: E402
from paddle_tpu.monitor.registry import set_default_registry  # noqa: E402
from paddle_tpu.monitor.tracing import (FlightRecorder,       # noqa: E402
                                        Tracer, set_default_tracer)


class _Ticks:
    """A fake clock: every read is one second later than the last."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(params=[None])
def traced(request, tmp_path):
    """Fresh registry + tracer (on the fake clock a test asks for, or
    the real ones), installed as the process defaults before the engine
    under test is built."""
    clock = request.param and request.param()
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=1024, dump_dir=str(tmp_path / 'flight'),
                         cooldown=3600.0, registry=reg, clock=clock)
    tr = Tracer(registry=reg, recorder=rec, clock=clock)
    prev_reg = set_default_registry(reg)
    prev_tr = set_default_tracer(tr)
    yield tr, clock
    set_default_tracer(prev_tr)
    set_default_registry(prev_reg)


def _paged(model, **kw):
    args = dict(num_seqs=2, max_len=32, page_size=8, prefill_chunk=8,
                decode_block=2)
    args.update(kw)
    return PagedContinuousBatchingEngine(model, **args)


def _dur(s):
    return s['end_mono'] - s['start_mono']


@pytest.mark.parametrize('traced', [_Ticks], indirect=True)
def test_step_span_has_its_phases_as_children(model, traced):
    tr, clock = traced
    eng = _paged(model)
    eng.metrics._clock = clock          # the engine on the same clock
    # two slots, three requests: while the third is queued a burst stays
    # in flight across the step's return; once nothing is queued and a
    # slot is free, a step waits for its own burst before it returns
    reqs = [eng.add_request(list(range(1, 12)), max_new_tokens=4),
            eng.add_request([5, 6, 7], max_new_tokens=8),
            eng.add_request([9, 8], max_new_tokens=4)]
    eng.run()
    assert [len(r.tokens) for r in reqs] == [4, 8, 4]
    spans = tr.recorder.spans()
    steps = [s for s in spans if s['name'] == 'serving.step']
    assert [s['tags']['step'] for s in steps] == list(
        range(1, len(steps) + 1))
    assert steps[0]['tags']['queue_depth'] == 3
    assert steps[0]['tags']['residents'] == 0
    assert all(s['tags']['cpu_s'] >= 0.0 for s in steps)
    assert steps[0]['tags']['pages_in_use'] == 0
    kids = {}
    for s in spans:
        kids.setdefault(s['parent_id'], []).append(s)
    waits, calls = {}, 0
    for st in steps:
        mine = [k for k in kids[st['span_id']]
                if k['name'] != 'perf.straggler']
        names = [k['name'] for k in mine]
        # a wait for the burst in flight comes first, one for the step's
        # own burst (where it does not stay in flight) last
        phases = [n for n in names if n != 'serving.step.wait']
        assert phases == ['serving.step.admit', 'serving.step.prefill']
        assert names.count('serving.step.wait') <= 2
        for k in mine:                  # inside the parent, in order
            assert st['start_mono'] <= k['start_mono'] <= k['end_mono'] \
                <= st['end_mono']
        assert sum(_dur(k) for k in mine) <= _dur(st)
        pre = next(k for k in mine if k['name'] == 'serving.step.prefill')
        pcs = kids.get(pre['span_id'], [])
        assert [c['name'] for c in pcs] == ['serving.prefill_call'] * len(
            pcs)
        assert pre['tags']['calls'] == len(pcs)
        assert pre['tags']['tokens'] == sum(c['tags']['tokens']
                                            for c in pcs)
        assert pre['tags']['picks'] == sum(c['tags']['final'] for c in pcs)
        assert sum(_dur(c) for c in pcs) <= _dur(pre)
        calls += len(pcs)
        for w in mine:
            if w['name'] == 'serving.step.wait':
                assert w['tags']['waited_s'] == _dur(w)
                waits[w['tags']['burst']] = (w, st)
    assert calls == 4                   # 11 tokens: 2 chunks; the others 1
    # a burst runs from its dispatch to the host's knowing that it
    # ended: no step's child, and the wait's span says how much of it
    # the host really waited
    bursts = [s for s in spans if s['name'] == 'serving.decode_burst']
    assert [b['tags']['burst'] for b in bursts] == list(
        range(1, len(bursts) + 1))
    assert len(bursts) == len(waits) == eng.timeline.steps > 0
    left_in_flight = 0
    for b in bursts:
        assert b['parent_id'] is None
        w, ended_in = waits[b['tags']['burst']]
        assert b['end_mono'] == w['end_mono']
        assert b['tags']['block_s'] == w['tags']['waited_s']
        assert b['tags']['dispatch_s'] + b['tags']['block_s'] <= _dur(b)
        dispatched_in = [st for st in steps if st['start_mono']
                         <= b['start_mono'] <= st['end_mono']]
        assert len(dispatched_in) == 1
        stayed = dispatched_in[0]['tags']['tail_under_burst']
        assert ended_in['tags']['step'] == \
            dispatched_in[0]['tags']['step'] + stayed
        left_in_flight += stayed
    # in flight while the third request was queued, not after
    tails = [st['tags']['tail_under_burst'] for st in steps]
    assert tails[0] is True and tails[-1] is False
    assert tails == sorted(tails, reverse=True)
    assert 0 < left_in_flight == sum(tails) < len(bursts)
    fam = eng.metrics.registry.get('serving_steps_total')
    assert fam.labels('overlapped').value() == sum(tails)
    assert fam.labels('exposed').value() == len(steps) - sum(tails)
    admit0 = kids[steps[0]['span_id']][0]['tags']    # no wait in step 1
    assert admit0 == {'admitted': 2, 'left': 1, 'head_left': 'slots'}
    reg = eng.metrics.registry
    assert reg.get('serving_prefill_calls_total').value() == 4


def test_decode_program_ops_carry_the_scope_names(model):
    """Named scopes are metadata on the compiled ops: the programs,
    their count and the outputs stay what they were. A decode program
    that reads the pool in place has no `gpt.attn.paged_gather` op; the
    one-row prefill program, which gathers, has."""
    eng = _paged(model, num_pages=8)
    prompt = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4]
    out = eng.generate([prompt], max_new_tokens=5)[0]
    want = model.generate(paddle.to_tensor([prompt]), max_new_tokens=5)
    assert out == [int(t) for t in want.numpy()[0][len(prompt):]]
    common = ('gpt.embed', 'gpt.ln', 'gpt.attn.qkv', 'gpt.attn.paged_write',
              'gpt.attn.mask', 'gpt.attn.core', 'gpt.attn.out', 'gpt.mlp',
              'gpt.lm_head', 'serving.pick_token')
    text = eng._decode_jit.lower(*eng._decode_args).compile().as_text()
    for scope in common:
        assert scope in text, scope
    assert 'gpt.attn.paged_gather' not in text
    key = np.zeros((2,), np.uint32)
    text = eng._prefill_jit.lower(
        eng._params, eng._bufs, eng._pools, eng.scheduler.block_tables[:1],
        np.zeros((1,), np.int32), np.zeros((1, 8), np.int32), np.int32(8),
        key, np.float32(1.0), np.int32(0), np.asarray(False)
    ).compile().as_text()
    for scope in common + ('gpt.attn.paged_gather',):
        assert scope in text, scope
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}


# ---- the K/V read: the pool in place, or each row's gathered view ------


def _slot_view(pool, table, page, dh):
    """Pool `[G, rows, W]` -> the row's logical `[capacity, H, Dh]` (H:
    the group's heads side by side, a last group's padding included)."""
    rows = (table[:, None] * page + np.arange(page)).reshape(-1)
    return np.transpose(pool[:, rows], (1, 0, 2)).reshape(len(rows), -1, dh)


# n = 1 is a decode step, 3 and 5 a verify call over drafts, from lengths
# that sit on no page boundary
@pytest.mark.parametrize('n', [1, 3, 5])
def test_pool_read_agrees_with_the_gathered_view(model, monkeypatch, n):
    """Float32 logits and greedy picks of the two reads over one pool of
    random rows, and the first layer's attention under each read against
    plain float64 attention over the same rows: a page shared
    by two rows, scratch entries behind a row's pages, an idle row (all
    scratch, length 0), a frozen lane (a row still in prefill, at a page
    boundary) and a row that this call fills to capacity. Whatever lies
    past a row's length, on pages it does not hold or on the scratch
    page, is garbage to all three."""
    page, nb, num_pages, layers, heads, dh = 8, 4, 12, 2, 4, 16
    rng = np.random.RandomState(5)
    tables = np.asarray([[1, 2, 11, 0],     # 13 rows, page 1 shared
                         [1, 3, 4, 0],      # 17 rows, page 1 shared
                         [0, 0, 0, 0],      # idle
                         [9, 10, 0, 0],     # frozen in prefill at 8
                         [5, 6, 7, 8]],     # full after this call
                        np.int32)
    lens = np.asarray([13, 17, 0, 8, nb * page - n], np.int32)
    ids = rng.randint(0, 211, (5, n)).astype(np.int32)
    shape = cache_mod.paged_pool_shape(heads, dh, num_pages, page)
    assert shape == (1, num_pages * page, 128)   # 8 heads of 16 fill a row
    pools = [tuple(rng.randn(*shape).astype(np.float32) for _ in 'kv')
             for _ in range(layers)]
    assert cache_mod.paged_kv_read(5, nb * page, num_pages * page) == 'pool'

    def run(read):
        monkeypatch.setattr(cache_mod, 'paged_kv_read', lambda *shape: read)
        caches = [gpt.GPTPagedCache(paddle.to_tensor(k), paddle.to_tensor(v),
                                    tables, lens, page) for k, v in pools]
        logits, new = model(paddle.to_tensor(ids), caches=caches)
        assert [c.kv_read for c in new] == [read] * layers
        return logits.numpy(), [(c.k.numpy(), c.v.numpy()) for c in new]

    (pool, pool_kv), (gather, gather_kv) = run('pool'), run('gather')
    assert pool.dtype == np.float32 and pool.shape == (5, n, 211)
    np.testing.assert_allclose(pool, gather, rtol=0, atol=1e-5)
    assert (pool.argmax(-1) == gather.argmax(-1)).all()
    # the write is one code: the first layer's pools come out the same
    # bit for bit, the next one's inputs already differ in the last digit
    np.testing.assert_array_equal(pool_kv[0], gather_kv[0])
    np.testing.assert_allclose(pool_kv[1], gather_kv[1], rtol=0, atol=1e-5)
    # ... and it wrote rows [len, len + n) of each row's view (the idle
    # row's on the scratch page) and nothing else
    k0 = pools[0][0].copy()
    kept = np.ones(num_pages * page, bool)
    for s in range(5):
        for j in range(lens[s], lens[s] + n):
            kept[tables[s, j // page] * page + j % page] = False
    np.testing.assert_array_equal(pool_kv[0][0][:, kept], k0[:, kept])
    live = slice(0, heads * dh)        # (the other lanes of a row are padding)
    assert not (pool_kv[0][0][:, ~kept][:, page:, live]
                == k0[:, ~kept][:, page:, live]).any()
    # plain float64 attention over each row's logical view, against the
    # first layer's attention under either read
    attn = model.gpt.h[0].attn
    x = paddle.to_tensor(rng.randn(5, n, heads * dh).astype(np.float32))
    qkv = attn.qkv_proj(x).numpy().astype(np.float64).reshape(
        5, n, 3, heads, dh)
    want = []
    for s in range(5):
        kview, vview = (
            _slot_view(p, tables[s], page, dh)[:, :heads].astype(np.float64)
            for p in pools[0])
        pos = lens[s] + np.arange(n)
        kview[pos], vview[pos] = qkv[s, :, 1], qkv[s, :, 2]
        want.append(_attend(qkv[s, :, 0], kview, vview, pos).reshape(n, -1))
    want = np.stack(want) @ attn.out_proj.weight.numpy().astype(np.float64) \
        + attn.out_proj.bias.numpy()
    for read in ('pool', 'gather'):
        monkeypatch.setattr(cache_mod, 'paged_kv_read', lambda *shape: read)
        cache = gpt.GPTPagedCache(*(paddle.to_tensor(p) for p in pools[0]),
                                  tables, lens, page)
        got, new = attn(x, cache=cache)
        assert new.kv_read == read
        np.testing.assert_allclose(got.numpy(), want, rtol=0, atol=1e-5)


def _attend(q, kview, vview, qpos):
    """Plain float64 causal attention of q `[n, H, Dh]` over one row's
    logical view `[L, H, Dh]`."""
    s = np.einsum('qhd,khd->hqk', q, kview) / np.sqrt(q.shape[-1])
    s = np.where(qpos[None, :, None] >= np.arange(len(kview)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum('hqk,khd->qhd', p / p.sum(-1, keepdims=True), vview)


# (heads, head_dim): eight narrow heads a row with room to spare, GPT-2
# XL's odd number of heads of 64 (two a row, the last row half padding),
# Olmo-Hybrid's heads of 128 (a row each).
# (start, n) on pages of 8 with 6 blocks: whole pages from a boundary (what
# the scheduler makes), from inside a page, a run whose padded tail passes
# the last block (start 40 and 37, the last block real: the tail goes to
# the scratch page), a run that is no whole page, and single rows
@pytest.mark.parametrize('heads,dh', [(4, 16), (3, 64), (2, 128)])
@pytest.mark.parametrize('start,n', [
    (0, 16), (16, 16), (13, 16), (3, 8), (40, 16), (37, 16), (5, 6),
    (21, 1), (47, 1)])
def test_a_call_writes_its_rows_and_no_other(monkeypatch, heads, dh, start,
                                             n):
    """A one-row call's rows `[start, start + n)` land in the row's pages
    (what runs past its last block on the scratch page), every other pool
    row stays bit for bit — from an unaligned start too, which no
    scheduler makes — and the output is plain causal attention over what
    the row holds, by the gathered view and by the pool."""
    page, nb, num_pages = 8, 6, 10
    rng = np.random.RandomState(start * 31 + n)
    table = np.asarray([[4, 2, 7, 9, 3, 5]], np.int32)
    lens = np.asarray([start], np.int32)
    shape = cache_mod.paged_pool_shape(heads, dh, num_pages, page)
    kp, vp = (rng.randn(*shape).astype(np.float32) for _ in 'kv')
    q, k, v = (rng.randn(1, n, heads, dh).astype(np.float32)
               for _ in 'qkv')
    real = min(n, nb * page - start)    # tokens inside the row's capacity
    lanes = heads * dh                  # live lanes of a token's rows
    row_of = lambda x: np.pad(x.reshape(-1), (0, shape[0] * shape[2] - lanes)
                              ).reshape(shape[0], shape[2])
    want_k, want_v = kp.copy(), vp.copy()
    touched = np.zeros(num_pages * page, bool)
    touched[:page] = True                       # scratch: anything goes
    for i in range(real):
        r = table[0, (start + i) // page] * page + (start + i) % page
        want_k[:, r], want_v[:, r] = row_of(k[0, i]), row_of(v[0, i])
        touched[r] = True
    view = lambda pool: _slot_view(pool, table[0], page, dh)[
        :, :heads].astype(np.float64)
    want = _attend(q[0, :real].astype(np.float64), view(want_k),
                   view(want_v), start + np.arange(real))

    def call(read, lens):
        c = cache_mod.PagedKVCache(paddle.to_tensor(kp), paddle.to_tensor(vp),
                                   table, lens, page)
        monkeypatch.setattr(cache_mod, 'paged_kv_read', lambda *shape: read)
        out, new = cache_mod.paged_attention(q, k, v, c, 't')
        assert new.kv_read == read
        return out._data, new.k._data, new.v._data

    for read in ('gather', 'pool'):
        if real < n:
            # (lengths the host can see are range-checked; the engine's
            # are traced, and the tail must find the scratch page)
            with pytest.raises(ValueError, match='overflow'):
                call(read, lens)
            out, got_k, got_v = map(np.asarray, jax.jit(
                lambda lens: call(read, lens))(lens))
        else:
            out, got_k, got_v = map(np.asarray, call(read, lens))
        for got, wanted in ((got_k, want_k), (got_v, want_v)):
            np.testing.assert_array_equal(got[:, page:][:, touched[page:]],
                                          wanted[:, page:][:, touched[page:]])
            np.testing.assert_array_equal(got[:, ~touched],
                                          wanted[:, ~touched])
        np.testing.assert_allclose(out[0, :real], want, rtol=0, atol=2e-5)


@pytest.mark.parametrize('spec_k', [0, 3])
def test_shape_rule_and_the_spans_kv_read_tag(model, traced, spec_k):
    """The rule reads shapes only: a decode (or verify) batch whose views
    together are at least the pool reads the pool, a one-row chunk
    gathers; the programs remember it, the spans carry it, and the
    tokens are the ones a pool too large for the rule gives."""
    assert cache_mod.paged_kv_read(24, 1024, 512 * 16) == 'pool'
    assert cache_mod.paged_kv_read(1, 1024, 512 * 16) == 'gather'
    tr, _ = traced
    rng = np.random.RandomState(3)
    system = [int(t) for t in rng.randint(0, 211, 8)]   # one shared page
    prompts = [system + [int(t) for t in rng.randint(0, 211, k)]
               for k in (3, 5, 2, 7)]
    want = [[int(t) for t in model.generate(
        paddle.to_tensor([p]), max_new_tokens=6).numpy()[0][len(p):]]
        for p in prompts]
    burst = 'verify' if spec_k else 'decode'
    # 2 rows x 32 logical rows >= 8 pages x 8; the default 9 pages: not
    eng = _paged(model, num_pages=8, spec_k=spec_k)
    assert eng.generate(prompts, max_new_tokens=6) == want
    assert eng.kv_read == {'prefill': 'gather', burst: 'pool'}
    assert eng.metrics.report()['prefix_hits'] > 0      # the page WAS shared
    spans = tr.recorder.spans()
    for name, read in (('serving.decode_burst', 'pool'),
                       ('serving.prefill_call', 'gather')):
        tags = [s['tags']['kv_read'] for s in spans if s['name'] == name]
        assert tags and set(tags) == {read}, name
    roomy = _paged(model, spec_k=spec_k)
    assert roomy.generate(prompts, max_new_tokens=6) == want
    assert roomy.kv_read == {'prefill': 'gather', burst: 'gather'}


def test_admit_pass_counts_and_causes_on_full_pool_and_full_slots():
    # the POOL is full: the head waits for pages, the rest behind it
    sched, pages = _mk_sched(num_seqs=3, num_pages=9)
    hog = Request(list(range(12)), max_new_tokens=5)    # 4 of 8 pages
    big = Request(list(range(20)), max_new_tokens=9)    # 7 pages
    small = Request([1, 2], max_new_tokens=2)
    sched.submit(hog)
    assert len(sched.admit()) == 1 and sched.head_left == 'none'
    assert hog._admit_waits == {}
    sched.submit(big)
    sched.submit(small)
    for _ in range(2):
        assert sched.admit() == [] and sched.head_left == 'pages'
    assert big._admit_waits == {'pages': 2}
    assert small._admit_waits == {'behind_head': 2}
    sched.mark_prefilled(hog, 12)
    sched.release(hog)
    assert len(sched.admit()) == 2 and sched.head_left == 'none'
    assert big._admit_waits == {'pages': 2}             # kept, not reset
    # the SLOTS are full (one slot, pages to spare)
    sched = _mk_sched(num_seqs=1, num_pages=30)[0]
    a, b, c = (Request([1, 2, 3], max_new_tokens=2) for _ in range(3))
    for r in (a, b, c):
        sched.submit(r)
    assert [r for _, r in sched.admit()] == [a]
    assert sched.head_left == 'slots'
    assert (b._admit_waits, c._admit_waits) == (
        {'slots': 1}, {'behind_head': 1})
    sched.mark_prefilled(a, 3)
    sched.release(a)
    assert [r for _, r in sched.admit()] == [b]
    assert c._admit_waits == {'slots': 1, 'behind_head': 1}


def test_blocked_passes_reach_the_span_and_the_registry(model, traced):
    tr, clock = traced
    eng = _paged(model)
    reqs = [eng.add_request([1 + i, 2, 3], max_new_tokens=4)
            for i in range(3)]          # two slots: the third waits
    eng.run()
    by_id = {s['tags']['request_id']: s for s in tr.recorder.spans()
             if s['name'] == 'serving.request'}
    blocked = [next(e for e in by_id[r.id]['events']
                    if e['name'] == 'admitted')['args']['blocked']
               for r in reqs]
    assert blocked[:2] == [{}, {}]
    assert set(blocked[2]) == {'slots'} and blocked[2]['slots'] >= 1
    fam = eng.metrics.registry.get('serving_admit_blocked_total')
    assert fam.labels('slots').value() == blocked[2]['slots']
    assert fam.labels('pages').value() == 0
    admits = [s['tags'] for s in tr.recorder.spans()
              if s['name'] == 'serving.step.admit']
    assert admits[0] == {'admitted': 2, 'left': 1, 'head_left': 'slots'}


def test_arrival_is_stamped_at_the_front_door(model):
    import threading
    import time
    eng = _paged(model)
    # a stated due time is honoured: TTFT and queue wait run from it
    due = eng.metrics.now() - 5.0
    r0 = eng.add_request([1, 2, 3], max_new_tokens=2, arrival_t=due)
    assert r0._arrival_t == due
    eng.run()
    assert r0._first_token_t - r0._arrival_t > 5.0
    assert r0._admit_t - r0._arrival_t > 5.0
    # the default stamp is taken BEFORE the lock: a caller that waits
    # for a slow step() to release it arrived when it called
    inside, release = threading.Event(), threading.Event()
    admit = eng._admit

    def slow_admit():
        inside.set()
        assert release.wait(10)
        return admit()
    eng._admit = slow_admit
    eng.add_request([4, 5, 6], max_new_tokens=2)
    stepper = threading.Thread(target=eng.step)
    stepper.start()
    assert inside.wait(10)              # step() now holds the lock
    got = {}

    def caller():
        got['t_call'] = eng.metrics.now()
        got['req'] = eng.add_request([7, 8, 9], max_new_tokens=2)
        got['t_back'] = eng.metrics.now()
    th = threading.Thread(target=caller)
    th.start()
    time.sleep(0.6)
    assert th.is_alive()                # blocked on the engine's lock
    release.set()
    th.join(30)
    stepper.join(30)
    assert not th.is_alive() and not stepper.is_alive()
    eng._admit = admit
    req = got['req']
    assert got['t_back'] - got['t_call'] >= 0.5
    assert got['t_call'] <= req._arrival_t < got['t_call'] + 0.25
    eng.run()
    assert len(req.tokens) == 2


def test_on_token_sees_each_delivered_token_once(model):
    eng = _paged(model, preempt=True)
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3], [5, 8, 9, 7, 9]]
    seen = {i: [] for i in range(3)}
    order = []

    def sink(i):
        def put(tok):
            seen[i].append(tok)
            order.append(i)
        return put
    r0 = eng.add_request(prompts[0], max_new_tokens=8, on_token=sink(0))
    r1 = eng.add_request(prompts[1], max_new_tokens=8, on_token=sink(1),
                         stream=True)
    while min(len(r0.tokens), len(r1.tokens)) < 2:
        eng.step()                      # both residents mid-decode
    r2 = eng.add_request(prompts[2], max_new_tokens=8, priority=1,
                         on_token=sink(2))
    streamed = list(eng.stream(r1))     # drives the engine to the end
    eng.run()
    assert eng.scheduler.preempted == 1
    victim = r1 if r1._preempts else r0
    assert victim._preempts == 1 and victim.outcome == 'ok'
    # once per delivered token, none for the tokens regenerated after
    # the preemption; `stream=True` is unchanged beside the hook
    for i, r in enumerate((r0, r1, r2)):
        assert seen[i] == r.tokens and len(r.tokens) == 8
    assert streamed == r1.tokens
    assert len(order) == 24


@pytest.mark.parametrize('queued', [False, True],
                         ids=['idle_queue', 'queued'])
def test_straggler_record_says_which_phase(model, traced, tmp_path, queued):
    import json
    import os
    import time
    from paddle_tpu.monitor.perf import StepTimeline
    tr, _ = traced
    eng = _paged(model)
    # 20x the median: a loaded test machine's jitter is not a straggler,
    # the half second planted below is
    eng.timeline = StepTimeline(registry=eng.metrics.registry, tracer=tr,
                                min_history=3, straggler_factor=20.0)
    eng.add_request([1, 2, 3], max_new_tokens=24)
    if queued:                # one behind the two slots: bursts stay in
        eng.add_request([4, 5], max_new_tokens=24)              # flight
        eng.add_request([6], max_new_tokens=2)
    for _ in range(5):
        eng.step()
    fast = eng._decode_jit

    def slow(*args):
        out = fast(*args)
        time.sleep(0.5)                 # the host sits in the dispatch
        return out
    eng._decode_jit = slow
    eng.step()
    eng._decode_jit = fast
    eng.run()
    # the record is the burst's, from its dispatch to its end, and
    # carries the phases of the step that saw it end: step 6 itself, or
    # step 7 where the burst stayed in flight across step 6's return
    at = 6 + queued
    recs = [s for s in tr.recorder.spans() if s['name'] == 'perf.straggler'
            and s['tags'].get('engine_step') == at]
    assert len(recs) == 1 and eng.timeline.stragglers >= 1
    tags = recs[0]['tags']
    assert set(tags) >= {'total_s', 'median_s', 'step', 'engine_step',
                         'step_s', 'wait_s', 'admit_s', 'prefill_s',
                         'burst_dispatch_s', 'burst_block_s', 'self_s',
                         'cpu_s', 'compiles'}
    assert tags['compiles'] == 0
    assert tags['burst_dispatch_s'] >= 0.5 > tags['burst_block_s']
    assert tags['total_s'] == pytest.approx(
        tags['burst_dispatch_s'] + tags['burst_block_s'], abs=1e-5)
    assert tags['wait_s'] <= tags['burst_block_s'] + 1e-9
    parts = sum(tags[k] for k in ('wait_s', 'admit_s', 'prefill_s',
                                  'self_s'))
    assert parts == pytest.approx(tags['step_s'])
    step = next(s for s in tr.recorder.spans()
                if s['span_id'] == recs[0]['parent_id'])
    assert step['name'] == 'serving.step' and step['tags']['step'] == at
    slow_step = next(s for s in tr.recorder.spans()
                     if s['name'] == 'serving.step'
                     and s['tags']['step'] == 6)
    # waiting, not busy; and where the burst stayed in flight the half
    # second sat in step 6, not in the step that found it ended
    assert _dur(slow_step) >= 0.5 > slow_step['tags']['cpu_s']
    assert (tags['step_s'] >= 0.5) == (not queued)
    # the road a recompile record takes: ring + one throttled dump
    dumps = os.listdir(str(tmp_path / 'flight'))
    assert dumps == ['flight_straggler_0001.json']      # throttled: one
    with open(os.path.join(str(tmp_path / 'flight'), dumps[0])) as f:
        assert any(s['name'] == 'perf.straggler'
                   for s in json.load(f)['spans'])


# ---- one burst in flight: the step's order -------------------------------


from paddle_tpu.serving import engine as engine_mod            # noqa: E402
from paddle_tpu.text.models import olmo_hybrid as O            # noqa: E402


@pytest.fixture(scope='module')
def recurrent():
    """A tiny model with per-slot recurrent state (gated delta-rule
    layers 3:1 with full attention), as tests/test_olmo_hybrid.py's."""
    paddle.seed(11)
    m = O.OlmoHybridForCausalLM(O.OlmoHybridConfig(
        vocab_size=211, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, linear_num_key_heads=4,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16))
    m.eval()
    return m


def _tails(model, prompts, budgets, **sampling):
    return [[int(t) for t in model.generate(
        paddle.to_tensor([p]), max_new_tokens=n, **s).numpy()[0][len(p):]]
        for p, n, s in zip(prompts, budgets, _per_request(sampling,
                                                          len(prompts)))]


def _per_request(sampling, n):
    """The i-th request's sampling arguments: `seed` counts up."""
    return [dict(sampling, seed=sampling['seed'] + i) if sampling else {}
            for i in range(n)]


def _churn():
    """More requests than slots, unequal budgets (one of a single
    token, one that ends mid-burst), two prompts that share a block."""
    rng = np.random.RandomState(3)
    shared = [int(t) for t in rng.randint(0, 211, 8)]
    prompts = [[int(t) for t in rng.randint(0, 211, n)]
               for n in (5, 11, 3, 9)]
    prompts += [shared + [4, 5, 6], shared + [9]]
    return prompts, [7, 1, 12, 6, 5, 9]


@pytest.mark.parametrize('sampling', [
    {}, {'do_sample': True, 'temperature': 0.8, 'top_k': 20, 'seed': 5}],
    ids=['greedy', 'sampled'])
def test_tokens_equal_generates_over_admissions_and_retirements(
        model, sampling):
    prompts, budgets = _churn()
    want = _tails(model, prompts, budgets, **sampling)
    eng = _paged(model)
    reqs = [eng.add_request(p, max_new_tokens=n, **s) for p, n, s in zip(
        prompts, budgets, _per_request(sampling, len(prompts)))]
    eng.run()
    assert [r.tokens for r in reqs] == want
    assert eng.metrics.report()['prefix_hits'] > 0
    assert eng._flight is None and eng._landed is None
    assert eng.compiled_sizes() == {'prefill': 1, 'decode': 1, 'verify': 0}


@pytest.mark.parametrize('sampling', [
    {}, {'do_sample': True, 'temperature': 0.7, 'top_k': 0, 'seed': 2}],
    ids=['greedy', 'sampled'])
def test_a_victim_preempted_under_a_burst_resumes_to_the_same_tokens(
        model, sampling):
    """The victim is evicted in the admit pass of a step that has just
    fetched a burst: its tokens of that burst are not delivered twice
    and not lost."""
    prompts, budgets = _churn()[0][:3], [12, 12, 6]
    want = _tails(model, prompts, budgets, **sampling)
    per = _per_request(sampling, 3)
    eng = _paged(model, preempt=True)
    seen = [[], [], []]
    r0, r1 = (eng.add_request(prompts[i], max_new_tokens=budgets[i],
                              on_token=seen[i].append, **per[i])
              for i in range(2))
    while min(len(r0.tokens), len(r1.tokens)) < 3:
        eng.step()
    assert eng._flight is not None
    r2 = eng.add_request(prompts[2], max_new_tokens=budgets[2], priority=1,
                         on_token=seen[2].append, **per[2])
    eng.run()
    assert eng.scheduler.preempted == 1
    assert [r0.tokens, r1.tokens, r2.tokens] == want == seen


def test_a_recurrent_models_tokens_are_its_own_forwards(recurrent):
    """State per slot: a slot released by count is re-used in the same
    step, and its new occupant starts from zeros."""
    rng = np.random.RandomState(6)
    prompts = [[int(t) for t in rng.randint(0, 211, n)]
               for n in (19, 7, 30, 12, 5)]
    budgets = [17, 1, 10, 12, 7]
    eng = PagedContinuousBatchingEngine(
        recurrent, num_seqs=2, max_len=64, page_size=8, prefill_chunk=16,
        decode_block=4, prefix_cache=False, preempt=True)
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts[:4], budgets)]
    while len(reqs[0].tokens) < 3:
        eng.step()
    reqs.append(eng.add_request(prompts[4], max_new_tokens=budgets[4],
                                priority=1))
    eng.run()
    assert eng.scheduler.preempted == 1
    ids = np.zeros((5, 64), np.int64)
    for row, p, r in zip(ids, prompts, reqs):
        assert len(r.tokens) == r.max_new_tokens
        row[:len(p) + len(r.tokens)] = p + r.tokens
    picks = recurrent(paddle.to_tensor(ids)).numpy().argmax(-1)
    for row, p, r in zip(picks, prompts, reqs):
        assert list(row[len(p) - 1:len(p) - 1 + len(r.tokens)]) == r.tokens


def _serial_schedule(prompts, budgets, block, **sched_kw):
    """{request index: engine step it is admitted in} under the SERIAL
    order (admit, prefill, burst, retire, all inside one step), from the
    scheduler's bookkeeping alone: what the engine's order must keep."""
    sched, _ = _mk_sched(**sched_kw)
    reqs = [Request(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    for r in reqs:
        sched.submit(r)
    gen, when, step = {}, {}, 0
    while sched.pending:
        step += 1
        for _, r in sched.admit():
            when[reqs.index(r)] = step
        for r, start, _, valid, final in sched.prefill_plan():
            sched.mark_prefilled(r, start + valid)
            if final:
                gen[r.id] = 1
        for r in [sched.resident[s] for s in sched.decode_slots()]:
            gen[r.id] = min(gen[r.id] + block, r.max_new_tokens)
        for r in [r for r in sched.resident.values()
                  if gen.get(r.id, 0) >= r.max_new_tokens]:
            sched.release(r)
            sched.finish(r)
    return when


def test_the_schedule_is_the_serial_orders(model, traced):
    """Two slots, a pool the two residents fill, three requests: the
    third enters in the step that fetches the first one's last burst,
    into the slot and the pages that step released by count, before the
    first one's last tokens are delivered."""
    tr, _ = traced
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    budgets = [5, 13, 6]
    shape = dict(num_seqs=2, num_pages=5, max_len=32, chunk=8, page=8,
                 prefix=False)
    eng = _paged(model, num_pages=5, prefix_cache=False, decode_block=4)
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    seen = []
    admit = eng.scheduler.admit

    def watched():
        got = admit()
        for slot, r in got:
            seen.append((reqs.index(r), eng._step_index, slot,
                         set(eng.scheduler.block_tables[slot])
                         - {SCRATCH_PAGE}, len(reqs[0].tokens),
                         reqs[0].done, eng.pages.in_use))
        return got
    eng.scheduler.admit = watched
    eng.step()
    # burst 1 takes request 0 to its budget of 5: by count, its slot and
    # pages are back where the burst is dispatched, the request pending
    assert eng._flight is not None and reqs[0].slot is None
    assert (eng.allocator.in_use, eng.scheduler.pending) == (1, 3)
    assert not reqs[0].done and len(reqs[0].tokens) == 1
    eng.run()
    when = {i: step for i, step, *_ in seen}
    assert when == _serial_schedule(prompts, budgets, 4, **shape) \
        == {0: 1, 1: 1, 2: 2}
    first_slot, first_pages = seen[0][2:4]
    i, step, slot, pages, delivered, done, in_use = seen[2]
    # step 2 fetched burst 1: request 0's slot and pages were request
    # 2's in that very pass, while request 0's last four tokens were
    # still to be delivered
    assert slot == first_slot and pages & first_pages
    assert (delivered, done) == (1, False)
    assert [len(r.tokens) for r in reqs] == budgets
    # the engine's own record agrees: `admitted` events by engine step
    steps = [s for s in tr.recorder.spans() if s['name'] == 'serving.step']
    for r in reqs:
        span = next(s for s in tr.recorder.spans()
                    if s['name'] == 'serving.request'
                    and s['tags']['request_id'] == r.id)
        at = next(e['mono'] for e in span['events']
                  if e['name'] == 'admitted')
        inside = [s['tags']['step'] for s in steps
                  if s['start_mono'] <= at <= s['end_mono']]
        assert inside == [when[reqs.index(r)]]


@pytest.mark.parametrize('slots,pages', [(2, 9), (3, 7), (4, 33)])
def test_a_backlogs_schedule_is_the_serial_orders(model, slots, pages):
    """A backlog limited by slots or by pages: every request enters at
    the engine step the serial order admits it in."""
    rng = np.random.RandomState(slots)
    prompts = [[int(t) for t in rng.randint(0, 211, int(rng.randint(1, 20)))]
               for _ in range(14)]
    budgets = [int(n) for n in rng.randint(1, 12, 14)]
    eng = PagedContinuousBatchingEngine(
        model, num_seqs=slots, max_len=32, page_size=8, prefill_chunk=8,
        decode_block=4, num_pages=pages, prefix_cache=False)
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    when, admit = {}, eng.scheduler.admit

    def watched():
        got = admit()
        when.update((reqs.index(r), eng._step_index) for _, r in got)
        return got
    eng.scheduler.admit = watched
    eng.run()
    assert when == _serial_schedule(
        prompts, budgets, 4, num_seqs=slots, num_pages=pages, max_len=32,
        chunk=8, page=8, prefix=False)
    assert [len(r.tokens) for r in reqs] == budgets


def test_a_bursts_tokens_come_after_the_next_bursts_dispatch(model, traced):
    """While a request is queued behind the one slot, burst K's tokens
    reach `on_token` after burst K+1 is on the device; once nobody is
    queued and a slot is free, a step delivers its own burst's tokens
    before it returns."""
    import time
    tr, _ = traced
    eng = _paged(model, num_seqs=1, decode_block=4)
    got = {'a': [], 'b': []}

    def sink(name):
        return lambda t: got[name].append(
            (time.monotonic(), eng._bursts, eng._flight is not None))
    a = eng.add_request([1, 2, 3], max_new_tokens=11, on_token=sink('a'))
    b = eng.add_request([4, 5], max_new_tokens=7, on_token=sink('b'))
    eng.run()
    assert (len(a.tokens), len(b.tokens)) == (11, 7)
    starts = {s['tags']['burst']: s['start_mono']
              for s in tr.recorder.spans()
              if s['name'] == 'serving.decode_burst'}
    assert sorted(starts) == [1, 2, 3, 4, 5]
    # a's first token (its final chunk's pick): burst 1 goes behind the
    # prefill call before the pick is read; tokens 2-5 are burst 1's,
    # delivered under burst 2; 6-9 under burst 3, which closes the lane
    # by count after 2 of its 4 steps; step 4 admits b into the freed
    # slot and delivers a's last two under b's first burst
    assert [(n, under) for _, n, under in got['a']] == (
        [(1, True)] + [(2, True)] * 4 + [(3, True)] * 4 + [(4, True)] * 2)
    # b: nobody is queued behind it, but the one slot is its own, so its
    # burst 4 stays in flight; burst 5 closes it, the slot is free, and
    # the step that dispatched burst 5 delivers its tokens itself
    assert [(n, under) for _, n, under in got['b']] == (
        [(4, True)] + [(5, True)] * 4 + [(5, False)] * 2)
    for t, n, _ in got['a'] + got['b']:
        assert t > starts[n]
    tails = [s['tags']['tail_under_burst'] for s in tr.recorder.spans()
             if s['name'] == 'serving.step']
    assert tails == [True, True, True, True, False]


def test_the_host_counts_what_the_device_counts(model, monkeypatch):
    """Lengths and counts are kept by arithmetic on the host and carried
    by the programs on the device: they agree at every dispatch, and a
    burst that follows a burst sends no lane array."""
    eng = _paged(model, decode_block=2)
    puts = []
    put = jax.device_put
    monkeypatch.setattr(engine_mod.jax, 'device_put',
                        lambda x, *a, **k: (puts.append(1), put(x, *a, **k))[1])
    reqs = [eng.add_request([1, 2, 3], max_new_tokens=9),
            eng.add_request([4, 5], max_new_tokens=5),
            eng.add_request([6], max_new_tokens=5)]
    sent, checked = [], 0
    while eng.scheduler.pending:
        before = len(puts)
        eng.step()
        sent.append(len(puts) - before)
        flight = eng._flight
        if flight is None:
            continue
        # the host holds the lanes as the burst in flight leaves them
        # (a lane it closes is cleared on the host, and sent again)
        live = [slot for slot, _, _, closed in flight.lanes if not closed]
        _, dev_lens, dev_gen = eng._lane_args[:3]
        assert (np.asarray(dev_lens)[live] == eng._lens[live]).all()
        assert (np.asarray(dev_gen)[live] == eng._gen[live]).all()
        assert (eng._gen[live] < eng._budgets[live]).all()
        checked += len(live)
    assert [len(r.tokens) for r in reqs] == [9, 5, 5] and checked >= 4
    # step 1 admits two (sent); step 2 follows a burst with a burst and
    # closes the second request under it; step 3 admits the third into
    # its slot (sent); step 4 follows and closes both: nothing queued,
    # both slots free, so it waits for its own burst
    assert sent == [1, 0, 1, 0]


def test_no_door_loses_a_token_with_a_burst_in_flight(model):
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9]]
    want = _tails(model, prompts, [9, 9, 9])
    # (the third request is queued behind the two slots: bursts stay in
    # flight.) shutdown() brings the burst home: what was generated is
    # delivered
    eng = _paged(model, decode_block=4)
    reqs = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    eng.step()
    assert eng._flight is not None
    assert [len(r.tokens) for r in reqs] == [1, 1, 0]
    eng.shutdown()
    assert eng._flight is None
    assert [len(r.tokens) for r in reqs] == [5, 5, 0]
    assert eng.scheduler.pending == 3       # still to be driven home
    eng.run()
    assert [r.tokens for r in reqs] == want and eng.scheduler.pending == 0
    # a request a burst in flight will finish is released by count and
    # still pending, so run() and `while step()` drain it
    eng = _paged(model, decode_block=4)
    reqs = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    in_flight = 0
    while eng.step():
        in_flight += eng._flight is not None
        assert eng.scheduler.pending == len(eng.scheduler.queue) + len(
            eng.scheduler.resident) + len(eng.scheduler.closing)
    assert in_flight >= 2
    assert [r.tokens for r in reqs] == want and eng._flight is None
    assert all(r.done and r.wait(0) for r in reqs)
    # generate() and stream()
    eng = _paged(model, decode_block=4)
    assert eng.generate(prompts, max_new_tokens=9) == want
    streamed = [eng.add_request(p, max_new_tokens=9, stream=True)
                for p in prompts]
    assert [list(eng.stream(r)) for r in streamed] == want
    assert eng.scheduler.pending == 0 and eng._flight is None


def test_an_idle_queue_gets_an_idle_device_back(model):
    """Nothing queued and a slot free: whoever arrives next could be
    admitted at the next pass, so the step waits for its own burst and
    its tokens are on the request when step() returns."""
    eng = _paged(model, decode_block=4)
    fam = eng.metrics.registry.get('serving_steps_total')
    before = fam.labels('overlapped').value()
    req = eng.add_request([1, 2, 3], max_new_tokens=9)
    eng.step()
    assert eng._flight is None and len(req.tokens) == 5
    late = eng.add_request([4, 5], max_new_tokens=3)    # arrives between
    eng.step()                                          # two steps
    assert len(late.tokens) == 3 and late.done          # no burst to wait out
    assert len(req.tokens) == 9 and req.done
    assert fam.labels('overlapped').value() == before


@pytest.mark.parametrize('seed', [0, 1, 7, 2 ** 31 - 1, 2 ** 31 + 5,
                                  2 ** 32 - 1, 2 ** 32 + 9, 2 ** 40 + 3,
                                  -1, -12345])
@pytest.mark.parametrize('x64', [False, True])
def test_the_host_made_key_is_jaxs(seed, x64):
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = engine_mod._prng_key(seed)
    assert got.dtype == want.dtype == np.uint32
    assert got.tolist() == want.tolist()
