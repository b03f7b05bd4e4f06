"""Paged continuous batching: block-granular KV + prefix reuse + spec
decode, in exactly THREE compiled programs (and one two-word write into
the decode carry) behind a thread-safe door.

The engine keeps one physical pool of fixed-size pages per layer and maps
sequences onto it through host numpy block tables (vLLM's PagedAttention
layout):

  - a sequence holds only the pages its actual length needs (reserved
    up front at admission — residents can never fail mid-flight);
  - requests sharing a prompt prefix map their leading block-table
    entries to the SAME already-filled pages (PrefixCache, chain-hashed
    full blocks) and skip that part of prefill entirely;
  - optionally, an n-gram proposer drafts K tokens per decode step and
    ONE batched verify forward accepts the longest prefix matching the
    model's own greedy picks — up to K+1 tokens per dispatch, output
    token-identical to sequential generate() by construction (every
    accepted token equals the greedy pick the model would have made).

Program set, each with one static shape, so request admit/retire churn
can never retrace (trace-count gauges assert it):

  prefill chunk  — [1, C] prompt tokens through one sequence's block-
                   table row (row offset / valid count are traced);
  decode burst   — K cached steps for ALL sequences in one dispatch
                   (lax.scan; per-step `step_active` masking freezes
                   finished or still-prefilling rows in-program, so the
                   burst length never depends on occupancy; spec off);
  verify pass    — [S, K+1] draft tokens for ALL sequences (spec on).

Only the layers' state lives on device — per layer, as the model names
it (`cache_specs()`, serving/kv_cache.py): the page pools of a layer that
keeps K/V rows, or `[num_seqs, ...]` arrays that belong to a slot for a
recurrent layer (a linear-attention state) — and the decode carry: the
token each lane feeds next and its PRNG key, results of one program
handed to the next without a visit to the host. Block tables, lengths
and the other per-lane control arrays are host numpy that the host keeps
by arithmetic and sends when admission or retirement changed them
(values change freely, shapes never). ONE decode burst stays in flight
across `step()`'s return wherever an arrival could not be admitted
before it anyway: what the host does with a burst's tokens runs while
the device runs the next (`step`'s docstring has the order).
A recurrent layer's state cannot be shared by prefix nor taken
back after a rejected draft, so a model with one runs with
`prefix_cache=False` and `spec_k=0` (the constructor says so); a
preempted request recomputes from position 0, state and all.

Greedy output is token-identical to sequential generate(): rows at or
beyond a sequence's length are unreachable garbage (masked scores hit
-1e9 and underflow to 0.0 after the f32 softmax), attention writes at
the pre-step offsets and the ENGINE advances lengths; sampling mirrors
generate()'s per-request PRNG stream (one split at prefill, one per
decode step, advanced only on active steps).
"""
import queue as _queue
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import functional as _fm
from ..framework.core import Tensor, no_grad_guard
from ..monitor import events as _events
from ..monitor import tracing as _tracing
from ..monitor.perf import CompileWatchdog, StepTimeline, compile_log
from ..monitor.perf import costmodel as _costmodel
from .kv_cache import (PageAllocator, PrefixCache, SlotAllocator,
                       build_paged_pools, cache_specs, kv_row_bytes,
                       latent_row_bytes, layer_caches, layer_counters,
                       layer_state, state_bytes_per_seq)
from .metrics import ServingMetrics
from .scheduler import PagedScheduler, Request

__all__ = ['PagedContinuousBatchingEngine', 'NGramProposer']


def _topk_threshold(lt, topk):
    """The value a row's top-k keeps down to: the `topk`-th largest of
    the float32 row `lt` — `jnp.sort(lt)[clip(V - topk, 0, V - 1)]`, bit
    for bit, so topk >= V keeps all — or -inf for topk == 0 (no
    threshold). Found by selection, not by sorting the vocabulary.

    Floats map onto uint32 so that integer order is float order; the
    answer is the largest `t` with at least topk keys >= t, built from
    the top bit down: 32 compare-and-count passes over the row. Exact,
    ties included (-0.0 sorts under 0.0 here and beside it in a sort,
    which no `lt >= thr` can tell apart)."""
    top = jnp.uint32(1 << 31)
    bits = jax.lax.bitcast_convert_type(lt, jnp.uint32)
    keys = jnp.where(bits >= top, ~bits, bits | top)
    k = jnp.clip(topk, 1, lt.shape[-1])

    def narrow(i, t):
        cand = t | (top >> i.astype(jnp.uint32))
        return jnp.where(jnp.sum(keys >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, narrow, jnp.uint32(0))
    kth = jax.lax.bitcast_convert_type(jnp.where(t >= top, t ^ top, ~t),
                                       jnp.float32)
    return jnp.where(topk > 0, kth, -jnp.inf)


def _sample_token(lg, key, temp, topk, sample):
    """ONE row's pick where some row of the batch samples — generate()'s:
    temperature/top-k categorical under the `sample` flag, else the
    argmax; ties at the top-k threshold are kept."""
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lt = lg / jnp.maximum(temp, 1e-6)
    lt = jnp.where(lt >= _topk_threshold(lt, topk), lt, -1e30)
    sampled = jax.random.categorical(key, lt).astype(jnp.int32)
    return jnp.where(sample, sampled, greedy)


@jax.named_scope('serving.pick_token')    # names the device ops, no more
def _pick_tokens(lg, keys, temps, topks, sample):
    """Next token for each row of logits [N, V]. The program branches on
    what the BATCH holds (a `lax.cond` outside the vmap: under it a
    per-row predicate lowers to a select and runs both sides): no row
    samples — the argmax and nothing else; some row does — every row
    through `_sample_token`, a greedy row still getting its argmax.
    `sample` is already masked to the rows whose pick is kept."""
    lg = lg.astype(jnp.float32)
    return jax.lax.cond(
        jnp.any(sample),
        lambda: jax.vmap(_sample_token)(lg, keys, temps, topks, sample),
        lambda: jnp.argmax(lg, axis=-1).astype(jnp.int32))


class NGramProposer:
    """Prompt-lookup drafting: find the most recent earlier occurrence
    of the sequence's trailing n-gram and propose whatever followed it.

    Free (no draft model, no device work) and surprisingly effective on
    serving traffic, where outputs quote their prompts — exactly the
    regime prefix sharing also targets. Wrong drafts cost only their
    share of one verify pass; the accept rule keeps output exact.
    """

    def __init__(self, n=2):
        if n < 1:
            raise ValueError('n-gram size must be >= 1')
        self.n = int(n)

    def propose(self, history, k):
        """k draft ids continuing `history` (prompt + generated so far).
        Falls back to repeating the last token when the n-gram has no
        earlier occurrence — a cheap guess beats proposing nothing,
        since the verify pass runs at [S, K+1] either way."""
        n = min(self.n, len(history) - 1)
        draft = []
        if n > 0:
            tail = history[-n:]
            for i in range(len(history) - n - 1, -1, -1):
                if history[i:i + n] == tail:
                    draft = list(history[i + n:i + n + k])
                    break
        last = history[-1]
        while len(draft) < k:
            draft.append(draft[-1] if draft else last)
        return draft[:k]


def _prng_key(seed):
    """The two words of `jax.random.PRNGKey(seed)` (threefry2x32, the
    stream generate() draws from), made on the host: an admission
    dispatches nothing and waits for nothing. The high word is the
    seed's only where jax holds 64-bit integers."""
    hi = seed >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([hi & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


class _Flight:
    """The decode burst that is on the device: what its dispatch knew
    (which lanes advance, by how many steps, and whether that takes them
    to their budget: `step_active`'s rule, by count), the device arrays
    `_deliver` will read, and its clock reads."""

    __slots__ = ('index', 'lanes', 'toks', 'counted', 'span', 't0', 't1')

    def __init__(self, index, lanes, toks, counted, span, t0, t1):
        self.index = index        # the engine's count of bursts
        self.lanes = lanes        # [(slot, request, steps, closed?)]
        self.toks = toks          # device [decode_block, S]
        self.counted = counted    # device {layer counter: scalar}
        self.span = span          # `serving.decode_burst`, open
        self.t0, self.t1 = t0, t1     # dispatch entered / returned


class PagedContinuousBatchingEngine:
    """Page-granular continuous batching over a decoder that names its
    per-layer caches (`cache_specs()`): GPTForCausalLM,
    OlmoHybridForCausalLM.

    Front door (`add_request` / `step` / `run` / `stream` / `generate`)
    is thread-safe: any number of threads may submit and drive; an RLock
    serializes scheduler state and device dispatches while `Request.wait`
    and stream consumption stay lock-free. `spec_k > 0` replaces the
    decode burst with draft-and-verify and is greedy-only: sampled
    requests are rejected at add_request, because the accept rule
    compares drafts against argmax picks.

    With the tracer on, a step explains itself: `serving.step` with
    `serving.step.wait` (the host blocked on the burst in flight),
    `serving.step.admit` and `serving.step.prefill` (one
    `serving.prefill_call` per jitted call) as children, each also a
    TraceAnnotation, so the same names sit in the flight ring on the
    engine's clock and in a device trace's host plane.
    `serving.decode_burst` runs from a burst's dispatch to its end as
    the host sees it: it can outlive the step that dispatched it and is
    no step's child. With the tracer off, step() opens no span.
    """

    # traced-body counter keys, one per compiled program; the zero-
    # retrace assertion is `trace_counts` staying at most one per key
    # across an arbitrary admit/retire workload
    _programs = ('prefill', 'decode', 'verify')

    def __init__(self, model, num_seqs=8, max_len=None, page_size=16,
                 num_pages=None, prefill_chunk=16, decode_block=4,
                 spec_k=0, ngram=2, prefix_cache=True, preempt=False,
                 max_preempts=None, donate=None):
        model.eval()
        self._model = model
        self.num_slots = int(num_seqs)
        self.max_len = int(max_len or model.config.max_position_embeddings)
        if self.max_len > model.config.max_position_embeddings:
            raise ValueError(
                'max_len %d exceeds max_position_embeddings %d'
                % (self.max_len, model.config.max_position_embeddings))
        self.page_size = int(page_size)
        self.num_blocks = -(-self.max_len // self.page_size)
        if num_pages is None:
            # parity default: enough for every sequence at max_len plus
            # scratch. Real deployments size the pool to ACTUAL length
            # distributions (the density win); the scheduler's up-front
            # reservation keeps a small pool safe, just slower to admit.
            num_pages = self.num_slots * self.num_blocks + 1
        self.num_pages = int(num_pages)
        self.decode_block = int(decode_block)
        if self.decode_block < 1:
            raise ValueError('decode_block must be >= 1')
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError('spec_k must be >= 0')
        self._proposer = NGramProposer(ngram) if self.spec_k else None
        # programs that must trace before the watchdog's warmup barrier
        # can be declared: the verify program only ever traces when
        # speculation is on, and must not be waited for forever without
        self._warm_programs = (self._programs if self.spec_k
                               else ('prefill', 'decode'))
        # what each layer keeps (the model names it): K/V rows in the
        # page pool, or per-SLOT arrays that every token rewrites
        self._specs = cache_specs(model)
        self._state_seq_bytes = state_bytes_per_seq(self._specs)
        self._latent_page_bytes = latent_row_bytes(self._specs) \
            * int(page_size)
        # what the layers counted on the device in the last burst
        # (`layer_counters`), fetched with its tokens: {} for a model
        # that counts nothing
        self._burst_counters = {}
        if self._state_seq_bytes and prefix_cache:
            raise ValueError(
                'prefix_cache=True with a recurrent layer: shared pages '
                'hold K/V rows only, so a prefix hit would start the '
                "recurrent layers' state from zeros at the hit's end "
                'instead of from the prefix. Pass prefix_cache=False '
                '(snapshots of state per cached block are not built yet).')
        if self._state_seq_bytes and self.spec_k:
            raise ValueError(
                'spec_k=%d with a recurrent layer: a verify pass advances '
                'the state over every draft and cannot take back the ones '
                'the accept rule rejects (rejected K/V rows are simply '
                'overwritten; a state has no dead rows). Pass spec_k=0.'
                % self.spec_k)
        self.metrics = ServingMetrics()
        self._params = _fm.extract_params(model)
        self._bufs = _fm.extract_buffers(model)
        # per-lane control state lives HOST-side as numpy: admission and
        # retirement mutate it in place for free instead of dispatching
        # an eager .at[].set() per field, and a burst's effect on it is
        # arithmetic (a lane advances one position a burst step until
        # its budget: `_decode_fn`'s `step_active`), done where the
        # burst is dispatched: the host holds the lanes as the burst in
        # flight will leave them.
        s = self.num_slots
        self._gen = np.zeros((s,), np.int32)          # tokens generated
        self._budgets = np.zeros((s,), np.int32)      # max_new_tokens
        self._active = np.zeros((s,), bool)           # slot decodes?
        self._temps = np.ones((s,), np.float32)
        self._topks = np.zeros((s,), np.int32)
        self._sample = np.zeros((s,), bool)
        # the decode carry lives on the DEVICE: results of one program
        # (`_decode_fn`'s outputs, a final chunk's pick and key through
        # `_carry_fn`) and arguments of the next
        self._last = jnp.zeros((s, 1), jnp.int32)     # token fed next step
        self._keys = jnp.zeros((s, 2), jnp.uint32)    # per-slot PRNG
        # the speculative path drafts on the host: its copy of `_last`
        self._spec_last = np.zeros((s,), np.int32)
        self._requests = {}                           # slot -> Request
        # the lane arrays as the last dispatch left them on the device
        # (lengths and counts: that burst's own results), good for the
        # next one until `_lanes_dirty` says the host changed a lane
        self._lane_args = None
        self._lanes_dirty = True
        self._flight = None       # the burst on the device (`_Flight`)
        self._landed = None       # ended, its tokens not yet delivered
        # (dispatch, dispatch-to-end, waited) seconds of the bursts that
        # ended in the running step, for the timeline
        self._ended = []
        self._bursts = 0          # bursts dispatched, the spans' `burst`
        # final chunks dispatched this step, their picks still on the
        # device: [(slot, request, pick, closed by count?)]
        self._picks = []
        self._lock = threading.RLock()
        self._closed = False
        # cached at construction (like the registry): swap the default
        # tracer BEFORE building the engine under test
        self._tracer = _tracing.default_tracer()
        # wide-event request log, same caching rule
        self.events = _events.default_request_log()
        self.trace_counts = {k: 0 for k in self._programs}
        # scrape-visible retrace canary: flat at 1 per program == the
        # bounded-compilation contract holds in production, not just
        # under the test
        trace_gauge = self.metrics.registry.gauge(
            'serving_trace_count',
            'times each serving program has been traced '
            '(flat == zero retrace)', ('program',))
        self._m_trace = {k: trace_gauge.labels(k)
                         for k in self.trace_counts}
        # performance introspection (monitor/perf): the watchdog turns
        # the "exactly one program per key" invariant from a test
        # assertion into a production watch — once every program this
        # engine will run has traced, step() declares the warmup
        # barrier and any further compile on THIS engine's stack is a
        # counted, attributed recompile (hard-fail under
        # PADDLE_TPU_COMPILE_STRICT=1). The timeline splits each decode
        # burst into host-dispatch vs device-blocked time.
        self.perf = CompileWatchdog(registry=self.metrics.registry,
                                    tracer=self._tracer, owner=self,
                                    name=type(self).__name__)
        self.timeline = StepTimeline(registry=self.metrics.registry,
                                     tracer=self._tracer)
        self._decode_args = None
        self._step_index = 0
        self._pools = build_paged_pools(model, self.num_pages,
                                        self.page_size, self.num_slots)
        self.pages = PageAllocator(self.num_pages)
        self.prefix = (PrefixCache(self.page_size, self.pages)
                       if prefix_cache else None)
        self.allocator = SlotAllocator(self.num_slots)
        self.scheduler = PagedScheduler(self.allocator, self.pages,
                                        self.max_len, prefill_chunk,
                                        self.page_size, self.prefix)
        # priority preemption: a page-blocked high-priority arrival may
        # evict strictly-lower-priority residents (scheduler policy);
        # this engine's hook clears the freed lane and accounts the
        # eviction. max_preempts bounds how often one request may lose
        # its pages before it is finished terminally (outcome
        # 'preempted') instead of requeued.
        self.scheduler.preempt_enabled = bool(preempt)
        self.scheduler.max_preempts = (None if max_preempts is None
                                       else int(max_preempts))
        self.scheduler.on_preempt = self._on_preempt
        # billing unit for kv_byte_seconds: one physical page
        self._kv_page_bytes = kv_row_bytes(self._specs) * self.page_size
        # per-row KV length (rows written), the block-table companion to
        # the host control arrays above. Mid-prefill rows track
        # consumed so in-program garbage writes from frozen lanes land
        # on rows the next real pass overwrites anyway.
        self._lens = np.zeros((self.num_slots,), np.int32)
        self._prefix_seen = [0, 0]    # hit/miss totals already reported
        # which K/V read each program took ('pool' | 'gather'), written
        # where the program is traced (`_unpack`) from what attention
        # recorded on the caches it returned; the spans' `kv_read` tag.
        # Likewise which product an expert layer took ('dense' |
        # 'grouped'), for a model that has one: the tag `experts`
        self.kv_read = {}
        self.experts = {}
        if donate is None:
            donate = jax.default_backend() in ('tpu', 'gpu')
        dn = (2,) if donate else ()
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=dn)
        self._carry_jit = jax.jit(self._carry_fn,
                                  donate_argnums=(0, 1) if donate else ())
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=dn)
        self._verify_jit = jax.jit(self._verify_fn, donate_argnums=dn)
        self._verify_args = None
        # the compile log tags what these build with this class's name
        compile_log().own(type(self).__name__, self._prefill_fn,
                          self._carry_fn, self._decode_fn, self._verify_fn)

    @property
    def num_seqs(self):
        return self.num_slots

    # ---- front door ---------------------------------------------------

    def add_request(self, prompt, max_new_tokens=32, temperature=1.0,
                    top_k=0, do_sample=False, seed=0, stream=False,
                    tenant=None, priority=0, model=None, emit_event=True,
                    arrival_t=None, on_token=None):
        """Queue a generation request; returns the Request handle.

        `tenant` is the attribution dimension: it rides the request into
        the per-tenant metric families and the wide event. `model` is
        the second attribution dimension (multi-model gateways route on
        it; a single-model engine just records it). `priority` (int,
        higher wins) orders admission and — with preempt=True — marks
        lower-priority residents evictable.
        `emit_event=False` suppresses this engine's wide event — the
        gateway sets it so a failed-over request still produces exactly
        ONE canonical record (the gateway's, which knows the failover
        history). `arrival_t` states when the request was DUE, on the
        engine's clock (`engine.metrics.now()`, time.monotonic): an
        open-loop caller that hands requests over late passes the due
        time, and TTFT, queue wait and the `queued` event run from it;
        the default is the clock on entry, before the engine's lock.
        `on_token(token)` is called for every token delivered to this
        request, in order, on the thread driving step() and under the
        engine's lock — keep it short; tokens regenerated after a
        preemption are not delivered twice."""
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k,
                      do_sample=do_sample, seed=seed, tenant=tenant,
                      priority=priority, model=model)
        req._emit_event = bool(emit_event)
        if arrival_t is not None:
            req._arrival_t = float(arrival_t)
        req.on_token = on_token
        if stream:
            req._stream_q = _queue.Queue()
        return self.enqueue(req)

    def enqueue(self, req):
        """Admit a pre-built scheduler.Request through the front door —
        the ModelHost path: a multi-model host constructs the Request at
        submission (stamping its arrival time), parks it while weights
        load, then enqueues it here without re-timestamping. All
        validation, metrics and tracing of add_request happen here.
        A request without an arrival stamp gets the clock ON ENTRY:
        step() holds the lock for a whole step, and the wait for it is
        part of what the caller waited."""
        if req._arrival_t is None:
            req._arrival_t = self.metrics.now()
        req._emit_event = getattr(req, '_emit_event', True)
        req._tenant_label = self.metrics.tenant_label(req.tenant)
        req._model_label = self.metrics.model_label(
            getattr(req, 'model', None))
        # front-door guard: a request whose worst case — prompt plus
        # every generated token but the last — cannot fit the cache
        # would sit at the queue head forever, wedging admission for
        # everyone behind it. Fail loud at submission.
        worst = len(req.prompt) + req.max_new_tokens - 1
        if len(req.prompt) and worst > self.max_len:
            raise ValueError(
                'request cannot ever be admitted: prompt of %d tokens + '
                'max_new_tokens=%d needs %d cache rows but max_len=%d'
                % (len(req.prompt), req.max_new_tokens, worst,
                   self.max_len))
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    'engine is shut down — it no longer admits requests')
            if self.spec_k and req.do_sample:
                raise ValueError(
                    'speculative decoding (spec_k=%d) is greedy-only: '
                    'the accept rule compares drafts against argmax '
                    'picks. Submit with do_sample=False or run '
                    'spec_k=0.' % self.spec_k)
            self.scheduler.submit(req)
            self.metrics.on_arrival(req.id, req._arrival_t)
            tr = self._tracer
            if tr.enabled:
                tags = {'request_id': req.id,
                        'prompt_len': len(req.prompt),
                        'max_new_tokens': req.max_new_tokens}
                if req.tenant is not None:
                    tags['tenant'] = req._tenant_label
                if getattr(req, 'model', None) is not None:
                    tags['model'] = req._model_label
                # root=True: the request owns its trace even when
                # submitted inside a gateway routing/failover span —
                # tail retention decides at THIS span's finish, and the
                # wide event's trace_id joins to exactly this tree
                req._span = tr.start_span('serving.request', tags=tags,
                                          root=True, mono=req._arrival_t)
                req._span.add_event('queued', mono=req._arrival_t,
                                    queue_depth=len(self.scheduler.queue))
        return req

    def shutdown(self):
        """Refuse all future add_request calls, and bring home the burst
        that is on the device: its tokens are delivered here, so what
        was generated is on the requests when this returns. Requests
        still resident may be driven to completion with step()/run();
        shutdown only closes the front door."""
        with self._lock, no_grad_guard():
            self._closed = True
            self._land()
            self._deliver()
            self._end_bursts()
            self.perf.close()

    def step(self):
        """One scheduler iteration; returns the number of requests still
        pending. One decode burst stays in flight across the return:

            wait for the burst the last step queued to END         (the
                step's one wait for the device that nothing hides)
            admit; dispatch the prefill calls, none of them read back
            dispatch the next burst        -- the device is busy from here
            close BY COUNT the lanes it will finish: slot, pages, lane
            read the final chunks' picks (the burst runs behind them)
            and the ended burst's tokens (on their way to the host since
            its dispatch), deliver both, finish the requests the ended
            burst closed, metrics

        so what the host does with a burst's tokens, and what the caller
        does between two steps, runs while the device runs the next
        burst; the next burst is dispatched only after the running one
        ended. What admission depends on is released by count (nothing
        can take a slot or a page before the next admit, which follows
        the burst's end), so it is free in the step a serial order frees
        it in; what needs token values happens after the dispatch.

        A caller hands requests over BETWEEN steps, so one that arrives
        under a burst left in flight is seen only after the next burst
        is queued too, and waits out both. Where that can cost anyone —
        nothing is queued and a slot is free, so whoever arrives could
        be admitted at the next pass — the step waits for its own burst
        before it returns (the same wait, at the step's end) and hands
        back an idle device, as a serial order does. Where a head is
        queued behind full slots or pages, an arrival waits behind it
        (FIFO) whatever the device does, and the burst stays in flight.

        The speculative path (`spec_k`) drafts from the delivered tokens
        and so reads before it dispatches: dispatch, read, deliver in
        one step."""
        with self._lock, no_grad_guard():
            self._step_index += 1
            tr, sched = self._tracer, self.scheduler
            with tr.start_span('serving.step', annotate=True) as sp:
                if sp:
                    # wall >> CPU reads "waiting on the device", wall ==
                    # CPU reads "the host was busy"
                    cpu0 = time.process_time()
                    compiles0 = self.perf.counts['compile']
                self._land()
                if sp:
                    slots, nbytes = self._state_in_use()
                    sp.tags.update(step=self._step_index,
                                   residents=len(sched.resident),
                                   queue_depth=len(sched.queue),
                                   slots_in_use=self.allocator.in_use,
                                   pages_in_use=self.pages.in_use,
                                   state_slots_in_use=slots,
                                   state_bytes=nbytes,
                                   latent_bytes=self._latent_in_use())
                with tr.start_span('serving.step.admit',
                                   annotate=True) as ph_admit:
                    admitted = self._admit()
                    if ph_admit:
                        ph_admit.tags.update(admitted=admitted,
                                             left=len(sched.queue),
                                             head_left=sched.head_left)
                with tr.start_span('serving.step.prefill',
                                   annotate=True) as ph_prefill:
                    calls, tokens = self._prefill_step()
                    # a final chunk's pick is read in this step (a first
                    # token does not wait out a burst): the burst goes
                    # behind the calls first, and the phase ends with
                    # their picks on the host. The speculative path
                    # reads before it dispatches
                    behind = bool(self._picks) and not self.spec_k
                    if behind:
                        self._decode_step()
                    if ph_prefill:
                        ph_prefill.tags.update(calls=calls, tokens=tokens,
                                               picks=len(self._picks))
                    self._deliver_picks()
                if not behind:
                    self._decode_step()
                # whoever arrives under this burst could be admitted at
                # the next pass: it must not find a second one queued
                in_flight = self._flight is not None
                overlapped = in_flight and bool(
                    sched.queue or not self.allocator.available)
                self._deliver()
                if sched.head_left != 'none':
                    self.metrics.on_admit_blocked(sched.head_left)
                self.metrics.on_prefill_calls(calls)
                self.metrics.on_step(self.allocator.in_use, self.num_slots,
                                     overlapped)
                self.metrics.on_queue_depth(len(sched.queue))
                self.metrics.on_pages_in_use(self.pages.in_use)
                self.metrics.on_state_bytes(self._state_in_use()[1])
                self.metrics.on_latent_bytes(self._latent_in_use())
                if self.prefix is not None:
                    h, m = self.prefix.hits, self.prefix.misses
                    self.metrics.on_prefix_lookup(
                        h - self._prefix_seen[0], m - self._prefix_seen[1])
                    self._prefix_seen = [h, m]
                for prog, child in self._m_trace.items():
                    child.set(self.trace_counts[prog])
                if not self.perf.armed and all(
                        self.trace_counts[p] > 0
                        for p in self._warm_programs):
                    self.perf.declare_warmup(
                        '%s steady state' % type(self).__name__)
                if in_flight and not overlapped:
                    self._land()
                    self._deliver()
                if self._ended and self._burst_counters:
                    self.metrics.on_layer_counters(self._burst_counters)
                detail = None
                if sp:
                    if self._ended:
                        sp.tags.update(self._burst_counters)
                    sp.set_tag('tail_under_burst', overlapped)
                    sp.set_tag('cpu_s', time.process_time() - cpu0)
                    sp.finish()
                    detail = self._step_detail(
                        sp, ph_admit, ph_prefill,
                        self.perf.counts['compile'] - compiles0)
                self._end_bursts(detail)
            return sched.pending

    def _step_detail(self, sp, ph_admit, ph_prefill, compiles):
        """Where a step went, for a `perf.straggler` record: phase
        seconds from the spans' own stamps (self = the step minus its
        child spans: the waits, admit, prefill), CPU seconds, compiles
        counted during it, its index."""
        admit = ph_admit.end_mono - ph_admit.start_mono
        prefill = ph_prefill.end_mono - ph_prefill.start_mono
        wait = sum(w for _, _, w in self._ended)
        wall = sp.end_mono - sp.start_mono
        return {'engine_step': self._step_index, 'step_s': wall,
                'wait_s': wait, 'admit_s': admit, 'prefill_s': prefill,
                'self_s': wall - wait - admit - prefill,
                'cpu_s': sp.tags['cpu_s'], 'compiles': compiles}

    def _end_bursts(self, detail=None):
        """The timeline's step is a burst, from its dispatch to its end
        as the host saw it (its totals feed the straggler rule and the
        burst percentiles): one for each burst that ended in this engine
        step; a flagged one carries the step's phases beside its own."""
        ended, self._ended = self._ended, []
        for dispatch, block, _ in ended:
            self.timeline.record('host_dispatch', dispatch)
            self.timeline.record('device_block', block)
            self.timeline.end_step(detail=detail and dict(
                detail, burst_dispatch_s=dispatch, burst_block_s=block))

    def run(self):
        """Drive until every submitted request has finished."""
        while True:
            with self._lock:
                if not self.scheduler.pending:
                    return
                self.step()

    def generate(self, prompts, **sampling):
        """Blocking batch door: submit all, drive to completion, return
        generated ids per prompt (prompt not included) in order."""
        reqs = [self.add_request(p, **sampling) for p in prompts]
        self.run()
        return [r.tokens for r in reqs]

    def stream(self, req):
        """Yield req's tokens as they are produced. Cooperative: if no
        other thread is driving the engine, this one steps it."""
        q = req._stream_q
        if q is None:
            raise ValueError('request was not added with stream=True')
        while True:
            try:
                tok = q.get_nowait()
            except _queue.Empty:
                if req.done:
                    return         # sentinel already consumed
                self.step()
                continue
            if tok is None:
                return
            yield tok

    def compiled_sizes(self):
        """Times each program has been traced — the no-retrace metric."""
        return dict(self.trace_counts)

    def rebind_perf(self, registry):
        """Move the perf instrumentation onto `registry` (the gateway
        replica pattern: engine metrics live on a private per-replica
        registry so counters stay per-replica honest). The fresh
        watchdog starts disarmed; the next step() re-declares warmup
        once the trace counts check out."""
        self.perf.close()
        self.perf = CompileWatchdog(registry=registry,
                                    tracer=self._tracer, owner=self,
                                    name=type(self).__name__)
        self.timeline = StepTimeline(registry=registry,
                                     tracer=self._tracer)
        return self

    def perf_estimate(self, bursts=None, wall_seconds=None):
        """Cost-model estimate of the steady-state program (the
        dollar spender): analytic flops/bytes, roofline bound, warm
        compile seconds — plus mfu_est when told how many decode bursts
        ran over a measured wall. None before the first burst dispatch.

        The deliberate lower+compile here is watchdog-suspended (it is
        a measurement, not a retrace) and reuses the exact arrays of
        the last dispatch, so the traced avals match and the program's
        trace count stays flat."""
        # under speculation the verify forward is the steady-state
        # spender (the plain decode program never dispatches)
        if self.spec_k and self._verify_args is not None:
            jit_fn, args = self._verify_jit, self._verify_args
        else:
            jit_fn, args = self._decode_jit, self._decode_args
        if args is None:
            return None
        with self._lock, self.perf.suspended():
            t0 = time.monotonic()
            compiled = jit_fn.lower(*args).compile()
            warm_s = time.monotonic() - t0
        step_s = None
        if bursts and wall_seconds and bursts > 0:
            step_s = wall_seconds / float(bursts)
        est = _costmodel.estimate(compiled, step_seconds=step_s)
        if est is None:
            return None
        est['compile_s_warm'] = warm_s
        return est

    @property
    def occupancy(self):
        return self.allocator.occupancy

    def _state_in_use(self):
        """(slots whose recurrent state belongs to a resident, its
        bytes): zeros for a model that keeps K/V rows only."""
        slots = self.allocator.in_use if self._state_seq_bytes else 0
        return slots, slots * self._state_seq_bytes

    def _latent_in_use(self):
        """Bytes of latent rows in the pages sequences hold: 0 for a
        model without a latent layer."""
        return self.pages.in_use * self._latent_page_bytes

    # ---- scheduler glue (lock held) -----------------------------------

    def _admit(self):
        admitted = self.scheduler.admit()
        for slot, req in admitted:
            req._admit_t = self.metrics.now()
            self.metrics.on_admitted(req.id)
            if req._preempts:
                # a previously preempted request coming back: the
                # regenerated prefix is swallowed via req._replay, so
                # the caller-visible stream resumes where it stopped
                self.metrics.on_resumed(req._tenant_label)
                if req._span is not None:
                    req._span.add_event('resumed', preempts=req._preempts)
            if req._span is not None:
                req._span.add_event('admitted', mono=req._admit_t,
                                    slot=slot,
                                    blocked=dict(req._admit_waits))
                req._phase = self._tracer.start_span(
                    'serving.prefill', parent=req._span,
                    tags={'slot': slot})
            self._requests[slot] = req
            self._budgets[slot] = req.max_new_tokens
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            self._sample[slot] = req.do_sample
            # generate()'s stream: key = PRNGKey(seed), split once at
            # prefill end — created here, advanced by the final chunk
            req._key = _prng_key(req.seed)
            # no cache reset needed: the first prefill chunk writes from
            # the occupant's own offset and its length unreaches the
            # previous occupant's rows. A prefix hit means rows [0, hit)
            # are already valid shared pages: the row's length starts
            # there, not at zero
            self._lens[slot] = req._consumed
            if req._prefix_hit and req._span is not None:
                req._span.add_event('prefix_cache_hit',
                                    tokens=req._prefix_hit)
        if admitted:
            self._lanes_dirty = True
        return len(admitted)

    def _trace_prefill(self, req, start, valid, final):
        """Annotate the request's prefill phase span with one chunk; the
        final chunk closes it and opens the decode phase (lock held)."""
        if req._phase is None:
            return
        req._phase.add_event('prefill_chunk', start=start, valid=valid)
        if final:
            req._phase.finish()
            req._phase = self._tracer.start_span(
                'serving.decode', parent=req._span,
                tags={'slot': req.slot})

    def _prefill_step(self):
        """One chunk per prefilling resident, each its own jitted call
        (`_prefill_call`); returns (calls, prompt tokens forwarded). No
        call is read back here: a final chunk's pick and key go into the
        decode carry on the device and the pick onto `_picks`, for
        `_deliver_picks`; what the host needs of the lane it knows by
        count. A call's span is its dispatch."""
        tr = self._tracer
        calls = tokens = 0
        for req, start, ids, valid, final in self.scheduler.prefill_plan():
            slot = req.slot
            with tr.start_span('serving.prefill_call',
                               annotate=True) as sp:
                # mid chunks receive (and discard) the request key so
                # only the final chunk's split advances the sampling
                # stream
                tok, key2 = self._prefill_call(req, start, ids, valid)
                if final and not self.spec_k:
                    self._last, self._keys = self._carry_jit(
                        self._last, self._keys, np.int32(slot), tok, key2)
                if sp:
                    sp.tags.update(slot=slot, start=start, tokens=valid,
                                   final=final,
                                   kv_read=self.kv_read['prefill'],
                                   pick=('sample' if req.do_sample
                                         else 'argmax'),
                                   **self._expert_tag('prefill'))
            calls += 1
            tokens += valid
            self.metrics.on_prefill_tokens(valid)
            self.scheduler.mark_prefilled(req, start + valid)
            self._trace_prefill(req, start, valid, final)
            if not final:
                continue
            tok.copy_to_host_async()
            self._gen[slot] = 1
            self._active[slot] = True
            closed = req.max_new_tokens <= 1
            if closed:
                self._release(req)
            self._picks.append((slot, req, tok, closed))
        return calls, tokens

    def _deliver_picks(self):
        """The step's final chunks' picks, read when their calls end (a
        burst dispatched meanwhile runs behind them): first tokens."""
        picks, self._picks = self._picks, []
        if not picks:
            return
        toks = jax.device_get([p[2] for p in picks])
        for (slot, req, _, closed), tok in zip(picks, toks):
            self._spec_last[slot] = tok
            self._emit(req, [int(tok)])
            if closed:
                self._finish(req)

    def _land(self):
        """Wait for the burst in flight, if there is one, to END: the
        step's one wait for the device that nothing hides.
        Its token values, on their way to the host since its dispatch,
        are not needed before the next dispatch: `_deliver` reads them.
        Its (dispatch, dispatch-to-end, waited) seconds go onto `_ended`,
        for the timeline."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        tw, t2 = self._wait(flight.index, flight.toks)
        dispatch, block = flight.t1 - flight.t0, t2 - flight.t1
        if flight.span:
            flight.span.tags.update(dispatch_s=dispatch, block_s=t2 - tw)
            flight.span.finish(mono=t2)
        self._ended.append((dispatch, block, t2 - tw))
        self._landed = flight

    def _wait(self, burst, result):
        """Block until the device has `result`, under the step's child
        span `serving.step.wait`; returns the clock before and after."""
        clock = self.metrics.now
        tw = clock()
        with self._tracer.start_span('serving.step.wait', annotate=True,
                                     mono=tw, tags={'burst': burst}) as sp:
            result.block_until_ready()
            t2 = clock()
            if sp:
                sp.set_tag('waited_s', t2 - tw)
                sp.finish(mono=t2)
        return tw, t2

    def _deliver(self):
        """The ended burst's tokens and layer counters to the host
        (their copy began with the dispatch) and on to their requests,
        in lane order, with the second half of retirement for the lanes
        the burst finished."""
        flight, self._landed = self._landed, None
        if flight is None:
            return
        toks, counted = jax.device_get((flight.toks, flight.counted))
        self._burst_counters = {k: int(v) for k, v in counted.items()}
        for slot, req, n, closed in flight.lanes:
            self._emit(req, toks[:n, slot].tolist())
            if closed:
                self._finish(req)

    def _emit(self, req, tokens):
        if req._replay:
            # post-preemption regeneration: the first _replay tokens
            # were already delivered before the eviction; determinism
            # (same prompt, sampling, seed) makes the regenerated ones
            # identical, so swallow them — no duplicates, no double
            # counting in the token metrics
            drop = min(req._replay, len(tokens))
            req._replay -= drop
            tokens = tokens[drop:]
        if not tokens:
            return
        req.tokens.extend(tokens)
        if req._stream_q is not None:
            for t in tokens:
                req._stream_q.put(t)
        if req.on_token is not None:
            for t in tokens:
                req.on_token(t)
        now = self.metrics.now()     # one read stamps every sink below
        if req._first_token_t is None:
            req._first_token_t = now
            if req._arrival_t is not None:
                self.metrics.on_tenant_ttft(
                    req._tenant_label, now - req._arrival_t)
            if req._span is not None:
                req._span.add_event('first_token', mono=now)
        self.metrics.on_tenant_tokens(req._tenant_label, len(tokens))
        self.metrics.on_tokens(
            req.id, len(tokens), t=now,
            trace_id=None if req._span is None else req._span.trace_id)

    def _release(self, req):
        """Retirement's first half, by count, once the last program that
        touches the lane is dispatched: what admission depends on — the
        lane, the slot, the pages (the billing window closes with them)
        — goes back before the next admit pass, which cannot come before
        that program has ended."""
        slot = req.slot
        self._active[slot] = False
        self._lens[slot] = 0
        self._lanes_dirty = True
        del self._requests[slot]
        self.scheduler.release(req)    # sets req.kv_page_seconds

    def _finish(self, req, outcome='ok'):
        """Retirement's second half, once the request's last tokens are
        delivered: outcome, metrics, spans, the wide event, and the
        request's waiters."""
        req.outcome = outcome
        req._finish_t = self.metrics.now()
        self.metrics.on_retired(req.id)
        self.metrics.on_tenant_retired(
            req._tenant_label, req.kv_page_seconds * self._kv_page_bytes)
        if req._phase is not None:
            req._phase.finish()
            req._phase = None
        if req._span is not None:
            req._span.set_tag('tokens', len(req.tokens))
            req._span.add_event('retired')
            req._span.finish()
        self._emit_wide_event(req, outcome)
        self.scheduler.finish(req)

    def _emit_wide_event(self, req, outcome):
        """THE canonical per-request record (monitor/events.py). One
        load + branch when the log is disabled; skipped entirely for
        gateway-managed requests (the gateway emits the canonical one,
        with the failover history only it knows)."""
        log = self.events
        if not log.enabled or not req._emit_event:
            return
        wait = (req._admit_t - req._arrival_t) \
            if req._admit_t is not None and req._arrival_t is not None \
            else None
        log.emit(
            request_id=req.id,
            tenant=req._tenant_label,
            model=getattr(req, '_model_label', None),
            priority=req.priority,
            trace_id=None if req._span is None else req._span.trace_id,
            arrival_t=req._arrival_t,
            admit_t=req._admit_t,
            first_token_t=req._first_token_t,
            finish_t=req._finish_t,
            queue_wait_s=wait,
            prefill_chunks=req._prefill_chunks,
            prompt_tokens=len(req.prompt),
            output_tokens=len(req.tokens),
            prefix_hit_tokens=req._prefix_hit,
            spec_proposed=req._spec_proposed,
            spec_accepted=req._spec_accepted,
            kv_page_seconds=req.kv_page_seconds,
            failovers=0,
            replicas=[],
            outcome=outcome)

    def _on_preempt(self, slot, req, dropped):
        """PagedScheduler eviction hook (lock held): the victim's pages
        and slot are already released — freeze the lane so the next
        decode burst cannot advance it (the freed pages may belong to
        someone else by then) and close the victim's phase span. A
        `dropped` victim burned its preemption budget: retire it here
        with outcome='preempted' (the scheduler already closed its
        billing window and sets the finished flag after this returns)."""
        self._active[slot] = False
        self._lens[slot] = 0
        # (the scheduler's hook: admit() runs under step()'s lock)
        self._lanes_dirty = True  # graftlint: disable=lock-guard-write
        self._requests.pop(slot, None)
        # the ended burst's tokens, not yet delivered: a victim that
        # comes back regenerates them (`_replay` counts what it was
        # given), one that is dropped gets them now
        landed = self._landed
        for lane in landed.lanes if landed else ():
            if lane[1] is req:
                landed.lanes.remove(lane)
                if dropped:
                    self._emit(req, np.asarray(landed.toks)[
                        :lane[2], slot].tolist())
                break
        self.metrics.on_preempted(req._tenant_label)
        if req._phase is not None:
            req._phase.finish()
            req._phase = None
        if req._span is not None:
            req._span.add_event('preempted', count=req._preempts,
                                dropped=dropped)
        if not dropped:
            return
        req.outcome = 'preempted'
        req._finish_t = self.metrics.now()
        self.metrics.on_retired(req.id)
        self.metrics.on_tenant_retired(
            req._tenant_label, req.kv_page_seconds * self._kv_page_bytes)
        if req._span is not None:
            req._span.set_tag('tokens', len(req.tokens))
            req._span.add_event('retired')
            req._span.finish()
        self._emit_wide_event(req, 'preempted')

    # ---- the three compiled programs ----------------------------------

    def _unpack(self, program, pools, caches, slot=None):
        self.kv_read[program] = next(
            (c.kv_read for c in caches if hasattr(c, 'block_tables')), None)
        self.experts[program] = next(
            (c.experts for c in caches if getattr(c, 'experts', None)), None)
        return layer_state(pools, caches, slot)

    def _expert_tag(self, program):
        return ({'experts': self.experts[program]}
                if self.experts.get(program) else {})

    def _prefill_fn(self, params, bufs, pools, bt1, len1, ids, valid,
                    key, temp, topk, sample, slot=None):
        """One [1, C] prompt chunk through block-table row `bt1` at
        offset len1. For K/V only `valid` tokens are real, padded-tail
        writes are garbage the next pass overwrites, and the returned
        pick matters on the final chunk; the PRNG key advances by one
        split. A recurrent layer has no dead rows: it works on row
        `slot` of its state (passed when the model has such a layer),
        starts from zeros when len1 is 0 and takes `valid` tokens."""
        self.trace_counts['prefill'] += 1
        caches = layer_caches(self._specs, pools, bt1, len1,
                              jnp.reshape(valid, (1,)), self.page_size,
                              slot)
        (lg, new_cs), _ = _fm.functional_call(
            self._model, params, bufs, args=(Tensor(ids),),
            kwargs={'caches': caches}, training=False)
        last = jax.lax.dynamic_index_in_dim(lg[0], valid - 1, axis=0,
                                            keepdims=False)
        key2, sub = jax.random.split(key)
        tok = _pick_tokens(last[None], sub[None], temp[None], topk[None],
                           sample[None])[0]
        return self._unpack('prefill', pools, new_cs, slot), tok, key2

    def _carry_fn(self, last, keys, slot, tok, key):
        """A final chunk's pick and advanced key into lane `slot` of the
        decode carry, on the device: two words and one."""
        return last.at[slot, 0].set(tok), keys.at[slot].set(key)

    def _decode_fn(self, params, bufs, pools, bt, lens, tok, gen,
                   budgets, active, keys, temps, topks, sample):
        """K cached decode steps for all rows in one dispatch, lengths
        carried through the scan (block tables are per-dispatch
        constants). `step_active` freezes rows that are unoccupied,
        mid-prefill or out of budget: their lengths / gen counts / keys
        do not advance and their fed token repeats, so a frozen lane's
        garbage logits never leak into state; it writes K/V garbage
        where nobody reads and keeps its recurrent state bit for bit.
        The scan length is the FIXED decode_block — a finishing row
        idles for the burst's remainder rather than shortening it (a
        variable length would recompile)."""
        self.trace_counts['decode'] += 1

        def body(carry, _):
            pools, lens, tok, gen, keys = carry
            step_active = active & (gen < budgets)
            inc = step_active.astype(jnp.int32)
            caches = layer_caches(self._specs, pools, bt, lens, inc,
                                  self.page_size)
            (lg, new_cs), _ = _fm.functional_call(
                self._model, params, bufs, args=(Tensor(tok),),
                kwargs={'caches': caches}, training=False)
            ks = jax.vmap(jax.random.split)(keys)
            subs = ks[:, 1]
            keys2 = jnp.where(step_active[:, None], ks[:, 0], keys)
            # a frozen lane's stale flag must not choose the branch
            nxt = _pick_tokens(lg[:, -1], subs, temps, topks,
                               sample & step_active)
            tok2 = jnp.where(step_active, nxt, tok[:, 0])[:, None]
            return ((self._unpack('decode', pools, new_cs), lens + inc,
                     tok2, gen + inc, keys2),
                    (tok2[:, 0], step_active, layer_counters(new_cs)))

        carry, (toks, actives, counted) = jax.lax.scan(
            body, (pools, lens, tok, gen, keys), None,
            length=self.decode_block)
        pools2, lens2, tok2, gen2, keys2 = carry
        # over the burst's steps as over the layers: sums, or the largest
        counted = {name: (jnp.max if name.endswith('_max') else jnp.sum)(v)
                   for name, v in counted.items()}
        return pools2, lens2, tok2, gen2, keys2, toks, actives, counted

    def _verify_fn(self, params, bufs, pools, bt, lens, toks):
        """ONE forward over [S, K+1] rows: position 0 feeds each row's
        last emitted token, positions 1..K feed its drafts. Returns the
        greedy pick after every position — pick i is the model's true
        next token given [..., tok_0..tok_i], which is what the host
        accept rule compares drafts against. Writes land at lens..
        lens+K; rows past what acceptance advances are garbage the next
        pass overwrites (or scratch-mapped, past the reservation)."""
        self.trace_counts['verify'] += 1
        # (no `valid`: a model with a recurrent layer never gets here)
        caches = layer_caches(self._specs, pools, bt, lens, None,
                              self.page_size)
        (lg, new_cs), _ = _fm.functional_call(
            self._model, params, bufs, args=(Tensor(toks),),
            kwargs={'caches': caches}, training=False)
        picks = jnp.argmax(lg.astype(jnp.float32), axis=-1).astype(
            jnp.int32)
        return self._unpack('verify', pools, new_cs), picks

    # ---- per-step dispatches (lock held) ------------------------------

    def _prefill_call(self, req, start, ids, valid):
        slot = req.slot
        # the program learns its slot only where a layer's state lives
        # per slot; a model of K/V rows alone is addressed by `bt1`
        where = (np.int32(slot),) if self._state_seq_bytes else ()
        # (a copy of the row: the call is not waited for, and the row
        # may change under it — a one-token request is released at once)
        self._pools, tok, key2 = self._prefill_jit(
            self._params, self._bufs, self._pools,
            self.scheduler.block_tables[slot:slot + 1].copy(),
            np.asarray([start], np.int32),
            np.asarray(ids, np.int32)[None, :],
            np.int32(valid), req._key,
            np.float32(req.temperature), np.int32(req.top_k),
            np.asarray(req.do_sample), *where)
        self._lens[slot] = start + valid
        self._lanes_dirty = True
        return tok, key2

    def _decode_step(self):
        """Dispatch the next burst and leave it in flight (`_flight`);
        nothing of it is read here. Under `spec_k` the draft-and-verify
        step instead, which reads its own results."""
        slots = self.scheduler.decode_slots()
        if not slots:
            return
        if self.spec_k:
            return self._spec_step(slots)
        # the lane arrays go to the device when the host changed one
        # (admission, a prefill chunk, retirement, preemption); a burst
        # that follows a burst takes them as they lie there, lengths and
        # counts as the last burst returned them, which is what the host
        # counted. The dispatch args are stashed for perf_estimate's
        # cost-model lowering (identical avals, so no retrace).
        if self._lanes_dirty:
            # (copies: the host writes its arrays again under the burst)
            self._lane_args = jax.device_put([a.copy() for a in (
                self.scheduler.block_tables, self._lens, self._gen,
                self._budgets, self._active, self._temps, self._topks,
                self._sample)])
            self._lanes_dirty = False
        bt, lens, gen, budgets, active, temps, topks, sample = \
            self._lane_args
        args = (self._params, self._bufs, self._pools, bt, lens,
                self._last, gen, budgets, active, self._keys, temps,
                topks, sample)
        self._decode_args = args
        # the program's own predicate (lanes only leave `step_active`
        # inside a burst): how far each lane advances, which pick it takes
        left = self._budgets - self._gen
        lanes = [(slot, self._requests[slot],
                  min(self.decode_block, int(left[slot])),
                  left[slot] <= self.decode_block) for slot in slots]
        sampling = any(self._sample[slot] for slot in slots)
        self._bursts += 1
        clock = self.metrics.now
        t0 = clock()
        # dispatch to results on the host: the span outlives this step
        # (`_land` closes it), so it is a root and no step's child
        span = self._tracer.start_span(
            'serving.decode_burst', root=True, annotate=True, mono=t0,
            tags={'burst': self._bursts, 'rows': len(slots),
                  'block': self.decode_block,
                  'pick': 'sample' if sampling else 'argmax'})
        (self._pools, lens, self._last, gen, self._keys, toks, _,
         counted) = self._decode_jit(*args)
        span.set_tag('kv_read', self.kv_read['decode'])
        for name, value in self._expert_tag('decode').items():
            span.set_tag(name, value)
        self._lane_args = [bt, lens, gen] + self._lane_args[3:]
        for out in (toks, *counted.values()):
            out.copy_to_host_async()
        self._flight = _Flight(self._bursts, lanes, toks, counted, span,
                               t0, clock())
        # the burst's effect on the lanes, by count: what it will have
        # done when it has ended, and what admission may have then
        for slot, req, n, closed in lanes:
            self._gen[slot] += n
            self._lens[slot] += n
            if closed:
                self._release(req)

    def _spec_step(self, slots):
        """Draft K tokens per decoding row, verify all rows in ONE
        [S, K+1] forward, accept each row's longest draft prefix that
        matches the model's own greedy picks, plus the pick after it
        (the 'bonus' token — free, since the verify forward already
        computed it). Worst case (0 accepted) this emits 1 token per
        row, exactly a decode step; best case K+1. It drafts from the
        tokens delivered so far, so dispatch, read and delivery are one
        step's."""
        K = self.spec_k
        toks = np.zeros((self.num_slots, K + 1), np.int32)
        drafts = {}
        for slot in slots:
            req = self._requests[slot]
            d = self._proposer.propose(req.prompt + req.tokens, K)
            drafts[slot] = d
            toks[slot, 0] = self._spec_last[slot]
            toks[slot, 1:] = d
        args = (self._params, self._bufs, self._pools,
                self.scheduler.block_tables, self._lens, toks)
        self._verify_args = args
        self._bursts += 1
        clock = self.metrics.now
        t0 = clock()
        span = self._tracer.start_span(
            'serving.decode_burst', root=True, annotate=True, mono=t0,
            tags={'burst': self._bursts, 'rows': len(slots), 'spec_k': K,
                  'pick': 'argmax'})
        self._pools, picks = self._verify_jit(*args)
        t1 = clock()
        tw, t2 = self._wait(self._bursts, picks)
        picks = jax.device_get(picks)
        self._ended.append((t1 - t0, t2 - t1, t2 - tw))
        if span:
            span.tags.update(dispatch_s=t1 - t0, block_s=t2 - tw,
                             kv_read=self.kv_read['verify'])
            span.finish(mono=t2)
        for slot in slots:
            req = self._requests[slot]
            d, g = drafts[slot], picks[slot]
            a = 0
            while a < K and d[a] == int(g[a]):
                a += 1
            # accepted drafts + the bonus pick, clipped to budget; a
            # decoding row always has budget left (it would have retired
            # otherwise), so at least one token emits and lens advances
            left = int(self._budgets[slot]) - int(self._gen[slot])
            emit = [int(x) for x in g[:min(a + 1, left)]]
            self.metrics.on_spec(K, max(len(emit) - 1, 0))
            req._spec_proposed += K
            req._spec_accepted += max(len(emit) - 1, 0)
            if req._span is not None:
                req._span.add_event('spec_accept', proposed=K,
                                    accepted=max(len(emit) - 1, 0))
            self._lens[slot] += len(emit)
            self._gen[slot] += len(emit)
            self._spec_last[slot] = emit[-1]
            self._emit(req, emit)
            if self._gen[slot] >= self._budgets[slot]:
                self._release(req)
                self._finish(req)
