"""Paged continuous batching: block-granular KV + prefix reuse + spec
decode, in exactly THREE compiled programs.

The slot engine (engine.py) reserves `max_len` KV rows per slot, so
memory density scales with the WORST-CASE sequence and identical system
prompts re-prefill on every request. This engine keeps one physical pool
of fixed-size pages per layer and maps sequences onto it through host
numpy block tables (vLLM's PagedAttention layout):

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

Program set (the PR-3 two-program invariant, generalized but still
bounded — trace-count gauges assert it):

  prefill chunk  — [1, C] prompt tokens through one sequence's block-
                   table row;
  decode burst   — K cached steps for ALL sequences (spec off);
  verify pass    — [S, K+1] draft tokens for ALL sequences (spec on).

Only the layers' state lives on device — per layer, as the model names
it (`cache_specs()`, serving/kv_cache.py): the page pools of a layer that
keeps K/V rows, or `[num_seqs, ...]` arrays that belong to a slot for a
recurrent layer (a linear-attention state). Block tables and lengths are
host numpy handed to jit per dispatch (values change freely, shapes
never). A recurrent layer's state cannot be shared by prefix nor taken
back after a rejected draft, so a model with one runs with
`prefix_cache=False` and `spec_k=0` (the constructor says so); a
preempted request recomputes from position 0, state and all.
"""
import numpy as np

import jax
import jax.numpy as jnp

from ..framework import functional as _fm
from ..framework.core import Tensor
from .engine import _EngineBase, _pick_token
from .kv_cache import (PageAllocator, PrefixCache, SlotAllocator,
                       build_paged_pools, cache_specs, kv_row_bytes,
                       layer_caches, layer_state, state_bytes_per_seq)
from .scheduler import PagedScheduler

__all__ = ['PagedContinuousBatchingEngine', 'NGramProposer']


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


class PagedContinuousBatchingEngine(_EngineBase):
    """Page-granular continuous batching over a decoder that names its
    per-layer caches (`cache_specs()`): GPTForCausalLM,
    OlmoHybridForCausalLM.

    Same front door and scheduling policy as ContinuousBatchingEngine;
    differs in the KV layout (page pool + block tables), prefix-cache
    admission, and the optional speculative decode path. `spec_k > 0`
    replaces the decode burst with draft-and-verify and is greedy-only:
    sampled requests are rejected at add_request, because the accept
    rule compares drafts against argmax picks.
    """

    _programs = ('prefill', 'decode', 'verify')

    def __init__(self, model, num_seqs=8, max_len=None, page_size=16,
                 num_pages=None, prefill_chunk=16, decode_block=4,
                 spec_k=0, ngram=2, prefix_cache=True, preempt=False,
                 max_preempts=None, donate=None):
        super().__init__(model, num_seqs, max_len)
        if self.max_len > model.config.max_position_embeddings:
            raise ValueError(
                'max_len %d exceeds max_position_embeddings %d'
                % (self.max_len, model.config.max_position_embeddings))
        self.page_size = int(page_size)
        self.num_blocks = -(-self.max_len // self.page_size)
        if num_pages is None:
            # parity default: enough for every sequence at max_len plus
            # scratch — same footprint as the slot engine. Real
            # deployments size the pool to ACTUAL length distributions
            # (the density win); the scheduler's up-front reservation
            # keeps a small pool safe, just slower to admit.
            num_pages = self.num_slots * self.num_blocks + 1
        self.num_pages = int(num_pages)
        self.decode_block = int(decode_block)
        if self.decode_block < 1:
            raise ValueError('decode_block must be >= 1')
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError('spec_k must be >= 0')
        self._proposer = NGramProposer(ngram) if self.spec_k else None
        # what each layer keeps (the model names it): K/V rows in the
        # page pool, or per-SLOT arrays that every token rewrites
        self._specs = cache_specs(model)
        self._state_seq_bytes = state_bytes_per_seq(self._specs)
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
        # the base class's host control arrays. Mid-prefill rows track
        # consumed so in-program garbage writes from frozen lanes land
        # on rows the next real pass overwrites anyway.
        self._lens = np.zeros((self.num_slots,), np.int32)
        self._prefix_seen = [0, 0]    # hit/miss totals already reported
        # which K/V read each program took ('pool' | 'gather'), written
        # where the program is traced (`_unpack`) from what attention
        # recorded on the caches it returned; the spans' `kv_read` tag
        self.kv_read = {}
        if donate is None:
            donate = jax.default_backend() in ('tpu', 'gpu')
        dn = (2,) if donate else ()
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=dn)
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=dn)
        self._verify_jit = jax.jit(self._verify_fn, donate_argnums=dn)
        self._verify_args = None

    @property
    def num_seqs(self):
        return self.num_slots

    def _warm_programs(self):
        # the verify program only ever traces when speculation is on;
        # without spec_k the watchdog must not wait for it forever
        if self.spec_k:
            return self._programs
        return ('prefill', 'decode')

    def _perf_target(self):
        # under speculation the verify forward is the steady-state
        # spender (the plain decode program never dispatches)
        if self.spec_k and self._verify_args is not None:
            return self._verify_jit, self._verify_args
        return self._decode_jit, self._decode_args

    def _validate(self, req):
        if self.spec_k and req.do_sample:
            raise ValueError(
                'speculative decoding (spec_k=%d) is greedy-only: the '
                'accept rule compares drafts against argmax picks. '
                'Submit with do_sample=False or run spec_k=0.'
                % self.spec_k)

    def _bind(self, slot, req):
        # a prefix hit means rows [0, hit) are already valid shared
        # pages: the row's length starts there, not at zero
        self._lens[slot] = req._consumed
        if req._prefix_hit and req._span is not None:
            req._span.add_event('prefix_cache_hit',
                                tokens=req._prefix_hit)

    def _state_in_use(self):
        """(slots whose recurrent state belongs to a resident, its
        bytes): zeros for a model that keeps K/V rows only."""
        slots = self.allocator.in_use if self._state_seq_bytes else 0
        return slots, slots * self._state_seq_bytes

    def _tag_step(self, span):
        slots, nbytes = self._state_in_use()
        span.tags.update(pages_in_use=self.pages.in_use,
                         state_slots_in_use=slots, state_bytes=nbytes)

    def _tag_prefill_call(self, span):
        span.set_tag('kv_read', self.kv_read['prefill'])

    def _on_step_metrics(self):
        self.metrics.on_pages_in_use(self.pages.in_use)
        self.metrics.on_state_bytes(self._state_in_use()[1])
        if self.prefix is not None:
            h, m = self.prefix.hits, self.prefix.misses
            self.metrics.on_prefix_lookup(h - self._prefix_seen[0],
                                          m - self._prefix_seen[1])
            self._prefix_seen = [h, m]

    def _retire(self, req, outcome='ok'):
        slot = req.slot
        super()._retire(req, outcome)
        self._lens[slot] = 0

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
        self._requests.pop(slot, None)
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
        return layer_state(pools, caches, slot)

    def _prefill_fn(self, params, bufs, pools, bt1, len1, ids, valid,
                    key, temp, topk, sample, slot=None):
        """One [1, C] prompt chunk through block-table row `bt1` at
        offset len1. Same contract as the slot prefill for K/V: only
        `valid` tokens are real, padded-tail writes are garbage the next
        pass overwrites, and the returned pick matters on the final
        chunk. A recurrent layer has no dead rows: it works on row
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
        tok = _pick_token(last, sub, temp, topk, sample)
        return self._unpack('prefill', pools, new_cs, slot), tok, key2

    def _decode_fn(self, params, bufs, pools, bt, lens, tok, gen,
                   budgets, active, keys, temps, topks, sample):
        """K cached decode steps for all rows — the slot engine's burst
        with lengths carried through the scan instead of living inside
        the cache pytree (block tables are per-dispatch constants). A
        lane that is frozen or past its budget writes K/V garbage where
        nobody reads and keeps its recurrent state bit for bit."""
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
            nxt = jax.vmap(_pick_token)(lg[:, -1], subs, temps, topks,
                                        sample)
            tok2 = jnp.where(step_active, nxt, tok[:, 0])[:, None]
            return ((self._unpack('decode', pools, new_cs), lens + inc,
                     tok2, gen + inc, keys2), (tok2[:, 0], step_active))

        carry, (toks, actives) = jax.lax.scan(
            body, (pools, lens, tok, gen, keys), None,
            length=self.decode_block)
        pools2, lens2, tok2, gen2, keys2 = carry
        return pools2, lens2, tok2, gen2, keys2, toks, actives

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
        self._pools, tok, key2 = self._prefill_jit(
            self._params, self._bufs, self._pools,
            self.scheduler.block_tables[slot:slot + 1],
            np.asarray([start], np.int32),
            np.asarray(ids, np.int32)[None, :],
            np.int32(valid), req._key,
            np.float32(req.temperature), np.int32(req.top_k),
            np.asarray(req.do_sample), *where)
        self._lens[slot] = start + valid
        return tok, key2

    def _decode_step(self):
        slots = self.scheduler.decode_slots()
        if not slots:
            return
        if self.spec_k:
            return self._spec_step(slots)
        # the span covers dispatch AND the device_get sync — the burst's
        # actual wall time, not just the async enqueue; `_burst_done`
        # splits the same window (host_dispatch vs device_block) and the
        # dispatch args are stashed for perf_estimate's cost-model
        # lowering (identical avals, so no retrace).
        args = (self._params, self._bufs, self._pools,
                self.scheduler.block_tables, self._lens, self._last,
                self._gen, self._budgets, self._active, self._keys,
                self._temps, self._topks, self._sample)
        self._decode_args = args
        clock = self.metrics.now
        t0 = clock()
        with self._tracer.start_span(
                'serving.decode_burst', annotate=True, mono=t0,
                tags={'rows': len(slots),
                      'block': self.decode_block}) as sp:
            (self._pools, lens, last, gen, keys, toks,
             actives) = self._decode_jit(*args)
            t1 = clock()
            lens, last, gen, keys, toks, actives = jax.device_get(
                (lens, last, gen, keys, toks, actives))
            burst = self._burst_done(sp, t0, t1, clock(),
                                     kv_read=self.kv_read['decode'])
        self._lens = np.array(lens)
        self._last = np.array(last)
        self._gen = np.array(gen)
        self._keys = np.array(keys)
        for slot in slots:
            req = self._requests[slot]
            new = [int(toks[k, slot]) for k in range(toks.shape[0])
                   if actives[k, slot]]
            self._emit(req, new)
            if len(req.tokens) >= req.max_new_tokens:
                self._retire(req)
        return burst

    def _spec_step(self, slots):
        """Draft K tokens per decoding row, verify all rows in ONE
        [S, K+1] forward, accept each row's longest draft prefix that
        matches the model's own greedy picks, plus the pick after it
        (the 'bonus' token — free, since the verify forward already
        computed it). Worst case (0 accepted) this emits 1 token per
        row, exactly a decode step; best case K+1."""
        K = self.spec_k
        toks = np.zeros((self.num_slots, K + 1), np.int32)
        drafts = {}
        for slot in slots:
            req = self._requests[slot]
            d = self._proposer.propose(req.prompt + req.tokens, K)
            drafts[slot] = d
            toks[slot, 0] = self._last[slot, 0]
            toks[slot, 1:] = d
        args = (self._params, self._bufs, self._pools,
                self.scheduler.block_tables, self._lens, toks)
        self._verify_args = args
        clock = self.metrics.now
        t0 = clock()
        with self._tracer.start_span(
                'serving.decode_burst', annotate=True, mono=t0,
                tags={'rows': len(slots), 'spec_k': K}) as sp:
            self._pools, picks = self._verify_jit(*args)
            t1 = clock()
            picks = np.asarray(jax.device_get(picks))
            burst = self._burst_done(sp, t0, t1, clock(),
                                     kv_read=self.kv_read['verify'])
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
            self._last[slot, 0] = emit[-1]
            self._emit(req, emit)
            if len(req.tokens) >= req.max_new_tokens:
                self._retire(req)
        return burst
