"""Continuous-batching engine: two jitted programs + a thread-safe door.

The whole engine compiles exactly TWO programs, each with one static
shape, so request admit/retire churn can never retrace:

  prefill chunk  — [1, C] prompt tokens into ONE slot's cache rows
                   (slot sliced out, forwarded, written back; the slot
                   index / row offset / valid count are traced scalars);
  decode burst   — K cached decode steps for ALL slots in one dispatch
                   (lax.scan; per-step `step_active` masking freezes
                   finished or still-prefilling slots in-program, so the
                   burst length never depends on occupancy).

Correctness relies on the GPTSlotCache invariants (text/models/gpt.py):
rows at/beyond a slot's length are unreachable garbage, attention writes
at the pre-step offsets and the ENGINE advances lengths — prefill
write-back sets `start + valid` (padding rows stay invalid), the decode
burst adds `step_active` per step.

Greedy output is token-identical to sequential generate(): the masked
slot attention contributes exact zeros for invalid rows (scores hit
-1e9 and underflow to 0.0 after the f32 softmax), and sampling mirrors
generate()'s per-request PRNG stream (one split at prefill, one per
decode step, advanced only on active steps).

`_EngineBase` holds everything that is NOT about the cache layout — the
thread-safe front door, the scheduler glue, metrics, shutdown — so the
paged engine (serving/paged_engine.py) shares it verbatim and differs
only in its compiled programs and page bookkeeping.
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
from ..monitor.perf import CompileWatchdog, StepTimeline
from ..monitor.perf import costmodel as _costmodel
from ..text.models.gpt import GPTSlotCache
from .kv_cache import (SlotAllocator, build_slot_caches, cache_specs,
                       kv_row_bytes)
from .metrics import ServingMetrics
from .scheduler import Request, Scheduler

__all__ = ['ContinuousBatchingEngine']


@jax.named_scope('serving.pick_token')    # names the device ops, no more
def _pick_token(lg, key, temp, topk, sample):
    """Next token for ONE row of logits — generate()'s pick, per slot.

    All branches execute and select (jit-safe): greedy argmax vs
    temperature/top-k categorical, chosen by the `sample` flag. topk==0
    means full vocab (threshold -inf), same as generate().
    """
    lg = lg.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lt = lg / jnp.maximum(temp, 1e-6)
    v = lt.shape[-1]
    srt = jnp.sort(lt, axis=-1)                    # ascending
    kth = srt[jnp.clip(v - topk, 0, v - 1)]        # the top-k'th value
    thr = jnp.where(topk > 0, kth, -jnp.inf)
    lt = jnp.where(lt >= thr, lt, -1e30)
    sampled = jax.random.categorical(key, lt).astype(jnp.int32)
    return jnp.where(sample, sampled, greedy)


class _EngineBase:
    """Cache-layout-agnostic half of a continuous-batching engine.

    Front door (`add_request` / `step` / `run` / `stream` / `generate`)
    is thread-safe: any number of threads may submit and drive; an RLock
    serializes scheduler state and device dispatches while `Request.wait`
    and stream consumption stay lock-free. Subclasses own the compiled
    programs: they set `self.allocator` / `self.scheduler` and implement
    `_prefill_call` / `_decode_step` (and may hook `_bind` /
    `_on_step_metrics`).

    With the tracer on, a step explains itself: `serving.step` with
    `serving.step.admit`, `serving.step.prefill` (one
    `serving.prefill_call` per jitted call) and `serving.decode_burst`
    as children, each also a TraceAnnotation, so the same names sit in
    the flight ring on the engine's clock and in a device trace's host
    plane. With it off, step() opens no span and reads no extra clock.
    """

    # traced-body counter keys, one per compiled program; the zero-
    # retrace assertion is `trace_counts` staying all-ones across an
    # arbitrary admit/retire workload
    _programs = ('prefill', 'decode')

    def __init__(self, model, num_slots, max_len):
        model.eval()
        self._model = model
        self.num_slots = int(num_slots)
        self.max_len = int(max_len or model.config.max_position_embeddings)
        self.metrics = ServingMetrics()
        self._params = _fm.extract_params(model)
        self._bufs = _fm.extract_buffers(model)
        # per-slot control state lives HOST-side as numpy: admission and
        # retirement mutate it in place for free instead of dispatching
        # an eager .at[].set() per field (the jitted calls accept numpy
        # operands directly). Only the KV caches stay device-resident.
        s = self.num_slots
        self._last = np.zeros((s, 1), np.int32)       # token fed next step
        self._gen = np.zeros((s,), np.int32)          # tokens generated
        self._budgets = np.zeros((s,), np.int32)      # max_new_tokens
        self._active = np.zeros((s,), bool)           # slot decodes?
        self._keys = np.zeros((s, 2), np.uint32)      # per-slot PRNG
        self._temps = np.ones((s,), np.float32)
        self._topks = np.zeros((s,), np.int32)
        self._sample = np.zeros((s,), bool)
        self._requests = {}                           # slot -> Request
        self._lock = threading.RLock()
        self._closed = False
        # cached at construction (like the registry): swap the default
        # tracer BEFORE building the engine under test
        self._tracer = _tracing.default_tracer()
        # wide-event request log, same caching rule; subclasses set the
        # page->bytes factor once their cache layout is known
        self.events = _events.default_request_log()
        self._kv_page_bytes = 0
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
        higher wins) orders admission and — on the paged engine with
        preempt=True — marks lower-priority residents evictable.
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
        # front-door guard, shared by BOTH engines (the paged subclass
        # overrides _validate without chaining): a request whose worst
        # case — prompt plus every generated token but the last — cannot
        # fit the cache would sit at the queue head forever, wedging
        # admission for everyone behind it. Fail loud at submission.
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
            self._validate(req)
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

    def _validate(self, req):
        """Subclass hook: extra front-door checks (lock held)."""

    def shutdown(self):
        """Refuse all future add_request calls. In-flight requests may
        still be driven to completion with step()/run(); shutdown only
        closes the front door."""
        with self._lock:
            self._closed = True
            self.perf.close()

    def step(self):
        """One scheduler iteration: admit → prefill chunks → decode
        burst → retire. Returns the number of requests still pending."""
        with self._lock, no_grad_guard():
            self._step_index += 1
            tr, sched = self._tracer, self.scheduler
            with tr.start_span('serving.step', annotate=True) as sp:
                if sp:
                    # wall >> CPU reads "waiting on the device", wall ==
                    # CPU reads "the host was busy"
                    cpu0 = time.process_time()
                    compiles0 = self.perf.counts['compile']
                    sp.tags.update(step=self._step_index,
                                   residents=len(sched.resident),
                                   queue_depth=len(sched.queue),
                                   slots_in_use=self.allocator.in_use)
                    self._tag_step(sp)
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
                    if ph_prefill:
                        ph_prefill.tags.update(calls=calls, tokens=tokens)
                if sched.head_left != 'none':
                    self.metrics.on_admit_blocked(sched.head_left)
                self.metrics.on_prefill_calls(calls)
                burst = self._decode_step()
                self.metrics.on_step(self.allocator.in_use, self.num_slots)
                self.metrics.on_queue_depth(len(sched.queue))
                self._on_step_metrics()
                for prog, child in self._m_trace.items():
                    child.set(self.trace_counts[prog])
                if not self.perf.armed and all(
                        self.trace_counts[p] > 0
                        for p in self._warm_programs()):
                    self.perf.declare_warmup(
                        '%s steady state' % type(self).__name__)
                detail = None
                if sp:
                    sp.set_tag('cpu_s', time.process_time() - cpu0)
                    sp.finish()
                    detail = self._step_detail(
                        sp, ph_admit, ph_prefill, burst,
                        self.perf.counts['compile'] - compiles0)
                if burst is not None:
                    # the timeline's step is the burst (its totals feed
                    # the straggler rule and the burst percentiles); a
                    # flagged one carries the whole step's phases
                    self.timeline.end_step(detail=detail)
            return sched.pending

    def _tag_step(self, span):
        """Subclass hook: more of the state at a step's entry."""

    def _tag_prefill_call(self, span):
        """Subclass hook: what the call's program says of itself."""

    def _step_detail(self, sp, ph_admit, ph_prefill, burst, compiles):
        """Where a step went, for a `perf.straggler` record: phase
        seconds from the spans' own stamps (self = the step minus its
        phases), CPU seconds, compiles counted during it, its index."""
        admit = ph_admit.end_mono - ph_admit.start_mono
        prefill = ph_prefill.end_mono - ph_prefill.start_mono
        dispatch, block = burst or (0.0, 0.0)
        wall = sp.end_mono - sp.start_mono
        return {'engine_step': self._step_index, 'step_s': wall,
                'admit_s': admit, 'prefill_s': prefill,
                'burst_dispatch_s': dispatch, 'burst_block_s': block,
                'self_s': wall - admit - prefill - dispatch - block,
                'cpu_s': sp.tags['cpu_s'], 'compiles': compiles}

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

    def _warm_programs(self):
        """Programs that must trace before the watchdog's warmup
        barrier can be declared (subclasses drop conditional ones)."""
        return self._programs

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

    def _perf_target(self):
        """(jitted_fn, last-dispatch args) for the steady-state program
        the cost model should price — the decode program by default
        (the spec-decode engine overrides with its verify program)."""
        return self._decode_jit, self._decode_args

    def perf_estimate(self, bursts=None, wall_seconds=None):
        """Cost-model estimate of the steady-state program (the
        dollar spender): analytic flops/bytes, roofline bound, warm
        compile seconds — plus mfu_est when told how many decode bursts
        ran over a measured wall. None before the first burst dispatch.

        The deliberate lower+compile here is watchdog-suspended (it is
        a measurement, not a retrace) and reuses the exact arrays of
        the last dispatch, so the traced avals match and the program's
        trace count stays flat."""
        jit_fn, args = self._perf_target()
        if args is None:
            return None
        with self._lock, self.perf.suspended():
            import time as _time
            t0 = _time.monotonic()
            compiled = jit_fn.lower(*args).compile()
            warm_s = _time.monotonic() - t0
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
            req._key = np.asarray(jax.random.PRNGKey(req.seed))
            # no cache reset needed: the first prefill chunk writes from
            # the occupant's own offset and its write-back length
            # unreaches the previous occupant's rows
            self._bind(slot, req)
        return len(admitted)

    def _bind(self, slot, req):
        """Subclass hook: extra per-admission state (lock held)."""

    def _on_step_metrics(self):
        """Subclass hook: extra per-step gauges (lock held)."""

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
        (`_prefill_call`, the subclass's); returns (calls, prompt tokens
        forwarded). A call's span ends after its host sync, so the host
        time BETWEEN two calls is `serving.step.prefill`'s self time."""
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
                if final:
                    tok = int(tok)           # the call's host sync
                if sp:
                    sp.tags.update(slot=slot, start=start, tokens=valid,
                                   final=final)
                    self._tag_prefill_call(sp)
            calls += 1
            tokens += valid
            self.metrics.on_prefill_tokens(valid)
            self.scheduler.mark_prefilled(req, start + valid)
            self._trace_prefill(req, start, valid, final)
            if not final:
                continue
            self._last[slot, 0] = tok
            self._gen[slot] = 1
            self._keys[slot] = np.asarray(key2)
            self._active[slot] = True
            self._emit(req, [tok])
            if len(req.tokens) >= req.max_new_tokens:
                self._retire(req)
        return calls, tokens

    def _burst_done(self, span, t0, t1, t2, **tags):
        """A burst's one set of clock reads — dispatch returned at t1,
        results on the host at t2 — feeds the timeline's phases and the
        `serving.decode_burst` span alike (`tags`: what else the span
        says of the burst); returns (dispatch, block) seconds, what
        `_decode_step` hands back to step()."""
        dispatch, block = t1 - t0, t2 - t1
        self.timeline.record('host_dispatch', dispatch)
        self.timeline.record('device_block', block)
        if span:
            span.tags.update(dispatch_s=dispatch, block_s=block, **tags)
            span.finish(mono=t2)
        return dispatch, block

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

    def _retire(self, req, outcome='ok'):
        req.outcome = outcome
        slot = req.slot
        self._active[slot] = False
        del self._requests[slot]
        self.scheduler.retire(req)     # sets req.kv_page_seconds
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


class ContinuousBatchingEngine(_EngineBase):
    """Slot-based continuous batching over a GPTForCausalLM.

    Every slot reserves `max_len` KV rows (GPTSlotCache); see
    PagedContinuousBatchingEngine for the page-granular variant with
    prefix sharing and speculative decoding.
    """

    def __init__(self, model, num_slots=8, max_len=None, prefill_chunk=16,
                 decode_block=4, donate=None):
        super().__init__(model, num_slots, max_len)
        self.decode_block = int(decode_block)
        if self.decode_block < 1:
            raise ValueError('decode_block must be >= 1')
        self._caches = build_slot_caches(model, self.num_slots, self.max_len)
        self.allocator = SlotAllocator(self.num_slots)
        self.scheduler = Scheduler(self.allocator, self.max_len,
                                   prefill_chunk)
        # billing unit for kv_byte_seconds: a slot reserves max_len rows
        self._kv_page_bytes = kv_row_bytes(
            cache_specs(model)) * self.max_len
        if donate is None:
            # cache buffers dominate engine memory; donating them lets
            # XLA update in place. CPU donation is a no-op that warns.
            donate = jax.default_backend() in ('tpu', 'gpu')
        dn = (2,) if donate else ()
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=dn)
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=dn)

    # ---- the two compiled programs ------------------------------------

    def _prefill_fn(self, params, bufs, caches, slot, ids, start, valid,
                    key, temp, topk, sample):
        """One [1, C] prompt chunk into slot `slot` at row `start`.

        Only `valid` of the C tokens are real; padded rows write garbage
        K/V beyond the valid length, which the write-back length
        (`start + valid`) keeps unreachable (the next chunk or decode
        step overwrites row start+valid before it becomes visible).
        Returns the updated caches, the post-chunk logits' pick (only
        meaningful on the final chunk) and the advanced PRNG key.
        """
        self.trace_counts['prefill'] += 1
        small = []
        for c in caches:
            ks = jax.lax.dynamic_slice_in_dim(c.k._data, slot, 1, axis=0)
            vs = jax.lax.dynamic_slice_in_dim(c.v._data, slot, 1, axis=0)
            small.append(GPTSlotCache(Tensor(ks), Tensor(vs),
                                      jnp.full((1,), start, jnp.int32)))
        (lg, small2), _ = _fm.functional_call(
            self._model, params, bufs, args=(Tensor(ids),),
            kwargs={'caches': small}, training=False)
        new_caches = []
        for c, s2 in zip(caches, small2):
            kb = jax.lax.dynamic_update_slice(
                c.k._data, s2.k._data, (slot, 0, 0, 0))
            vb = jax.lax.dynamic_update_slice(
                c.v._data, s2.v._data, (slot, 0, 0, 0))
            new_caches.append(GPTSlotCache(
                Tensor(kb), Tensor(vb),
                c.lengths.at[slot].set(start + valid)))
        last = jax.lax.dynamic_index_in_dim(lg[0], valid - 1, axis=0,
                                            keepdims=False)
        key2, sub = jax.random.split(key)
        tok = _pick_token(last, sub, temp, topk, sample)
        return new_caches, tok, key2

    def _decode_fn(self, params, bufs, caches, tok, gen, budgets, active,
                   keys, temps, topks, sample):
        """K cached decode steps for all slots in one dispatch.

        `step_active` freezes slots that are unoccupied, mid-prefill, or
        out of budget: their lengths / gen counts / keys do not advance
        and their fed token repeats, so a frozen slot's garbage logits
        never leak into state. The scan length is the FIXED decode_block
        — a finishing slot idles for the burst's remainder rather than
        shortening it (a variable length would recompile)."""
        self.trace_counts['decode'] += 1

        def body(carry, _):
            caches, tok, gen, keys = carry
            step_active = active & (gen < budgets)
            (lg, new_cs), _ = _fm.functional_call(
                self._model, params, bufs, args=(Tensor(tok),),
                kwargs={'caches': caches}, training=False)
            inc = step_active.astype(jnp.int32)
            new_cs = [GPTSlotCache(c.k, c.v, c.lengths + inc)
                      for c in new_cs]
            ks = jax.vmap(jax.random.split)(keys)       # [S, 2, 2]
            subs = ks[:, 1]
            keys2 = jnp.where(step_active[:, None], ks[:, 0], keys)
            nxt = jax.vmap(_pick_token)(lg[:, -1], subs, temps, topks,
                                        sample)
            tok2 = jnp.where(step_active, nxt, tok[:, 0])[:, None]
            return (new_cs, tok2, gen + inc, keys2), (tok2[:, 0],
                                                      step_active)

        carry, (toks, actives) = jax.lax.scan(
            body, (caches, tok, gen, keys), None, length=self.decode_block)
        new_caches, tok2, gen2, keys2 = carry
        return new_caches, tok2, gen2, keys2, toks, actives

    # ---- per-step dispatches (lock held) ------------------------------

    def _prefill_call(self, req, start, ids, valid):
        self._caches, tok, key2 = self._prefill_jit(
            self._params, self._bufs, self._caches,
            np.int32(req.slot),
            np.asarray(ids, np.int32)[None, :],
            np.int32(start), np.int32(valid), req._key,
            np.float32(req.temperature), np.int32(req.top_k),
            np.asarray(req.do_sample))
        return tok, key2

    def _decode_step(self):
        slots = self.scheduler.decode_slots()
        if not slots:
            return
        # the span covers dispatch AND the device_get sync — the burst's
        # actual wall time, not just the async enqueue; `_burst_done`
        # splits the same window into host_dispatch (enqueue returns)
        # and device_block (results ready). Dispatch args are stashed
        # for perf_estimate's cost-model lowering (same avals, no
        # retrace).
        args = (self._params, self._bufs, self._caches, self._last,
                self._gen, self._budgets, self._active, self._keys,
                self._temps, self._topks, self._sample)
        self._decode_args = args
        clock = self.metrics.now
        t0 = clock()
        with self._tracer.start_span(
                'serving.decode_burst', annotate=True, mono=t0,
                tags={'rows': len(slots),
                      'block': self.decode_block}) as sp:
            (self._caches, last, gen, keys, toks,
             actives) = self._decode_jit(*args)
            t1 = clock()
            last, gen, keys, toks, actives = jax.device_get(
                (last, gen, keys, toks, actives))
            burst = self._burst_done(sp, t0, t1, clock())
        # device_get can hand back read-only views; these three are
        # mutated in place at prefill/retire
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
