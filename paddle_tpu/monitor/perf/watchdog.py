"""CompileWatchdog: count, time, and attribute every jit compile.

Hooks ``jax.monitoring``'s event-duration listeners (graceful no-op on
a jaxlib without them): each jit compilation fires three duration
events — jaxpr trace, MLIR lowering, backend compile — which land in
the ``perf_compiles_total`` counter and ``perf_compile_seconds``
histogram, labeled by stage.

The steady-state contract is the interesting part. After the owner
declares a warmup barrier (``declare_warmup``), ANY further backend
compile is a recompile: the watchdog walks the live stack to attribute
it to the triggering callsite and the abstract-shape signature that
forced the retrace (the pjit frame's ClosedJaxpr ``in_avals``), bumps
``perf_recompiles_total``, pushes a ``perf.recompile`` record into the
tracer's flight ring and fires a throttled flight dump — and, under
``PADDLE_TPU_COMPILE_STRICT=1`` (or ``strict=True``), raises
:class:`RecompileError` straight out of the offending dispatch.

Listeners are process-global: every watchdog sees every compile in the
process. The optional ``owner`` filter keeps multi-engine processes
honest — a recompile is only charged to a watchdog whose owner object
appears on the compiling stack (so replica A's warm barrier is not
tripped by replica B's first compile). With no owner, every post-warmup
compile counts.

Persistent-cache composition: jax fires the backend-compile duration
event even when ``compiler.compile_or_get_cached`` was served from the
persistent compilation cache (the event wraps the whole call), so a
cache-hit *reload* after ``declare_warmup()`` used to count as a
recompile. The watchdog now diffs ``framework.compile_cache``'s
per-thread hit/miss tallies around every compile event: a fresh hit is
exported as ``perf_persistent_cache_hits_total`` and exempted from the
recompile path; a miss (or a cache-less compile) stays a violation.
Each watchdog keeps its own per-thread marks, so several watchdogs on
one registry classify every compile independently and identically.
"""
import contextlib
import os
import sys
import threading
import time

from ..registry import default_registry
from ..telemetry import record_perf_schema
from .. import tracing as _tracing

__all__ = ['CompileWatchdog', 'RecompileError', 'COMPILE_EVENTS']

# jax.monitoring event -> stage label (closed set; docs/observability.md)
COMPILE_EVENTS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'compile',
}

_KINDS = ('trace', 'lower', 'compile')


class RecompileError(RuntimeError):
    """A jit recompile happened after a declared warmup barrier while
    the watchdog ran in strict mode."""


def _is_internal_frame(filename):
    """Frames that can never be the *triggering* callsite: jax's own
    machinery, contextlib plumbing, and this package."""
    f = filename.replace('\\', '/')
    return ('/jax/' in f or '/jaxlib/' in f or f.endswith('contextlib.py')
            or '/monitor/perf/' in f or f.endswith('threading.py'))


def _walk_attribution(max_depth=120):
    """(callsite, signature, owner_candidates) from the live stack.

    Called inside jax's compile path, so the stack below us holds the
    pjit frame whose local ``jaxpr`` (a ClosedJaxpr) carries the
    abstract input shapes that keyed this compilation, and further down
    the first non-jax frame is the dispatch that triggered it.
    ``owner_candidates`` collects every ``self`` seen on non-jax frames
    so a watchdog bound to an engine can tell its own dispatches from a
    sibling replica's.
    """
    callsite = signature = None
    owners = []
    try:
        f = sys._getframe(2)
    except Exception:
        return callsite, signature, owners
    depth = 0
    while f is not None and depth < max_depth:
        code = f.f_code
        if signature is None:
            jaxpr = f.f_locals.get('jaxpr')
            avals = getattr(jaxpr, 'in_avals', None)
            if avals is not None:
                try:
                    signature = ', '.join(a.str_short() for a in avals)
                except Exception:
                    signature = repr(avals)
                signature = signature[:400]
        if not _is_internal_frame(code.co_filename):
            if callsite is None:
                callsite = '%s:%d:%s' % (code.co_filename, f.f_lineno,
                                         code.co_name)
            slf = f.f_locals.get('self')
            if slf is not None:
                owners.append(slf)
        f = f.f_back
        depth += 1
    return callsite, signature, owners


class CompileWatchdog:
    """Per-registry jit-compilation accountant with a warmup barrier.

        wd = CompileWatchdog()           # default registry + tracer
        ... compile everything once ...
        wd.declare_warmup('serving steady state')
        # any compile from here on is a counted, attributed recompile

    ``enabled`` is a plain attribute checked first in the listener (the
    registry's one-load+branch discipline); ``close()`` unregisters the
    listener — always pair construction with close() in tests.
    """

    def __init__(self, registry=None, tracer=None, strict=None,
                 owner=None, name='', clock=None, max_records=64):
        self.registry = registry if registry is not None \
            else default_registry()
        fams = record_perf_schema(self.registry)
        self._m_compiles = {k: fams['perf_compiles_total'].labels(k)
                            for k in _KINDS}
        self._h_seconds = {k: fams['perf_compile_seconds'].labels(k)
                           for k in _KINDS}
        self._m_recompiles = fams['perf_recompiles_total']
        self._m_cache_hits = fams['perf_persistent_cache_hits_total']
        self._m_cache_misses = fams['perf_persistent_cache_misses_total']
        try:
            from ...framework import compile_cache as _cc
        except Exception:
            _cc = None
        self._cc = _cc
        self._cc_marks = threading.local()  # this watchdog's own marks
        self.enabled = True
        self.armed = False
        self.warmup_label = None
        self.name = name
        self.owner = owner
        if strict is None:
            strict = os.environ.get('PADDLE_TPU_COMPILE_STRICT') == '1'
        self.strict = bool(strict)
        self.max_records = int(max_records)
        self.counts = {k: 0 for k in _KINDS}
        self.recompile_count = 0    # this watchdog's own violations
        self.records = []           # recompile attributions, oldest first
        self._tracer = tracer       # None -> default_tracer() at use
        self._clock = clock or time.time
        self._lock = threading.Lock()
        self._listener = None
        self._install()

    # ---- listener lifecycle -------------------------------------------

    def _install(self):
        import jax

        def _listen(event, duration, **kw):
            if self.enabled:
                self._on_event(event, duration)

        jax.monitoring.register_event_duration_secs_listener(_listen)
        self._listener = _listen

    @property
    def active(self):
        """True while the jax.monitoring listener is registered."""
        return self._listener is not None

    def close(self):
        """Stop counting and unregister the listener (idempotent)."""
        self.enabled = False
        listener, self._listener = self._listener, None
        if listener is None:
            return
        import jax
        jax.monitoring.unregister_event_duration_listener(listener)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---- warmup barrier -----------------------------------------------

    def declare_warmup(self, label='warmup'):
        """Arm recompile accounting: every backend compile from now on
        is a steady-state violation."""
        self.warmup_label = label
        self.armed = True
        return self

    def disarm(self):
        self.armed = False

    @contextlib.contextmanager
    def suspended(self):
        """Temporarily disarm — for deliberate compiles (cost-model
        lowering, bench warm-compile timing) inside a warm window."""
        was = self.armed
        self.armed = False
        try:
            yield self
        finally:
            self.armed = was

    # ---- event path ---------------------------------------------------

    def _on_event(self, event, duration):
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        try:
            with self._lock:
                self.counts[kind] += 1
            self._m_compiles[kind].inc()
            self._h_seconds[kind].observe(float(duration))
        except Exception:
            return              # accounting must never break a compile
        if kind != 'compile':
            return
        cache_hit = False
        try:
            cache_hit = self._classify_cache()
        except Exception:
            pass                # classification must never break a compile
        if self.armed and not cache_hit:
            self._on_recompile(float(duration))

    def _classify_cache(self):
        """Diff compile_cache's per-thread lookup tallies against this
        watchdog's marks: returns True when the compile event being
        handled was a persistent-cache HIT (exempt from the recompile
        rule), publishing the hit/miss counters along the way. The
        lookup event fires on the compiling thread before the duration
        event does, so the fresh delta belongs to this compile."""
        if self._cc is None:
            return False
        hits, misses, last = self._cc.thread_state()
        marks = self._cc_marks
        prev = getattr(marks, 'state', None)
        marks.state = (hits, misses)
        if prev is None:
            # first compile event this watchdog sees on this thread:
            # only the lookup belonging to THIS compile is fresh —
            # earlier lookups predate the watchdog (or its thread) and
            # must not be charged to it
            dh = 1 if last == 'hit' else 0
            dm = 1 if last == 'miss' else 0
        else:
            dh = hits - prev[0]
            dm = misses - prev[1]
        if dh > 0:
            self._m_cache_hits.inc(dh)
        if dm > 0:
            self._m_cache_misses.inc(dm)
        return dh > 0 and dm == 0

    def _on_recompile(self, duration):
        callsite, signature, owners = _walk_attribution()
        if self.owner is not None and not any(o is self.owner
                                              for o in owners):
            return              # someone else's compile, not a violation
        rec = {'time': self._clock(), 'duration_s': duration,
               'after_warmup': self.warmup_label, 'callsite': callsite,
               'signature': signature, 'watchdog': self.name}
        with self._lock:
            self.recompile_count += 1
            self.records.append(rec)
            del self.records[:-self.max_records]
        self._m_recompiles.inc()
        tracer = self._tracer if self._tracer is not None \
            else _tracing.default_tracer()
        try:
            # drop the attribution into the flight ring so the dump
            # that follows carries WHO retraced, not just that one did
            tracer.recorder.record({'name': 'perf.recompile',
                                    'start': rec['time'],
                                    'duration': duration,
                                    'tags': dict(rec)})
            tracer.recorder.maybe_dump('recompile')
        except Exception:
            pass
        if self.strict:
            raise RecompileError(
                'recompile after warmup barrier %r: callsite=%s '
                'signature=%s (set PADDLE_TPU_COMPILE_STRICT=0 or fix '
                'the retrace)' % (self.warmup_label, callsite, signature))

    # ---- inspection ---------------------------------------------------

    @property
    def recompiles(self):
        """Violations charged to THIS watchdog (the registry counter is
        shared when several watchdogs publish to one registry)."""
        return self.recompile_count

    def report(self):
        """Plain-dict summary for logs / bench rows."""
        with self._lock:
            return {'counts': dict(self.counts),
                    'recompiles': self.recompiles,
                    'armed': self.armed,
                    'warmup_label': self.warmup_label,
                    'records': [dict(r) for r in self.records]}
