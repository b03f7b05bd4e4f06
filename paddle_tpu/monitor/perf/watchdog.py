"""The process's compile log, and CompileWatchdog: log, count, time and
attribute every jit compile.

**The compile log** (:class:`CompileLog`, one a process: ``compile_log()``)
is the ONE consumer of jax's compile events. ``install()`` registers it
with ``jax.monitoring`` once; ``paddle_tpu``'s import calls it, after
``framework.core`` has loaded jax (this module stays stdlib-only at
import). Each jit compilation fires three duration events, jaxpr trace,
MLIR lowering and backend compile (or load from the persistent cache),
each with the program's ``fun_name``; jax also records the instant each
one starts, which is how the log knows, without timing guesses, that an
event ran inside another one on the same thread (a ``jnp`` helper traced
inside the program that calls it, an eager op run while a program
traces). Every outer event becomes one entry in the format of
``tracing.Span.to_dict()``, named ``perf.compile``, on both of the
tracer's clocks, tagged ``program``, ``stage``, ``seconds``, ``cache``
(``hit`` / ``miss`` / ``none`` on the compile stage), ``owner``: the
class that registered the program's function name (``own``: the serving
engine's and ``TrainStep``'s programs) or None, and ``nested_s`` /
``nested_n``: the seconds and the number of the events, by stage, that
ran inside it. A nested event is summed into the entry of the event
around it, not logged: a program's trace holds thousands of them (one
for each ``jnp`` helper), and an entry each costs a set-up time and
memory for what no reader looks at one by one. Sums of the entries'
``seconds`` count each second once; with ``nested_s`` they give what a
plain listener sums. No entry walks the stack. The log keeps the FIRST
``LOG_CAPACITY`` entries of the process and counts the events it left
out: set-up is the start of a process. It follows the default tracer's
switch: with tracing off nothing is logged and ``totals()`` gives None.

**CompileWatchdog** subscribes to the log's listener (the watchdog
registers none of its own). The three events land in the
``perf_compiles_total`` counter and ``perf_compile_seconds`` histogram,
labeled by stage.

The steady-state contract is the interesting part. After the owner
declares a warmup barrier (``declare_warmup``), ANY further backend
compile is a recompile: the watchdog walks the live stack to attribute
it to the triggering callsite and the abstract-shape signature that
forced the retrace (the pjit frame's ClosedJaxpr ``in_avals``), bumps
``perf_recompiles_total``, pushes a ``perf.recompile`` record and the
log's entries of the rebuilt program (its trace, lower and compile) into
the tracer's flight ring and fires a throttled flight dump — and, under
``PADDLE_TPU_COMPILE_STRICT=1`` (or ``strict=True``), raises
:class:`RecompileError` straight out of the offending dispatch.

Every watchdog sees every compile in the process. The optional
``owner`` filter keeps multi-engine processes honest — a recompile is
only charged to a watchdog whose owner object appears on the compiling
stack (so replica A's warm barrier is not tripped by replica B's first
compile). With no owner, every post-warmup compile counts.

Persistent-cache composition: jax fires the backend-compile duration
event even when ``compiler.compile_or_get_cached`` was served from the
persistent compilation cache (the event wraps the whole call), so a
cache-hit *reload* after ``declare_warmup()`` used to count as a
recompile. The log diffs ``framework.compile_cache``'s per-thread
hit/miss tallies around every compile event (the lookup fires on the
compiling thread before the duration event does, so the fresh delta
belongs to this compile): its entry's ``cache`` tag, and what every
watchdog exports as ``perf_persistent_cache_hits_total`` /
``misses_total``; a hit is exempt from the recompile path, a miss (or a
cache-less compile) stays a violation.
"""
import collections
import contextlib
import os
import sys
import threading
import time

from ..registry import default_registry
from ..telemetry import record_perf_schema
from .. import tracing as _tracing

__all__ = ['CompileWatchdog', 'RecompileError', 'COMPILE_EVENTS',
           'CompileLog', 'compile_log', 'install', 'LOG_CAPACITY']

# jax.monitoring event -> stage label (closed set; docs/observability.md)
COMPILE_EVENTS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'compile',
}

_KINDS = ('trace', 'lower', 'compile')

# entries the compile log keeps, the first of the process (a run of
# GPT-2 large's training cell logs under 200, its 23 000 nested events
# summed into them)
LOG_CAPACITY = 16384

# one outer event, kept as a tuple and read out as a span dict; `nested`
# is None or the seconds then the counts, by stage, of the events inside
_Event = collections.namedtuple(
    '_Event', 'start start_mono end_mono seconds program stage cache owner '
              'tid nested')


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


class CompileLog:
    """The process's record of the programs jax built (module docstring).

    Written by the listeners ``install()`` registers; read by
    ``totals()`` / ``report()`` / ``entries()``, and by a recompile's
    flight record (``last_build()``). Thread-safe: events
    arrive on whatever thread compiles."""

    def __init__(self):
        self._cc = None            # framework.compile_cache, at install()
        self.capacity = LOG_CAPACITY
        self._lock = threading.Lock()
        self._events = []          # _Event, in the order they ended
        self.left_out = 0          # events not logged: log full, tracing off
        self._gap_mono = None      # end of the first of them
        self._owners = {}          # function name -> owner's class name
        self._watchdogs = ()       # subscribed CompileWatchdogs
        self._tls = threading.local()   # open events, cache marks

    # ---- who builds what ----------------------------------------------

    def own(self, owner, *functions):
        """Name `owner` (a class name) the owner of the programs jitted
        from `functions`: their entries carry it as tag ``owner``."""
        with self._lock:
            for fn in functions:
                self._owners[fn.__name__] = owner

    def subscribe(self, watchdog):
        with self._lock:
            self._watchdogs += (watchdog,)

    def unsubscribe(self, watchdog):
        with self._lock:
            self._watchdogs = tuple(w for w in self._watchdogs
                                    if w is not watchdog)

    # ---- the listeners ------------------------------------------------

    def _on_start(self, event, value, **kw):
        """jax records each compile event's start (epoch) as a scalar."""
        if event in COMPILE_EVENTS:
            tls = self._tls
            if not hasattr(tls, 'open'):
                tls.open = []
            tls.open.append((value, time.monotonic()))

    def _on_end(self, event, duration, fun_name='', **kw):
        stage = COMPILE_EVENTS.get(event)
        if stage is None:
            return
        tls = self._tls
        opened = getattr(tls, 'open', None)
        if opened:
            start, start_mono = opened.pop()
        else:                      # its start predates the listeners
            start = time.time() - duration
            start_mono = time.monotonic() - duration
        lookups = self._lookups() if stage == 'compile' else (0, 0)
        inner = getattr(tls, 'inner', None)
        if opened:                 # nested: summed into the outer entry
            if inner is None:
                inner = tls.inner = [0.0, 0.0, 0.0, 0, 0, 0]
            i = _KINDS.index(stage)
            inner[i] += duration
            inner[i + 3] += 1
        else:
            tls.inner = None
            if _tracing.default_tracer().enabled \
                    and len(self._events) < self.capacity:
                self._log(stage, fun_name, duration, start, start_mono,
                          lookups, inner)
            else:
                self._leave_out(start_mono + duration,
                                1 + (sum(inner[3:]) if inner else 0))
        for wd in self._watchdogs:
            if wd.enabled:
                wd._on_event(event, duration, lookups)

    def _lookups(self):
        """(hits, misses) of the persistent cache on this thread since
        its previous compile event: the lookup of THIS compile. On a
        thread's first event only its last lookup is this compile's."""
        if self._cc is None:
            return 0, 0
        hits, misses, last = self._cc.thread_state()
        prev = getattr(self._tls, 'cache', None)
        self._tls.cache = (hits, misses)
        if prev is None:
            return int(last == 'hit'), int(last == 'miss')
        return hits - prev[0], misses - prev[1]

    def _log(self, stage, fun_name, duration, start, start_mono, lookups,
             inner):
        program = sys.intern(fun_name[4:-1] if fun_name.startswith('jit(')
                             else fun_name)
        cache = None
        if stage == 'compile':
            cache = 'miss' if lookups[1] else 'hit' if lookups[0] \
                else 'none'
        ev = _Event(start, start_mono, start_mono + duration, duration,
                    program, stage, cache, self._owners.get(program),
                    threading.get_ident(), inner and tuple(inner))
        with self._lock:
            if len(self._events) < self.capacity:
                self._events.append(ev)
                return
        # another thread filled it
        self._leave_out(ev.end_mono, 1 + (sum(inner[3:]) if inner else 0))

    def _leave_out(self, end_mono, events):
        with self._lock:
            self.left_out += events
            if self._gap_mono is None:
                self._gap_mono = end_mono

    # ---- reading ------------------------------------------------------

    def entries(self):
        """The kept entries, in the order they ended: span dicts in the
        format of ``tracing.Span.to_dict()`` (``tracing.spans_to_chrome``
        and flight dumps take them)."""
        with self._lock:
            events = list(self._events)
        return [_span(e) for e in events]

    def last_build(self):
        """The entries of the program this thread built last: its trace,
        lower and compile, as far as they were kept. A recompile
        puts them in the flight ring, so the dump that follows shows
        which stage the retrace spent its time in."""
        tid = threading.get_ident()
        out = []
        with self._lock:
            for e in reversed(self._events):
                if e.tid != tid:
                    continue
                if out and (e.program != out[-1].program
                            or e.stage == 'compile'):
                    break
                out.append(e)
                if e.stage == 'trace':
                    break
        return [_span(e) for e in reversed(out)]

    def totals(self, until_mono):
        """Seconds and counts of the entries that ended before the
        monotonic stamp `until_mono` (a nested event counts where the
        event around it ended): ``outer_s`` {stage: the entries'
        seconds}, ``nested_s`` {stage: those and their nested events':
        what a plain listener sums}, ``counts`` {stage: events, nested
        ones too}, ``owned_s`` (seconds of the entries of programs with
        an owner) and ``programs`` (compile-stage events: programs built
        or loaded). None with tracing off, or when an event that ended
        before `until_mono` was left out."""
        if not _tracing.default_tracer().enabled:
            return None
        with self._lock:
            if self._gap_mono is not None and self._gap_mono < until_mono:
                return None
            events = list(self._events)
        out = {'outer_s': dict.fromkeys(_KINDS, 0.0),
               'nested_s': dict.fromkeys(_KINDS, 0.0),
               'counts': dict.fromkeys(_KINDS, 0), 'owned_s': 0.0}
        for e in events:
            if e.end_mono >= until_mono:
                continue
            out['outer_s'][e.stage] += e.seconds
            out['nested_s'][e.stage] += e.seconds
            out['counts'][e.stage] += 1
            if e.owner is not None:
                out['owned_s'] += e.seconds
            if e.nested:
                for i, k in enumerate(_KINDS):
                    out['nested_s'][k] += e.nested[i]
                    out['counts'][k] += e.nested[i + 3]
        out['programs'] = out['counts']['compile']
        return out

    def report(self, top=10, since_mono=None, until_mono=None):
        """The `top` costliest programs by their entries' seconds among
        the entries that ended in [since_mono, until_mono): dicts of
        ``program``, ``owner``, ``seconds``, seconds by stage and
        ``builds`` (compile-stage entries), costliest first."""
        lo = -float('inf') if since_mono is None else since_mono
        hi = float('inf') if until_mono is None else until_mono
        with self._lock:
            events = list(self._events)
        rows = {}
        for e in events:
            if not lo <= e.end_mono < hi:
                continue
            row = rows.get((e.program, e.owner))
            if row is None:
                row = rows[(e.program, e.owner)] = dict(
                    program=e.program, owner=e.owner, seconds=0.0,
                    builds=0, **dict.fromkeys(_KINDS, 0.0))
            row['seconds'] += e.seconds
            row[e.stage] += e.seconds
            row['builds'] += e.stage == 'compile'
        return sorted(rows.values(), key=lambda r: -r['seconds'])[:top]

    def reset(self):
        """Forget the entries and the count of those left out: the log
        starts over, as a fresh process's does (a test worker runs
        many). The owners stay."""
        with self._lock:
            self._events = []
            self.left_out = 0
            self._gap_mono = None


def _span(e):
    """A logged event as a ``tracing.Span.to_dict()`` span dict."""
    nested = e.nested or (0.0,) * 3 + (0,) * 3
    return {'name': 'perf.compile', 'trace_id': None, 'span_id': None,
            'parent_id': None, 'start': e.start, 'end': e.start + e.seconds,
            'start_mono': e.start_mono, 'end_mono': e.end_mono,
            'tid': e.tid, 'status': 'ok', 'error': None,
            'tags': {'program': e.program, 'stage': e.stage,
                     'seconds': e.seconds, 'cache': e.cache,
                     'owner': e.owner,
                     'nested_s': dict(zip(_KINDS, nested[:3])),
                     'nested_n': dict(zip(_KINDS, nested[3:]))},
            'events': []}


_log = CompileLog()
_installed = False
_install_lock = threading.Lock()


def compile_log():
    """The process's compile log."""
    return _log


def install():
    """Register the compile log's listeners with jax, once a process;
    False on a jax without ``jax.monitoring`` listeners."""
    global _installed
    with _install_lock:
        if not _installed:
            import jax
            mon = getattr(jax, 'monitoring', None)
            if mon is None or not hasattr(mon, 'register_scalar_listener'):
                return False
            from ...framework import compile_cache
            _log._cc = compile_cache
            mon.register_scalar_listener(_log._on_start)
            mon.register_event_duration_secs_listener(_log._on_end)
            _installed = True
    return True


class CompileWatchdog:
    """Per-registry jit-compilation accountant with a warmup barrier.

        wd = CompileWatchdog()           # default registry + tracer
        ... compile everything once ...
        wd.declare_warmup('serving steady state')
        # any compile from here on is a counted, attributed recompile

    ``enabled`` is a plain attribute the log checks before handing an
    event on (the registry's one-load+branch discipline); ``close()``
    unsubscribes from the log — always pair construction with close()
    in tests.
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
        self.active = install()     # True while the log hands events on
        if self.active:
            _log.subscribe(self)

    def close(self):
        """Stop counting and unsubscribe from the log (idempotent)."""
        self.enabled = False
        if self.active:
            self.active = False
            _log.unsubscribe(self)

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

    def _on_event(self, event, duration, lookups=(0, 0)):
        """One compile event from the log; `lookups` = the persistent
        cache's (hits, misses) that belong to it."""
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
        hits, misses = lookups
        if hits > 0:
            self._m_cache_hits.inc(hits)
        if misses > 0:
            self._m_cache_misses.inc(misses)
        if self.armed and not (hits > 0 and misses == 0):
            self._on_recompile(float(duration))

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
            for entry in _log.last_build():   # and where its time went
                tracer.recorder.record(entry)
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
