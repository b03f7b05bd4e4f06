"""One configuration path for jax's persistent compilation cache.

Every entry point that wants the cache (the Predictor's
``set_optim_cache_dir``, bench.py, bench_extra.py, chip_smoke.py, the
dryrun driver) goes through :func:`configure`, which is idempotent across
repeated calls and callers — two Predictors in one process, or a
Predictor plus the bench harness, configure the cache once.

The module also *counts*: one process-global ``jax.monitoring`` event
listener tallies ``/jax/compilation_cache/cache_hits`` and
``cache_misses`` globally and per-thread. The CompileWatchdog
(monitor/perf/watchdog.py) reads the per-thread counts to tell a
persistent-cache *hit* (XLA skipped; not a steady-state violation)
from a real backend compile, and exports them as the
``perf_persistent_cache_hits_total`` / ``misses_total`` families.
Bench rows surface the same tallies as ``compile_cache_hit_rate``.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR``, when the
environment sets it — jax reads that variable itself, and this module
then sets no directory in code, whatever a caller passes (a driver that
places the cache must find it where it put it). Otherwise the explicit
argument (the Predictor's ``set_optim_cache_dir``), else the fixed
``<repo>/.jax_cache``. The path is part of jax's cache key, so it is
never derived from a temp dir, a pid or the time.

Stdlib-only at import time (jax loads inside :func:`configure`), so
schema tooling can import the counters without touching a backend.
"""
import contextlib
import os
import threading

__all__ = ['configure', 'disable', 'suspended', 'enabled', 'cache_dir',
           'default_dir', 'stats', 'hit_rate', 'thread_state', 'reset_stats']

_HIT_EVENT = '/jax/compilation_cache/cache_hits'
_MISS_EVENT = '/jax/compilation_cache/cache_misses'

_lock = threading.Lock()
_dir = None                 # currently configured cache dir (None = off)
_listener = None            # installed jax.monitoring record_event hook
_hits = 0
_misses = 0
_tls = threading.local()    # per-thread hit/miss tallies for watchdogs


_ENV_DIR = 'JAX_COMPILATION_CACHE_DIR'


def default_dir():
    """The cache dir :func:`configure` uses when none is given."""
    return os.environ.get(_ENV_DIR) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), '.jax_cache')


def _on_event(event, **kwargs):
    global _hits, _misses
    if event == _HIT_EVENT:
        with _lock:
            _hits += 1
        _tls.hits = getattr(_tls, 'hits', 0) + 1
        _tls.last = 'hit'
    elif event == _MISS_EVENT:
        with _lock:
            _misses += 1
        _tls.misses = getattr(_tls, 'misses', 0) + 1
        _tls.last = 'miss'


def _install_listener():
    global _listener
    if _listener is not None:
        return
    import jax
    jax.monitoring.register_event_listener(_on_event)
    _listener = _on_event


def configure(path=None):
    """Enable the persistent compile cache (where: module docstring) and
    install the hit/miss listener.

    Idempotent: repeat calls with the same effective dir are no-ops; a
    different dir re-points the live config (last caller wins, which is
    what the reference's per-Predictor cache dirs did) — except under
    ``JAX_COMPILATION_CACHE_DIR``, which always wins and is never
    overwritten. Returns the effective dir."""
    global _dir
    env_dir = os.environ.get(_ENV_DIR)
    path = env_dir or path or default_dir()
    with _lock:
        already = _dir == path
    _install_listener()
    if already:
        return path
    import jax
    jax.config.update('jax_enable_compilation_cache', True)
    if not env_dir:
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    _drop_cache_latch()
    with _lock:
        _dir = path
    return path


def _drop_cache_latch():
    """jax memoizes "is the cache used" at the FIRST compile of the
    process; any compile before configure() would latch it off and make
    the config knobs dead. reset_cache() drops the latch (and the
    in-memory handle — the disk cache is untouched) so the next compile
    re-evaluates the config."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def disable():
    """Turn the persistent cache back off (tests; audits use the scoped
    :func:`suspended` instead). Counters keep running."""
    global _dir
    with _lock:
        if _dir is None:
            return
        _dir = None
    import jax
    jax.config.update('jax_enable_compilation_cache', False)
    _drop_cache_latch()


@contextlib.contextmanager
def suspended():
    """Force the compiles inside the block through XLA even when the
    process has a persistent cache in use (restored on exit) — for
    audits that read what only a real compile emits, and for compiles
    for a described, unattached device, whose cache entries cannot be
    read back. The config flip alone is not enough: jax memoizes
    cache-in-use at the first compile, so the latch is dropped on BOTH
    transitions."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        yield
        return
    jax.config.update('jax_enable_compilation_cache', False)
    _drop_cache_latch()
    try:
        yield
    finally:
        jax.config.update('jax_enable_compilation_cache', True)
        _drop_cache_latch()


def enabled():
    """True when configure() has pointed jax at a persistent cache."""
    with _lock:
        return _dir is not None


def cache_dir():
    with _lock:
        return _dir


def stats():
    """Process-wide {'hits', 'misses'} since import (or reset_stats)."""
    with _lock:
        return {'hits': _hits, 'misses': _misses}


def hit_rate():
    """hits / (hits + misses), or None before any cache lookup — the
    bench ladder's ``compile_cache_hit_rate`` column."""
    with _lock:
        total = _hits + _misses
        return (_hits / total) if total else None


def thread_state():
    """(hits, misses, last) for the CALLING thread, where `last` is
    'hit' / 'miss' / None. jax fires the lookup event on the compiling
    thread before the backend-compile duration event completes, so a
    watchdog's duration listener sees this thread's lookup for the
    compile it is classifying already counted."""
    return (getattr(_tls, 'hits', 0), getattr(_tls, 'misses', 0),
            getattr(_tls, 'last', None))


def reset_stats():
    """Zero the global tallies (tests). Per-thread tallies are left to
    age out — watchdogs diff against their own marks, so stale thread
    counts never leak across watchdog instances."""
    global _hits, _misses
    with _lock:
        _hits = 0
        _misses = 0
