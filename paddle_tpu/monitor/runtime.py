"""RuntimeSampler: background capture of host/runtime health gauges.

What an operator needs on a dashboard BEFORE anything is wrong: host
RSS (is the host-side sparse table / dataloader leaking?), live jax
array bytes (is the device heap creeping toward the 13B-class OOM the
sharding tests gate?), device count (did a chip drop out of the mesh?),
and compiled-program cache sizes (is something retracing per step? —
the serving engine's whole design is that these stay flat).

Every probe is individually guarded: a jax internals rename degrades one
gauge to absent instead of killing the sampler thread. `sample_once()`
is the deterministic test surface; the thread just calls it on an
interval.
"""
import os
import threading

from .registry import default_registry

__all__ = ['RuntimeSampler', 'read_rss_bytes', 'jax_cache_entries']


def read_rss_bytes():
    """Resident set size in bytes from /proc (no psutil in the image);
    None where /proc is unavailable (macOS CI)."""
    try:
        with open('/proc/self/status') as f:
            for line in f:
                if line.startswith('VmRSS:'):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        # ru_maxrss is the PEAK, not current — still monotone-useful
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


def jax_cache_entries():
    """Total entries across jax's registered tracing caches plus the
    C++ pjit executable caches — a flat number means no retrace churn.

    jax keeps every weakref-LRU/lru cache it creates in
    ``jax._src.util._caches`` (what ``jax.clear_caches`` walks); the C++
    fast-path caches (PjitFunctionCache) are NOT in that registry, and
    without them this probe under-reports jax.jit churn — every
    steady-state jit call resolves through them. Private jax API with no
    public spelling: a jax that moves it fails here loudly rather than
    reporting nothing."""
    import jax._src.pjit as _pjit
    import jax._src.util as _u
    # two of the registered memoizers expose cache_clear only
    total = sum(c.cache_info().currsize for c in list(_u._caches)
                if hasattr(c, 'cache_info'))
    for cache in (_pjit._cpp_pjit_cache_fun_only,
                  _pjit._cpp_pjit_cache_explicit_attributes):
        total += cache.size()
    return total


class RuntimeSampler:
    """Periodic gauges over one registry.

        sampler = RuntimeSampler(interval=10.0)
        sampler.start()          # daemon thread; stop() to quit
        sampler.sample_once()    # or: one deterministic capture

    Extra probes register via ``add_source(fn)`` where fn(registry) is
    called per sample (the serving engine wires its trace counts this
    way).
    """

    def __init__(self, registry=None, interval=10.0, jax_metrics=True):
        self.registry = registry if registry is not None \
            else default_registry()
        self.interval = float(interval)
        self._jax = bool(jax_metrics)
        self._stop = threading.Event()
        self._thread = None
        self._sources = []
        r = self.registry
        self._rss = r.gauge('process_resident_bytes',
                            'host RSS of this process')
        self._live_bytes = r.gauge('jax_live_array_bytes',
                                   'bytes held by live jax arrays')
        self._live_count = r.gauge('jax_live_array_count',
                                   'number of live jax arrays')
        self._devices = r.gauge('jax_device_count',
                                'devices visible to this process')
        self._caches = r.gauge('jax_trace_cache_entries',
                               'entries across jax tracing caches '
                               '(flat == no retrace churn)')
        self._samples = r.counter('runtime_samples_total',
                                  'runtime sampler iterations')

    def add_source(self, fn):
        """Register an extra probe fn(registry), run every sample."""
        self._sources.append(fn)
        return fn

    def sample_once(self):
        rss = read_rss_bytes()
        if rss is not None:
            self._rss.set(rss)
        if self._jax:
            try:
                import jax
                arrays = jax.live_arrays()
                self._live_bytes.set(
                    sum(getattr(a, 'nbytes', 0) for a in arrays))
                self._live_count.set(len(arrays))
                self._devices.set(len(jax.devices()))
            except Exception:
                pass
            self._caches.set(jax_cache_entries())
        for fn in list(self._sources):
            try:
                fn(self.registry)
            except Exception:
                pass  # a broken probe must not take the sampler down
        self._samples.inc()

    def _run(self):
        while not self._stop.wait(self.interval):
            self.sample_once()

    def start(self):
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name='runtime-sampler', daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=self.interval + 1.0)
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False
