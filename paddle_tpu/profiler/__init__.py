"""Profiler (reference: paddle/fluid/platform/profiler.h RecordEvent +
python/paddle/fluid/profiler.py).

TPU-native: jax.profiler (XPlane -> Perfetto/TensorBoard) replaces
CUPTI+timeline.py; RecordEvent maps to TraceAnnotation so op names stay
readable in traces (SURVEY.md §5.1).
"""
import contextlib
import time

import jax

from ..monitor import tracing as _tracing

__all__ = ['RecordEvent', 'profiler', 'start_profiler', 'stop_profiler',
           'Profiler', 'ProfilerTarget', 'ProfilerState',
           'export_chrome_tracing', 'load_profiler_result', 'merge_traces']


class RecordEvent:
    """RAII trace annotation (platform/profiler.h:127 parity).

    Dual-sink through the tracer's one code path
    (``start_span(annotate=True)``): the name lands in the device trace
    as a jax.profiler.TraceAnnotation AND in the host tracer as a span,
    so the same region shows up in Perfetto next to XLA ops and in the
    flight recorder / /debug/traces view. With the tracer disabled it
    records nothing in either sink."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def __enter__(self):
        self._span = _tracing.default_tracer().start_span(
            self.name, annotate=True)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(*(exc or (None, None, None)))
        return False

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__(None, None, None)


_active_dir = [None]


def start_profiler(state='All', tracer_option='Default',
                   log_dir='/tmp/paddle_tpu_profile'):
    # mark active only AFTER start_trace succeeds, so a failed start
    # (bad dir, trace already running) leaves no stale state behind and
    # the paired stop_profiler stays a no-op
    jax.profiler.start_trace(log_dir)
    _active_dir[0] = log_dir


def stop_profiler(sorted_key=None, profile_path=None):
    """Idempotent: safe to call repeatedly, or without a start."""
    if _active_dir[0] is None:
        return
    _active_dir[0] = None
    jax.profiler.stop_trace()


@contextlib.contextmanager
def profiler(state='All', sorted_key=None,
             profile_path='/tmp/paddle_tpu_profile', tracer_option='Default'):
    start_profiler(state, tracer_option, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class ProfilerTarget:
    CPU = 'cpu'
    GPU = 'gpu'
    TPU = 'tpu'


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class Profiler:
    """paddle.profiler.Profiler-style context over jax.profiler."""

    def __init__(self, targets=None, scheduler=None,
                 on_trace_ready=None, timer_only=False,
                 log_dir=None):
        import os
        # launcher/spawn seat a per-rank trace dir so a distributed run's
        # traces land rank-separated, ready for merge_traces
        self.log_dir = (log_dir
                        or os.environ.get('PADDLE_TRAINER_TRACE_DIR')
                        or '/tmp/paddle_tpu_profile')
        self.timer_only = timer_only
        self._on_trace_ready = on_trace_ready
        self._times = []
        self._t0 = None
        self._tracing = False     # a device trace is actually running

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        self._t0 = time.time()
        if not self.timer_only:
            # the handler may redirect log_dir (export_chrome_tracing),
            # so it must run BEFORE the trace starts
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True

    def stop(self):
        # only stop a trace this profiler actually started: stop()
        # without start(), after a failed start(), or called twice must
        # not raise (and must not kill someone else's trace)
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()

    def step(self, num_samples=None):
        now = time.time()
        if self._t0 is not None:
            self._times.append(now - self._t0)
        self._t0 = now

    def step_info(self, unit=None):
        if not self._times:
            return ''
        avg = sum(self._times[-10:]) / len(self._times[-10:])
        return 'avg step time: %.4fs' % avg

    def summary(self, **kwargs):
        print(self.step_info())


def export_chrome_tracing(dir_name, worker_name=None):
    """Reference tools/timeline.py output parity: jax traces are XPlane
    protos consumable by TensorBoard/Perfetto; this returns an
    on_trace_ready callback that redirects the profiler's output dir.
    The Profiler invokes it at start(), before tracing begins, so the
    trace files land under `dir_name` when the profiler stops."""
    def handler(prof):
        prof.log_dir = dir_name
    return handler


def merge_traces(rank_dirs, out_path, rank_names=None):
    """Merge per-rank chrome-tracing outputs into ONE timeline with
    per-rank lanes (reference: tools/CrossStackProfiler/ — merges
    per-trainer timelines into a cluster view).

    rank_dirs: ordered per-rank trace dirs (each a jax.profiler/Profiler
    log_dir, holding *.trace.json[.gz] chrome traces). out_path: merged
    chrome-tracing JSON, loadable in Perfetto/chrome://tracing. Every
    rank's processes are remapped into a disjoint pid range and labeled
    'rank N: <process>', so lanes group by rank.
    """
    import gzip
    import json
    import os

    _PID_STRIDE = 1 << 20
    merged = []
    total = 0
    for rank, d in enumerate(rank_dirs):
        label = (rank_names[rank] if rank_names else 'rank %d' % rank)
        events = []
        for f in load_profiler_result(d):
            if f.endswith('.trace.json.gz'):
                try:
                    with gzip.open(f, 'rt') as fh:
                        data = json.load(fh)
                except (OSError, EOFError, ValueError):
                    continue  # truncated trace (run killed mid-write)
            elif f.endswith(('.trace.json', '.json')):
                with open(f) as fh:
                    try:
                        data = json.load(fh)
                    except ValueError:
                        continue
            else:
                continue
            evs = data.get('traceEvents', data) if isinstance(data, dict) \
                else data
            if isinstance(evs, list):
                events.extend(e for e in evs if isinstance(e, dict))
        pnames = {e.get('pid'): e.get('args', {}).get('name')
                  for e in events
                  if e.get('ph') == 'M' and e.get('name') == 'process_name'}
        # collision-free remap: sequential index per distinct source pid
        pid_map = {}

        def _remap(pid):
            if pid not in pid_map:
                pid_map[pid] = rank * _PID_STRIDE + len(pid_map)
            return pid_map[pid]

        seen_pids = set()
        for e in events:
            e = dict(e)
            pid = e.get('pid', 0)
            e['pid'] = _remap(pid)
            if e.get('ph') == 'M' and e.get('name') == 'process_name':
                orig = e.get('args', {}).get('name') or str(pid)
                e['args'] = {'name': '%s: %s' % (label, orig)}
            seen_pids.add((pid, e['pid']))
            merged.append(e)
        for orig_pid, new_pid in seen_pids:
            if orig_pid not in pnames:
                merged.append({'ph': 'M', 'name': 'process_name',
                               'pid': new_pid,
                               'args': {'name': '%s: pid %s'
                                        % (label, orig_pid)}})
            merged.append({'ph': 'M', 'name': 'process_sort_index',
                           'pid': new_pid, 'args': {'sort_index': rank}})
        total += len(events)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, 'w') as fh:
        json.dump({'traceEvents': merged,
                   'metadata': {'merged_ranks': len(rank_dirs),
                                'source_events': total}}, fh)
    return out_path


def load_profiler_result(path):
    """List the trace artifacts produced under `path` (xplane.pb /
    trace.json.gz per host), for tooling that post-processes traces."""
    import os
    out = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(('.xplane.pb', '.trace.json.gz', '.json')):
                out.append(os.path.join(root, f))
    return sorted(out)
