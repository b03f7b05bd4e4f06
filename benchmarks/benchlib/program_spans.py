"""What the per-layer readers take from the PROGRAM's own spans: the
flight ring of `paddle_tpu.monitor.tracing.default_tracer()`, read after
the run and windowed on the spans' monotonic stamps (`start_mono` /
`end_mono`, the clock the serving driver windows on too).

Which spans belong to a run:

  serving   an engine step belongs to the window when it STARTS in
            [obs['t0'], obs['t_end']), as `serve._account` rules; a
            request when it was admitted in it;
  joined    the device-joined shares take the spans that lie wholly in
            [obs['trace_t0'], obs['trace_t1']], where `reduced['modules']`
            keeps the program runs that lie wholly in it;
  training  the last obs['steps_done'] `train.step` spans: the window's
            calls, without its first.

Every function returns None when there is nothing to read: the tracer is
off, the program has no such span (a parent commit of the PR that added
them: its spans carry no monotonic stamp), the ring dropped spans of the
window, or nothing matched. A share whose denominator is an honest zero
reads 0.0.
"""
from .stats import percentile

STEP = 'serving.step'
ADMIT = 'serving.step.admit'
PREFILL = 'serving.step.prefill'
BURST = 'serving.decode_burst'
REQUEST = 'serving.request'
TRAIN_STEP = 'train.step'


def ring():
    """(the finished spans that carry a monotonic stamp, oldest first;
    the count the ring has dropped), or None when the tracer is off or
    absent."""
    try:
        from paddle_tpu.monitor import tracing
    except ImportError:
        return None
    tracer = tracing.default_tracer()
    if not tracer.enabled:
        return None
    return [s for s in tracer.recorder.spans()
            if s.get('start_mono') is not None], tracer.recorder.dropped


def _since(lo):
    """The ring's spans when none that ended at `lo` or later can have
    been dropped. The ring evicts in the order spans finished; a test
    worker's ring holds earlier runs, so having wrapped is a fault only
    when the oldest span left is younger than the window's start."""
    got = ring()
    if got is None:
        return None
    spans, dropped = got
    if dropped and spans and spans[0]['end_mono'] >= lo:
        return None
    return spans


def window_spans(obs, name):
    """Spans `name` that start in the serving window."""
    if obs.get('kind') != 'serve':
        return None
    lo, hi = obs['t0'], obs['t_end']
    spans = _since(lo)
    if spans is None:
        return None
    return [s for s in spans
            if s['name'] == name and lo <= s['start_mono'] < hi] or None


def traced_spans(obs, name):
    """Spans `name` wholly inside the traced window."""
    lo, hi = obs.get('trace_t0'), obs.get('trace_t1')
    if lo is None or hi is None:
        return None
    spans = _since(lo)
    if spans is None:
        return None
    return [s for s in spans if s['name'] == name
            and lo <= s['start_mono'] and s['end_mono'] <= hi] or None


def seconds(span):
    return span['end_mono'] - span['start_mono']


def duration_ms_p50(obs, name):
    spans = window_spans(obs, name)
    return spans and percentile([1e3 * seconds(s) for s in spans], 50)


def step_self_ms_p50(obs):
    """An engine step minus the part its child spans cover: bookkeeping
    between the phases, metrics, the token hand-over after the burst."""
    steps = window_spans(obs, STEP)
    if not steps:
        return None
    covered = {}
    for s in _since(obs['t0']) or ():
        if s.get('parent_id'):
            covered[s['parent_id']] = covered.get(s['parent_id'], 0.0) \
                + seconds(s)
    return percentile([1e3 * (seconds(s) - covered.get(s['span_id'], 0.0))
                       for s in steps], 50)


def prefill_calls_per_step(obs):
    spans = window_spans(obs, PREFILL)
    return spans and sum(s['tags']['calls'] for s in spans) / len(spans)


def blocked_on_pages_share(obs):
    """Of the window's admit passes that left their head queued, those
    that left it for want of pages (the rest: for want of a slot)."""
    spans = window_spans(obs, ADMIT)
    if not spans:
        return None
    causes = [s['tags']['head_left'] for s in spans
              if s['tags']['head_left'] != 'none']
    return 100.0 * causes.count('pages') / len(causes) if causes else 0.0


def _admitted(obs):
    """(request span, its first `admitted` event) of the requests
    admitted in the window and since finished."""
    if obs.get('kind') != 'serve':
        return []
    lo, hi = obs['t0'], obs['t_end']
    out = []
    for s in _since(lo) or ():
        if s['name'] != REQUEST:
            continue
        ev = next((e for e in s['events'] if e['name'] == 'admitted'), None)
        if ev is not None and lo <= ev['mono'] < hi:
            out.append((s, ev))
    return out


def admit_to_first_token_ms_p90(obs):
    waits = []
    for s, ev in _admitted(obs):
        first = next((e for e in s['events'] if e['name'] == 'first_token'),
                     None)
        if first is not None:
            waits.append(1e3 * (first['mono'] - ev['mono']))
    return percentile(waits, 90) if waits else None


def admit_blocked_share(obs):
    """Requests that sat through at least one admit pass unadmitted, of
    the requests admitted in the window."""
    got = _admitted(obs)
    if not got:
        return None
    return 100.0 * sum(1 for _, ev in got
                       if ev['args'].get('blocked')) / len(got)


def idle_share(obs, span_name, program):
    """1 - device seconds of `program`'s runs over the seconds of the
    `span_name` spans that dispatched them, both wholly inside the
    traced window: the share of those spans the device stood idle."""
    red = obs.get('reduced')
    runs = red and red['modules'].get(program)
    spans = runs and traced_spans(obs, span_name)
    if not spans:
        return None
    host = sum(seconds(s) for s in spans)
    return 100.0 * (1.0 - sum(runs) / host) if host > 0 else None


def train_dispatch_ms_p50(obs):
    """The host's part of a training step: `TrainStep.__call__` from
    entry to the dispatch's return."""
    n = obs.get('steps_done')
    got = ring() if obs.get('kind') == 'train' and n else None
    if got is None:
        return None
    spans = [s for s in got[0] if s['name'] == TRAIN_STEP][-n:]
    if len(spans) < n:
        return None
    return percentile([1e3 * seconds(s) for s in spans], 50)
