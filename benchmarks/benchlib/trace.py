"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, time per named operation, the runs
of each compiled program, and the idle gaps attributed to the benchmark's
own host spans (`jax.profiler.TraceAnnotation('bench.*')`).

The reduction works on a neutral form so that it can be checked on a
small recorded trace (`data/recorded_trace.json`):

    {'planes': [{'name': str,
                 'lines': [{'name': str,
                            'events': [[name, start_ns, duration_ns]]}]}]}

`load_xplane` builds that form from the `.xplane.pb` jax writes, with
`jax.profiler.ProfileData` and nothing else.
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
SPAN_PREFIX = 'bench.'
WINDOW_SPAN = 'bench.window'
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r'(\.\d+)+$')
_MODULE = re.compile(r'^(?:jit_)?(.*?)(?:\(\d+\))?$')


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not files:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return files[-1]


def load_xplane(path, keep_line=None):
    """The neutral form of one `.xplane.pb`. `keep_line(plane, line)`
    may drop lines that no metric reads (host threads without spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({'name': line.name, 'events': events})
        if lines:
            planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def load_json(path):
    opener = gzip.open if path.endswith('.gz') else open
    with opener(path, 'rt') as f:
        return json.load(f)


def op_name(name):
    """The operation without its serial. On a TPU an event of the `XLA
    Ops` line is named by its whole HLO text: `%fusion.123 = bf16[..]
    fusion(..)` -> `fusion`; a Pallas kernel (custom call to
    `tpu_custom_call`) is prefixed `tpu_custom_call:`."""
    base = _SUFFIX.sub('', name.split(' = ', 1)[0].lstrip('%'))
    if PALLAS_TARGET in name:
        return 'tpu_custom_call:' + base
    return base


def self_times(events):
    """[(name, self seconds)]: on the `XLA Ops` line a `while` or a
    `conditional` spans the operations of its body, so each event's time
    is taken without the events nested in it."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append((stack[-1][0], stack[-1][3]))
            stack.pop()
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out.extend((st[0], st[3]) for st in stack)
    return [(n, t / 1e9) for n, t in out]


def module_name(name):
    """`jit__decode_fn(1234)` -> `_decode_fn`."""
    return _MODULE.match(name).group(1)


def device_planes(trace):
    return [p for p in trace['planes'] if DEVICE_PLANE.match(p['name'])]


def _line(plane, name):
    for line in plane['lines']:
        if line['name'] == name:
            return line['events']
    return []


def host_spans(trace):
    """Every `bench.*` annotation on any host thread: [name, start, end]."""
    spans = []
    for plane in trace['planes']:
        if DEVICE_PLANE.match(plane['name']):
            continue
        for line in plane['lines']:
            for name, start, dur in line['events']:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    return spans


def window_of(trace):
    """(start_ns, end_ns) of the traced window: the `bench.window` span,
    or else the extent of the device operations."""
    for name, start, end in host_spans(trace):
        if name == WINDOW_SPAN:
            return start, end
    starts, ends = [], []
    for plane in device_planes(trace):
        for _, start, dur in _line(plane, OPS_LINE):
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        raise ValueError('the trace holds no device operation')
    return min(starts), max(ends)


def merged(intervals):
    """Sorted, overlapping intervals merged: the union as a list."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _clipped(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def reduce_trace(trace, min_gap_ns=20000):
    """The numbers every cell reads from a trace:

      window_s   the traced window
      busy_s     seconds an operation ran, union of intervals, averaged
                 over the device planes
      chips      device planes seen
      ops        {operation: self seconds}, summed over the chips
      modules    {program: [seconds of each run that lies wholly inside
                 the window]} on the first chip
      gaps       {host span or 'no bench span': idle seconds}, from the
                 first chip's gaps of `min_gap_ns` or longer, each put on
                 the innermost `bench.*` span that covers its middle
    """
    lo, hi = window_of(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError('the trace holds no TPU device plane')
    spans = [s for s in host_spans(trace) if s[0] != WINDOW_SPAN]
    busy, ops, gaps, modules = [], {}, {}, {}
    for idx, plane in enumerate(planes):
        clipped = list(_clipped(_line(plane, OPS_LINE), lo, hi))
        for name, secs in self_times(clipped):
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + secs
        union = merged((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in union) / 1e9)
        if idx:
            continue
        for name, s, dur in _line(plane, MODULES_LINE):
            if lo <= s and s + dur <= hi:       # whole runs only
                modules.setdefault(module_name(name), []).append(dur / 1e9)
        edges = [lo] + [x for pair in union for x in pair] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 < min_gap_ns:
                continue
            mid = (g0 + g1) // 2
            cover = [s for s in spans if s[1] <= mid < s[2]]
            owner = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
                else 'no bench span'
            gaps[owner] = gaps.get(owner, 0.0) + (g1 - g0) / 1e9
    return {'window_s': (hi - lo) / 1e9,
            'busy_s': sum(busy) / len(busy), 'chips': len(planes),
            'ops': ops, 'modules': modules, 'gaps': gaps}


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def breakdown(reduced):
    return {'device_ops': top(reduced['ops']),
            'idle_gaps': top(reduced['gaps'])}


def ops_matching(reduced, needle):
    """Seconds of the operations whose name contains `needle`."""
    return sum(v for k, v in reduced['ops'].items() if needle in k)
