"""One traced run of a cell, read through the PROGRAM's own names: the
device's idle gaps put on the innermost program span (`serving.*`,
`train.*`) the xplane's host plane holds, the device's self time by the
`jax.named_scope` of each operation, and how the program's step spans
make up the step the benchmark times from outside. Run on the chip:

    python benchmarks/tools/span_gaps.py --workload serve-xl.prefix-turns \
        --seed 11 --seconds 45 --out chiprun_out/gaps

It drives `run.run_cell` as `run.py` does and edits nothing of it: the
trace is taken where `run_cell` loads it (it deletes the file after),
the gaps are `benchlib.trace.reduce_trace`'s with the program's spans
handed to it under the prefix it looks for. Where the scope of an
operation lives in the xplane is not assumed: every string statistic of
an `XLA Ops` event and of its metadata is searched (`tf_op` first: on a
v5e it holds jax's name stack), then the HLO protos of the
`/host:metadata` plane (instruction -> `metadata.op_name`); the output
names the place that answered. An executable loaded from a compile cache
entry that an older tree wrote carries that tree's names: the cache's key
leaves metadata out, so read scopes from a run that compiled. The xplane's own protobuf comes
with tensorflow here; without it the scopes are left out.
"""
import argparse
import collections
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for _p in (BENCH, REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

PROGRAM_PREFIXES = ('serving.', 'train.')
NO_SPAN = 'no program span'
# the closed set of docs/observability.md; the innermost (last) match
# wins. Spelled out: a loose pattern also finds `gpt.py` in a source path
# and `gpt.wte.weight` in a parameter's name
SCOPES = ('gpt.embed', 'gpt.ln', 'gpt.attn.qkv', 'gpt.attn.paged_write',
          'gpt.attn.paged_gather', 'gpt.attn.mask', 'gpt.attn.core',
          'gpt.attn.out', 'gpt.mlp', 'gpt.lm_head', 'gpt.loss',
          'serving.pick_token', 'flash.fwd', 'flash.bwd')
SCOPE = re.compile('|'.join(re.escape(n) for n in SCOPES)
                   + r'|optimizer\.[a-z0-9_]+')
# the statistic that answered on a v5e (event METADATA, not the event)
OP_NAME_STAT = 'tf_op'
HLO_PROTO_STAT = 'Hlo Proto'


def program_gaps(trace):
    """{program span or NO_SPAN: idle seconds}: `reduce_trace`'s gaps
    with the program's host spans in the place of the benchmark's."""
    from benchlib import trace as T
    planes = []
    for plane in trace['planes']:
        if T.DEVICE_PLANE.match(plane['name']):
            planes.append(plane)
            continue
        lines = []
        for line in plane['lines']:
            events = [[T.SPAN_PREFIX + n, s, d] if n.startswith(
                PROGRAM_PREFIXES) else [n, s, d] for n, s, d in line['events']
                if n.startswith(PROGRAM_PREFIXES) or n == T.WINDOW_SPAN]
            if events:
                lines.append({'name': line['name'], 'events': events})
        if lines:
            planes.append({'name': plane['name'], 'lines': lines})
    red = T.reduce_trace({'planes': planes})
    gaps = {}
    for name, secs in red['gaps'].items():
        # every `bench.` name left is one of the program's, renamed
        key = name[len(T.SPAN_PREFIX):] if name.startswith(
            T.SPAN_PREFIX) else NO_SPAN
        gaps[key] = gaps.get(key, 0.0) + secs
    idle = sum(gaps.values())
    return {'idle_s': idle, 'window_s': red['window_s'],
            'gaps': dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
            'on_program_spans_share': 100.0 * (idle - gaps.get(
                NO_SPAN, 0.0)) / idle if idle else None}


def host_span_names(trace):
    """How many events of each program name the host planes hold."""
    from benchlib import trace as T
    names = collections.Counter()
    for plane in trace['planes']:
        if not T.DEVICE_PLANE.match(plane['name']):
            for line in plane['lines']:
                names.update(n for n, _, _ in line['events']
                             if n.startswith(PROGRAM_PREFIXES))
    return dict(names)


def scope_of(text):
    found = SCOPE.findall(text or '')
    return found[-1] if found else None


def _stat_value(plane, stat):
    which = stat.WhichOneof('value')
    if which == 'str_value':
        return stat.str_value
    if which == 'ref_value':
        return plane.stat_metadata[stat.ref_value].name
    return None


def hlo_scopes(space):
    """{module name: {instruction name: op_name}} from the HLO protos
    the `/host:metadata` plane carries."""
    from tensorflow.compiler.xla.service import hlo_pb2
    out = {}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            for stat in md.stats:
                if names.get(stat.metadata_id) != HLO_PROTO_STAT:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                table = out.setdefault(proto.hlo_module.name, {})
                for comp in proto.hlo_module.computations:
                    for ins in comp.instructions:
                        if ins.metadata.op_name:
                            table[ins.name] = ins.metadata.op_name
    return out


def scope_seconds(xplane_path, window):
    """Device self seconds by named scope inside `window` (ns), first
    TPU plane, and where the scope was read from."""
    from benchlib import trace as T
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(xplane_path, 'rb') as f:
        space.ParseFromString(f.read())
    plane = next((p for p in space.planes
                  if T.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return None
    lo, hi = window
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    modules, ops = [], []
    for line in plane.lines:
        base = line.timestamp_ns
        for ev in line.events:
            md = plane.event_metadata[ev.metadata_id]
            start = base + ev.offset_ps // 1000
            rec = (md.name, start, start + ev.duration_ps // 1000, ev, md)
            if line.name == T.MODULES_LINE:
                modules.append(rec)
            elif line.name == T.OPS_LINE and lo <= start < hi:
                ops.append(rec)
    stat_hits = collections.Counter()
    in_stats = {}
    for name, _, _, ev, md in ops:
        if name in in_stats:
            continue
        in_stats[name] = None
        stats = sorted(list(ev.stats) + list(md.stats), key=lambda st:
                       names.get(st.metadata_id) != OP_NAME_STAT)
        for stat in stats:
            got = scope_of(_stat_value(plane, stat))
            if got:
                in_stats[name] = got
                stat_hits[names.get(stat.metadata_id, '?')] += 1
                break
    by_module = None
    if not stat_hits:
        by_module = hlo_scopes(space)
    per_module = collections.defaultdict(list)
    modules.sort(key=lambda m: m[1])
    for name, start, end, _, _ in ops:
        owner = next((T.module_name(m[0]) for m in modules
                      if m[1] <= start < m[2]), '?')
        per_module[owner].append((name, start, end))
    seconds = collections.defaultdict(lambda: collections.defaultdict(float))
    for owner, events in per_module.items():
        table = None
        if by_module is not None:
            table = by_module.get('jit_' + owner) or by_module.get(owner) \
                or {}
        for name, secs in T.self_times(events):
            if table is None:
                scope = in_stats.get(name)
            else:
                ins = name.split(' = ', 1)[0].lstrip('%')
                scope = scope_of(table.get(ins))
            seconds[owner][scope or 'unscoped:' + T.op_name(name)] += secs
    return {'read_from': dict(stat_hits) if stat_hits
            else 'HLO proto of the /host:metadata plane, metadata.op_name',
            'event_stat_names': sorted(set(names.values()))[:40],
            'by_program': {m: dict(sorted(t.items(), key=lambda kv: -kv[1])
                                   [:16]) for m, t in seconds.items()}}


def step_summary(obs):
    """The program's step spans beside the benchmark's outside steps,
    and a first token's wait split at admission."""
    from benchlib import program_spans as P
    from benchlib.stats import percentile
    got = P.ring()
    out = {'ring': None if got is None else
           {'spans': len(got[0]), 'dropped': got[1]}}
    if obs.get('kind') == 'train':
        spans = [s for s in (got[0] if got else ())
                 if s['name'] == P.TRAIN_STEP][-obs['steps_done']:]
        out['train.step_ms_mean'] = 1e3 * sum(map(P.seconds, spans)) / max(
            len(spans), 1)
        out['outside_step_ms_mean'] = sum(obs['step_ms']) / len(
            obs['step_ms'])
        return out
    steps = P.window_spans(obs, P.STEP)
    if not steps:
        return out
    mean = lambda xs: sum(xs) / len(xs)
    kids = collections.defaultdict(list)
    for s in got[0]:
        kids[s.get('parent_id')].append(s)
    parts = collections.defaultdict(list)
    for st in steps:
        left = P.seconds(st)
        for name in (P.ADMIT, P.PREFILL, P.BURST):
            secs = sum(P.seconds(k) for k in kids[st['span_id']]
                       if k['name'] == name)
            parts[name].append(secs)
            left -= secs
        parts['self'].append(left)
        parts['cpu_s'].append(st['tags']['cpu_s'])
    outside = [te - ts for ts, te, *_ in obs['steps']]
    out['steps'] = len(steps)
    out['serving.step_ms_mean'] = 1e3 * mean([P.seconds(s) for s in steps])
    out['outside_step_ms_mean'] = 1e3 * mean(outside)
    out['parts_ms_mean'] = {k: 1e3 * mean(v) for k, v in parts.items()}
    calls = [k for st in steps for p in kids[st['span_id']]
             if p['name'] == P.PREFILL for k in kids[p['span_id']]]
    if calls:
        out['prefill_call_ms_mean'] = 1e3 * mean(
            [P.seconds(c) for c in calls])
        out['prefill_calls'] = len(calls)
    waits = [(1e3 * (ev['mono'] - s['start_mono']),
              1e3 * (first['mono'] - ev['mono']))
             for s, ev in P._admitted(obs)
             for first in [e for e in s['events']
                           if e['name'] == 'first_token'][:1]]
    if waits:
        # the program's arrival is the hand-over (the benchmark keeps
        # its own due-time stamps in this PR): the lag of the generator
        # and of the inbox is the difference to `ttft_p90_ms`
        out['arrival_to_admit_ms_p50_p90'] = [
            percentile([w[0] for w in waits], q) for q in (50, 90)]
        out['admit_to_first_token_ms_p50_p90'] = [
            percentile([w[1] for w in waits], q) for q in (50, 90)]
        out['arrival_to_first_token_ms_p90'] = percentile(
            [w[0] + w[1] for w in waits], 90)
    return out


def report(benchmark, root, workload, seed, seconds, require_chip=True):
    """Run the cell traced and read it; returns the report dict."""
    import run as bench_run
    from benchlib import trace as T
    taken = {}
    load = T.load_xplane

    def load_and_keep(path, keep_line=None):
        trace = load(path, keep_line)
        taken['trace'] = trace
        try:
            taken['scopes'] = scope_seconds(path, T.window_of(trace)) \
                if T.device_planes(trace) else None
        except ImportError as e:
            taken['scopes'] = 'not read: %s' % e
        return trace

    T.load_xplane = load_and_keep
    try:
        result, obs = bench_run.run_cell(benchmark, root, workload, seed,
                                         seconds, 1,
                                         require_chip=require_chip)
    finally:
        T.load_xplane = load
    trace = taken['trace']
    return {'workload': workload, 'seed': seed,
            'host_plane_spans': host_span_names(trace),
            'program_gaps': program_gaps(trace)
            if T.device_planes(trace) else None,
            'benchmark_gaps': (obs.get('reduced') or {}).get('gaps'),
            'scopes': taken.get('scopes'),
            'steps': step_summary(obs), 'result': result}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--out', default=os.path.join(REPO, 'chiprun_out',
                                                  'gaps'))
    args = ap.parse_args(argv)
    import run as bench_run
    benchmark = bench_run.load_json(os.path.join(REPO, 'BENCHMARK.json'))
    rep = report(benchmark, BENCH, args.workload, args.seed, args.seconds)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, '%s.seed%d.json' % (args.workload,
                                                      args.seed))
    with open(path, 'w') as f:
        json.dump(rep, f, indent=1)
    print(json.dumps({k: v for k, v in rep.items() if k != 'result'}))
    print(json.dumps(rep['result']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
