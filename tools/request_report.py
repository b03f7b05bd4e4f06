"""Request-centric report over wide-event logs (monitor/events.py).

Input is the canonical per-request record stream, from either side of
the serving stack:

    --jsonl  FILE        a RequestLog sink (one JSON event per line);
    --text   FILE|-      captured driver/bench output containing
                         `request_event(N)[tag]: {json}` lines (the
                         dryrun surface), or stdin.

Both may repeat; events concatenate. The report:

  * top-N slowest requests (by TTFT, falling back to total latency when
    a request never produced a token), each with the trace_id to pull
    from tail retention / the /requests route;
  * per-tenant rollups — requests, tokens, TTFT p50/p99, summed KV
    page·seconds — the attribution table "which tenant held the pool";
  * optional joins: --flight-dump / --chrome-trace files are scanned
    for span trace_ids so each slow request shows whether its span tree
    was actually retained somewhere on disk.

Gate mode (tools/gate_common protocol, like check_bench_regression):

  * --slo-ms X       : any request whose TTFT exceeds X ms is a finding;
  * --kv-integral X  : the per-request kv_page_seconds must sum to the
    allocator's pool-occupancy integral X within --kv-tol relative
    error (prefix sharing legitimately exceeds it — pass the pool's
    integral only when sharing is off). Mismatch is a finding.

No events -> exit 2; findings -> exit 1; otherwise 0 with a summary.
"""
import argparse
import json
import os
import sys
import types

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# monitor/ is stdlib-only but the package __init__ pulls in jax: load
# the subpackage without executing the parent (check_metrics_snapshot's
# pattern).
if 'paddle_tpu' not in sys.modules:
    _pkg = types.ModuleType('paddle_tpu')
    _pkg.__path__ = [os.path.join(_REPO_ROOT, 'paddle_tpu')]
    sys.modules['paddle_tpu'] = _pkg

from paddle_tpu.monitor.events import (FIELD_NAMES,  # noqa: E402
                                       parse_event_lines)
from tools import gate_common  # noqa: E402

__all__ = ['load_events', 'rollup_by_tenant', 'rollup_by_model',
           'slowest', 'check', 'main']


def _percentile(values, q):
    """serving.metrics.percentile re-stated (that module sits behind the
    jax-importing serving package): linear interpolation, numpy-free."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def load_events(jsonl_paths=(), texts=()):
    """Wide events from sink files and/or captured text, in input order.
    Lines that don't parse (torn writes, interleaved logs) are skipped
    and counted, never fatal."""
    events, skipped = [], 0
    for path in jsonl_paths:
        with open(path, errors='replace') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    skipped += 1
                    continue
                if isinstance(ev, dict) and 'request_id' in ev:
                    events.append(ev)
                else:
                    skipped += 1
    for text in texts:
        events.extend(ev for _, ev in parse_event_lines(text))
    return events, skipped


def _ttft_s(ev):
    a, f = ev.get('arrival_t'), ev.get('first_token_t')
    if a is None or f is None:
        return None
    return f - a


def _latency_s(ev):
    a, f = ev.get('arrival_t'), ev.get('finish_t')
    if a is None or f is None:
        return None
    return f - a


def slowest(events, n=10):
    """Top-n by TTFT (total latency when no token was ever produced),
    newest-schema fields only — unknown keys ride along untouched."""
    def key(ev):
        t = _ttft_s(ev)
        return t if t is not None else (_latency_s(ev) or 0.0)
    ranked = sorted(events, key=key, reverse=True)[:n]
    return [{'request_id': ev.get('request_id'),
             'tenant': ev.get('tenant'),
             'trace_id': ev.get('trace_id'),
             'ttft_ms': None if _ttft_s(ev) is None
             else _ttft_s(ev) * 1e3,
             'latency_ms': None if _latency_s(ev) is None
             else _latency_s(ev) * 1e3,
             'failovers': ev.get('failovers'),
             'outcome': ev.get('outcome')} for ev in ranked]


def rollup_by_tenant(events):
    """{tenant: {requests, tokens, ttft_p50_ms, ttft_p99_ms,
    kv_page_seconds, failovers, rejected, preempted, errors}} — the
    attribution table. QoS outcomes get their own columns: a shed
    request (outcome='rejected') or a preemption-budget kill
    (outcome='preempted') is policy doing its job, not an engine error,
    and capacity review needs them countable per tenant."""
    by = {}
    for ev in events:
        t = ev.get('tenant') or 'default'
        row = by.setdefault(t, {'requests': 0, 'tokens': 0,
                                'kv_page_seconds': 0.0, 'failovers': 0,
                                'rejected': 0, 'preempted': 0,
                                'errors': 0, '_ttfts': []})
        row['requests'] += 1
        row['tokens'] += int(ev.get('output_tokens') or 0)
        row['kv_page_seconds'] += float(ev.get('kv_page_seconds') or 0.0)
        row['failovers'] += int(ev.get('failovers') or 0)
        outcome = ev.get('outcome')
        if outcome == 'rejected':
            row['rejected'] += 1
        elif outcome == 'preempted':
            row['preempted'] += 1
        elif outcome not in (None, 'ok'):
            row['errors'] += 1
        ttft = _ttft_s(ev)
        if ttft is not None:
            row['_ttfts'].append(ttft)
    for row in by.values():
        ttfts = row.pop('_ttfts')
        row['ttft_p50_ms'] = (None if not ttfts
                              else _percentile(ttfts, 50) * 1e3)
        row['ttft_p99_ms'] = (None if not ttfts
                              else _percentile(ttfts, 99) * 1e3)
    return by


def rollup_by_model(events):
    """{model: {requests, tokens, ttft_p50_ms, ttft_p99_ms, failovers,
    rejected, errors}} — the multi-model attribution table. Events
    without a model field (single-model deployments, pre-schema logs)
    fold under '(none)': they are unattributed, not a named model."""
    by = {}
    for ev in events:
        m = ev.get('model') or '(none)'
        row = by.setdefault(m, {'requests': 0, 'tokens': 0,
                                'failovers': 0, 'rejected': 0,
                                'errors': 0, '_ttfts': []})
        row['requests'] += 1
        row['tokens'] += int(ev.get('output_tokens') or 0)
        row['failovers'] += int(ev.get('failovers') or 0)
        outcome = ev.get('outcome')
        if outcome == 'rejected':
            row['rejected'] += 1
        elif outcome not in (None, 'ok', 'preempted'):
            row['errors'] += 1
        ttft = _ttft_s(ev)
        if ttft is not None:
            row['_ttfts'].append(ttft)
    for row in by.values():
        ttfts = row.pop('_ttfts')
        row['ttft_p50_ms'] = (None if not ttfts
                              else _percentile(ttfts, 50) * 1e3)
        row['ttft_p99_ms'] = (None if not ttfts
                              else _percentile(ttfts, 99) * 1e3)
    return by


def _trace_ids_in_file(path):
    """Every trace_id mentioned in a flight dump ({'spans': [...]}) or a
    Chrome trace ({'traceEvents': [...]}, ids under args)."""
    with open(path, errors='replace') as f:
        try:
            doc = json.load(f)
        except ValueError:
            return set()
    ids = set()
    for span in doc.get('spans') or ():
        if span.get('trace_id'):
            ids.add(span['trace_id'])
    for ev in doc.get('traceEvents') or ():
        tid = (ev.get('args') or {}).get('trace_id')
        if tid:
            ids.add(tid)
    return ids


def check(events, slo_ms=None, kv_integral=None, kv_tol=1e-6):
    """Pure gate: findings list (empty == pass)."""
    findings = []
    if slo_ms is not None:
        for ev in events:
            ttft = _ttft_s(ev)
            if ttft is not None and ttft * 1e3 > slo_ms:
                findings.append({
                    'problem': 'ttft_over_slo',
                    'request_id': ev.get('request_id'),
                    'tenant': ev.get('tenant'),
                    'trace_id': ev.get('trace_id'),
                    'ttft_ms': ttft * 1e3, 'slo_ms': slo_ms})
    if kv_integral is not None:
        total = sum(float(ev.get('kv_page_seconds') or 0.0)
                    for ev in events)
        denom = max(abs(kv_integral), 1e-12)
        if abs(total - kv_integral) / denom > kv_tol:
            findings.append({
                'problem': 'kv_attribution_mismatch',
                'sum_per_request': total,
                'pool_integral': kv_integral,
                'relative_error': abs(total - kv_integral) / denom,
                'note': 'per-request kv_page_seconds must sum to the '
                        'allocator pool-occupancy integral (slot '
                        'engine: exact; paged + prefix sharing may '
                        'legitimately exceed — do not gate that case)'})
    return findings


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--jsonl', action='append', default=[],
                    help='RequestLog JSONL sink (repeatable)')
    ap.add_argument('--text', action='append', default=[],
                    help="driver/bench capture with request_event "
                         "lines, or '-' (repeatable)")
    ap.add_argument('--top', type=int, default=10,
                    help='slowest requests to list (default %(default)s)')
    ap.add_argument('--tenant', help='restrict the report to one tenant')
    ap.add_argument('--model', help='restrict the report to one model')
    ap.add_argument('--flight-dump', action='append', default=[],
                    help='flight-recorder dump JSON to join by trace_id')
    ap.add_argument('--chrome-trace', action='append', default=[],
                    help='Chrome-trace JSON to join by trace_id')
    ap.add_argument('--slo-ms', type=float,
                    help='gate: fail on any TTFT over this many ms')
    ap.add_argument('--kv-integral', type=float,
                    help='gate: allocator pool-occupancy integral the '
                         'per-request kv_page_seconds must sum to')
    ap.add_argument('--kv-tol', type=float, default=1e-6,
                    help='relative tolerance for --kv-integral '
                         '(default %(default)s)')
    args = ap.parse_args(argv)

    texts = []
    for t in args.text:
        texts.append(sys.stdin.read() if t == '-'
                     else open(t, errors='replace').read())
    events, skipped = load_events(args.jsonl, texts)
    if args.tenant:
        events = [e for e in events if e.get('tenant') == args.tenant]
    if args.model:
        events = [e for e in events if e.get('model') == args.model]
    if not events:
        return gate_common.nothing_to_check('no wide events found',
                                            skipped=skipped)

    known = set()
    for path in list(args.flight_dump) + list(args.chrome_trace):
        known |= _trace_ids_in_file(path)
    top = slowest(events, args.top)
    if known:
        for row in top:
            row['trace_on_disk'] = row['trace_id'] in known

    findings = check(events, slo_ms=args.slo_ms,
                     kv_integral=args.kv_integral, kv_tol=args.kv_tol)
    return gate_common.finish(findings, {
        'events': len(events), 'skipped_lines': skipped,
        'fields': list(FIELD_NAMES),
        'tenants': rollup_by_tenant(events),
        'models': rollup_by_model(events),
        'slowest': top,
        'joined_trace_ids': len(known)})


if __name__ == '__main__':
    sys.exit(main())
