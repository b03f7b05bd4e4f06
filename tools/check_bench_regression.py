"""Perf-regression gate over bench capture logs.

Compares a NEW capture log (JSONL rows as written by bench.py or
bench_extra.py) against the stored best and
FAILS (exit 1) when any same-config metric regresses more than the
threshold (default 10%). Reference counterpart:
tools/check_op_benchmark_result.py, which gates op microbenchmark PRs
the same way — compare same-case logs, alarm past a ratio.

"Same config" means: same metric AND same effective replay environment.
Rows are canonicalized through bench._capture_replay_env +
bench._effective_env, so a legacy row with unstated knobs and a new row
spelling out today's defaults still land in the same bucket (the whole
point of those helpers), plus the auxiliary workload fields
(num_slots/new_tokens/... for the serving and decode rungs).

Only trustworthy rows participate: real-TPU, non-degraded, non-suspect,
no error field.

Usage:
    python tools/check_bench_regression.py --new NEW.jsonl \
        [--baseline BEST.jsonl ...] [--threshold 0.10]

With no --baseline, the repo's in-window logs (bench._inwindow_log_paths)
are the stored best. Exit codes: 0 ok, 1 regression, 2 nothing to check.
"""
import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tools import gate_common  # noqa: E402

# auxiliary config fields that distinguish otherwise same-env rows
# (bench_extra rungs vary these, not the knob env). The paged-serving
# rung adds page_size/spec_k/workload: a spec-on row must never land in
# a spec-off row's regression bucket. `tenant` keys the mixed-tenant
# gateway rung's per-tenant TTFT rows — premium and batch latencies are
# different contracts and must gate separately. `transport`/`n_procs`
# key the serving-fabric rung: in-proc and socket-transport rows are
# different regimes (the process boundary is the measured cost).
_AUX_CONFIG = ('replicas', 'kill_at', 'policy',
               'num_slots', 'new_tokens', 'prompt_len', 'image_size',
               'trace', 'model', 'n_models', 'swap_at', 'scan_steps',
               'page_size', 'spec_k', 'workload', 'tenant',
               'transport', 'n_procs')

__all__ = ['eligible', 'config_key', 'higher_is_better', 'expand_derived',
           'check', 'main']

# row fields that gate as first-class metrics of their own. Synthesized
# as pseudo-rows ('<metric>_compile_s_cold', unit 's') rather than added
# to _AUX_CONFIG: an aux field would bucket-split every existing config
# and orphan the stored bests. compile_cache_hit_rate (unit 'ratio')
# regresses DOWNWARD like throughput — a warmed persistent cache losing
# its hits is exactly the cold-start regression this column exists for.
_DERIVED_KEYS = ('compile_s_cold', 'compile_s_warm',
                 'compile_cache_hit_rate')
_DERIVED_UNITS = {'compile_cache_hit_rate': 'ratio'}


def eligible(row, trust_degraded=False):
    """The trust rule: real-TPU, clean, measured.
    `trust_degraded` relaxes the platform/degraded half — the
    compile-cache rungs are measured on CPU (XLA compile + persistent
    cache behave identically there) and gate via an explicit
    --trust-degraded invocation against their own committed baseline,
    never against the real-TPU bests."""
    if not (not row.get('suspect')
            and 'error' not in row
            and isinstance(row.get('value'), (int, float))
            and row.get('metric')):
        return False
    if trust_degraded:
        return True
    return row.get('platform', 'tpu') == 'tpu' and not row.get('degraded')


def config_key(row):
    """Canonical same-config identity for a capture row."""
    import bench
    env = bench._effective_env(bench._capture_replay_env(row))
    aux = tuple((k, row[k]) for k in _AUX_CONFIG if k in row)
    return (row['metric'],) + aux + tuple(sorted(env.items()))


def higher_is_better(row):
    """Throughput-style metrics regress DOWN; latency-style and
    compile-time metrics regress UP. hit_rate is checked first: cache
    hit rates are higher-is-better even though 'compile' is in the
    metric name."""
    text = '%s %s' % (row.get('metric', ''), row.get('unit', ''))
    if 'hit_rate' in text:
        return True
    if 'completed_ratio' in text:
        # QoS rung: premium requests finishing is the whole contract
        return True
    if 'shed_rate' in text:
        # QoS rung: more shedding on the same workload = policy or
        # capacity regression, even though shedding itself is by design
        return False
    if 'mttr' in text:
        # recovery time: a faster supervisor is a better supervisor
        return False
    if 'ttft' in text:
        # time-to-first-token (incl. the per-tenant columns): latency
        return False
    if 'divergence' in text or 'rel_err' in text:
        # sim-vs-real calibration error (capacity_sim_ttft_divergence):
        # a better-calibrated simulator diverges LESS
        return False
    if 'min_replicas' in text:
        # capacity answer: fewer replicas for the same SLO is better
        return False
    if 'data_wait' in text:
        # ingest rung: fraction of step wall blocked on input — the
        # number the async prefetcher exists to drive to zero
        return False
    if 'examples_per_sec' in text:
        # ingest throughput (explicit so a future unit rename can't
        # flip it into the latency default)
        return True
    return not ('ms' in text.split() or 'latency' in text
                or text.endswith('_ms') or 'compile' in text)


def expand_derived(rows):
    """rows + pseudo-rows for the derived gate keys: a row carrying
    compile_s_cold/compile_s_warm also gates those values under
    '<metric>_compile_s_cold' (unit 's'). mfu_est and the roofline
    fields stay informational — analytic estimates, not measurements."""
    out = list(rows)
    for row in rows:
        if not row.get('metric') or 'error' in row:
            continue
        for key in _DERIVED_KEYS:
            val = row.get(key)
            if isinstance(val, (int, float)):
                derived = dict(row)
                derived['metric'] = '%s_%s' % (row['metric'], key)
                derived['value'] = float(val)
                derived['unit'] = _DERIVED_UNITS.get(key, 's')
                out.append(derived)
    return out


def check(new_rows, baseline_rows, threshold=0.10, trust_degraded=False):
    """Pure gate: list of regression findings (empty == pass).

    For every config present in BOTH logs, the best new value must not
    be worse than the stored best by more than `threshold`. Configs only
    one side knows are skipped — a new rung has no best yet, and a
    retired rung must not block forever.
    """
    def best_by_config(rows):
        best = {}
        for row in rows:
            if not eligible(row, trust_degraded=trust_degraded):
                continue
            key = config_key(row)
            cur = best.get(key)
            if cur is None:
                best[key] = row
            elif higher_is_better(row) == (row['value'] > cur['value']):
                best[key] = row
        return best

    stored = best_by_config(expand_derived(baseline_rows))
    fresh = best_by_config(expand_derived(new_rows))
    findings = []
    for key, old in sorted(stored.items()):
        new = fresh.get(key)
        if new is None:
            continue
        hib = higher_is_better(old)
        ratio = (new['value'] / old['value']) if old['value'] else 1.0
        regressed = (ratio < 1.0 - threshold) if hib \
            else (ratio > 1.0 + threshold)
        if regressed:
            findings.append({
                'metric': old['metric'],
                'stored_best': old['value'],
                'new_best': new['value'],
                'ratio': round(ratio, 4),
                'threshold': threshold,
                'direction': 'down' if hib else 'up',
                'stored_label': old.get('label'),
                'new_label': new.get('label'),
            })
    return findings


def _load_jsonl(path):
    rows = []
    with open(path, errors='replace') as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--new', required=True, help='new capture JSONL')
    ap.add_argument('--baseline', action='append', default=[],
                    help='stored-best JSONL (repeatable; default: the '
                         'repo in-window logs)')
    ap.add_argument('--threshold', type=float, default=0.10,
                    help='allowed fractional regression (default 0.10)')
    ap.add_argument('--trust-degraded', action='store_true',
                    help='admit non-TPU/degraded rows (compile-cache CPU '
                         'rungs gating against their own baseline)')
    args = ap.parse_args(argv)

    baselines = args.baseline
    if not baselines:
        import bench
        baselines = [p for p in bench._inwindow_log_paths()
                     if os.path.exists(p)]
    new_rows = _load_jsonl(args.new)
    base_rows = [r for p in baselines for r in _load_jsonl(p)]
    if not new_rows or not base_rows:
        return gate_common.nothing_to_check(
            'nothing to compare (new=%d baseline=%d eligible rows '
            'pre-filter)' % (len(new_rows), len(base_rows)))
    findings = check(new_rows, base_rows, threshold=args.threshold,
                     trust_degraded=args.trust_degraded)
    return gate_common.finish(
        findings, {'regressions': 0, 'threshold': args.threshold})


if __name__ == '__main__':
    sys.exit(main())
