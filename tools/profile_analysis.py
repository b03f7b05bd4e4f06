#!/usr/bin/env python
"""Roofline analysis of a captured TPU profile (jax.profiler trace).

Usage: python tools/profile_analysis.py [docs/tpu_profile_r4] [--top N]

Reads the newest `*.trace.json.gz` under the given profile dir (written
by jax.profiler.start_trace via PADDLE_TPU_BENCH_PROFILE) and prints, per XLA op aggregated over steps:

  - time/step, roofline-ideal time (max of flops/peak, bytes/bw), and
    the achieved fraction;
  - totals: program flops vs the 6N model, program HBM bytes, and
    whether the step is compute- or bandwidth-bound;
  - the top byte movers — the list that names the next fusion target
    (this is how the fused-CE and native-dtype-matmul levers were
    found; docs/profile_summary_r5.txt is such a report).

Peak numbers default to v5e (197 TFLOP/s bf16, 819 GB/s HBM); override
with --peak-tflops / --hbm-gbs for other TPU generations.

Reference counterpart: the op-benchmark harness family
(/root/reference/paddle/fluid/operators/benchmark/op_tester.cc) — this
is the XLA-profile-driven equivalent: measure the compiled program,
attribute time to ops, rank by headroom.
"""
import argparse
import collections
import glob
import gzip
import json
import os
import sys


def _read_json(path):
    """Chrome-trace JSON, gzip or plain, judged by content not suffix."""
    with open(path, 'rb') as f:
        magic = f.read(2)
    opener = gzip.open if magic == b'\x1f\x8b' else open
    with opener(path, 'rb') as f:
        return json.load(f)


def load_trace(profile_dir):
    """Newest trace under a profile dir — or a trace file given
    directly. Accepts the profiler's *.trace.json.gz and plain *.json
    Chrome traces (monitor.tracing.spans_to_chrome output), so the
    offline tools can join host-span dumps with device profiles."""
    if os.path.isfile(profile_dir):
        return _read_json(profile_dir), profile_dir
    paths = sorted(glob.glob(os.path.join(
        profile_dir, '**', '*.trace.json.gz'), recursive=True))
    if not paths:
        paths = sorted(p for p in glob.glob(os.path.join(
            profile_dir, '**', '*.json'), recursive=True)
            if p.endswith('.json') and 'trace' in os.path.basename(p))
    if not paths:
        raise SystemExit('no *.trace.json.gz (or *trace*.json) under %s'
                         % profile_dir)
    return _read_json(paths[-1]), paths[-1]


def device_ops(trace):
    """XLA-op duration events from the device pid's 'XLA Ops' lane."""
    tids = {}
    device_pids = set()
    for e in trace['traceEvents']:
        if e.get('ph') != 'M':
            continue
        if e.get('name') == 'process_name' and '/device:' in str(
                e.get('args', {}).get('name', '')):
            device_pids.add(e['pid'])
        if e.get('name') == 'thread_name':
            tids[(e['pid'], e['tid'])] = e['args'].get('name')
    ops, n_modules = [], 0
    for e in trace['traceEvents']:
        if e.get('ph') != 'X' or e['pid'] not in device_pids:
            continue
        lane = tids.get((e['pid'], e['tid']))
        if lane == 'XLA Ops':
            ops.append(e)
        elif lane == 'XLA Modules':
            n_modules += 1
    return ops, n_modules


def busy_us(ops):
    """Union of the device-op time intervals per (pid, tid), in us.

    A plain sum of durations double-counts nested ops (a while/scan op's
    slice covers its body ops, which appear as their own events), which
    inflated the r5 summary's 'on-chip op time' to ~2x the measured
    step. The interval union is the actual busy time."""
    lanes = {}
    for e in ops:
        lanes.setdefault((e['pid'], e.get('tid')), []).append(
            (float(e['ts']), float(e['ts']) + float(e['dur'])))
    total = 0.0
    for spans in lanes.values():
        spans.sort()
        cur_s, cur_e = spans[0]
        for s, t in spans[1:]:
            if s > cur_e:
                total += cur_e - cur_s
                cur_s, cur_e = s, t
            else:
                cur_e = max(cur_e, t)
        total += cur_e - cur_s
    return total


def aggregate(ops):
    rows = {}
    for e in ops:
        a = e.get('args', {})
        r = rows.setdefault(e['name'], dict(
            dur_us=0.0, n=0,
            flops=float(a.get('model_flops', 0) or 0),
            bytes=float(a.get('bytes_accessed', 0) or 0),
            cat=a.get('hlo_category', ''),
            ln=a.get('long_name', '')))
        r['dur_us'] += e['dur']
        r['n'] += 1
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('profile_dir', nargs='?', default='docs/tpu_profile_r4')
    ap.add_argument('--top', type=int, default=15)
    ap.add_argument('--steps', type=int, default=0,
                    help='profiled steps (default: inferred from the '
                         'most-frequent op count)')
    ap.add_argument('--peak-tflops', type=float, default=197.0)
    ap.add_argument('--hbm-gbs', type=float, default=819.0)
    ap.add_argument('--model-gflops', type=float, default=0.0,
                    help='model flops per step (e.g. 6N*batch*seq) for '
                         'the MFU line')
    args = ap.parse_args()

    trace, path = load_trace(args.profile_dir)
    ops, n_modules = device_ops(trace)
    if not ops:
        raise SystemExit('no device XLA-op events in %s' % path)
    rows = aggregate(ops)

    steps = args.steps
    if not steps:
        # each per-step op repeats once per step; the modal count is
        # robust against setup/one-off modules in the same trace
        counts = collections.Counter(r['n'] for r in rows.values())
        steps = counts.most_common(1)[0][0]
    peak = args.peak_tflops * 1e12
    bw = args.hbm_gbs * 1e9

    tot_ms = busy_us(ops) / 1e3 / steps
    tot_flops = sum(r['flops'] * r['n'] for r in rows.values()) / steps
    tot_bytes = sum(r['bytes'] * r['n'] for r in rows.values()) / steps
    print('trace: %s' % path)
    print('steps inferred: %d   on-chip busy time: %.1f ms/step '
          '(interval union; nested ops not double-counted)' %
          (steps, tot_ms))
    print('program flops/step: %.3e  -> %.1f ms at %.0f TFLOP/s' %
          (tot_flops, tot_flops / peak * 1e3, args.peak_tflops))
    print('program bytes/step: %.3e  -> %.1f ms at %.0f GB/s' %
          (tot_bytes, tot_bytes / bw * 1e3, args.hbm_gbs))
    bound = 'BANDWIDTH' if tot_bytes / bw > tot_flops / peak else 'COMPUTE'
    print('the step is %s-bound; achieved %.0f GB/s, %.1f TFLOP/s' %
          (bound, tot_bytes / (tot_ms / 1e3) / 1e9,
           tot_flops / (tot_ms / 1e3) / 1e12))
    if args.model_gflops:
        print('MFU vs --model-gflops: %.1f%%' %
              (100 * args.model_gflops * 1e9 / (tot_ms / 1e3) / peak))

    print('\ntop %d ops by time:' % args.top)
    print('%-40s %7s %7s %5s  %s' % ('op', 'ms/st', 'ideal', 'eff', 'category'))
    for k, r in sorted(rows.items(), key=lambda kv: -kv[1]['dur_us'])[:args.top]:
        ms = r['dur_us'] / 1e3 / steps
        ideal = max(r['flops'] / peak, r['bytes'] / bw) * 1e3
        eff = (ideal / ms * 100) if ms else 0
        print('%-40s %7.2f %7.2f %4.0f%%  %s' % (k[:40], ms, ideal, eff,
                                                 r['cat'][:24]))

    print('\ntop %d byte movers (the fusion-target list):' % args.top)
    for k, r in sorted(rows.items(),
                       key=lambda kv: -kv[1]['bytes'] * kv[1]['n'])[:args.top]:
        gb = r['bytes'] * r['n'] / steps / 1e9
        print('%-40s %6.2f GB/step  %s' % (k[:40], gb, r['ln'][:80]))
    return 0


if __name__ == '__main__':
    sys.exit(main())
