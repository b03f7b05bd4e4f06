"""Replay a backlog cell's schedule from its files alone, with no model
and no device: which engine step admits which request, how many prefill
calls and decoding rows each step carries, and at which step the queue
is empty. The benchmark's own runs never run this.

    python3 benchmarks/tools/replay.py --workload <cell> \
        [--num-pages N] [--repeats R] [--steps-log <file.json>]
    python3 benchmarks/tools/replay.py --workload <cell> \
        --record <seed> --out chiprun_out/steps.json        (on the chip)

It answers what a backlog's `repeats` has to be: the cell measures a
full engine only while requests are queued, so with the window opening
after `warmup_steps` engine steps and lasting `run_seconds`, the queue
must not be empty before the close. `dry_below_step_ms` is the engine
step under which it would be; keep it well under the step the chip
measures (a quarter, ISSUE 30), so that a later PR which shortens the
step still measures a full engine.

The rules are the paged engine's (`serving/scheduler.py:PagedScheduler`,
`serving/engine.py:_EngineBase.step`): first come, first served; a
request is admitted when a slot is free and the pool covers its whole
reservation, `ceil(max(prompt + output - 1, prompt rounded up to the
prefill chunk) / page_size)` pages (one page of the pool is scratch);
each step gives every prefilling resident one chunk, in a call of its
own, and every decoding resident `decode_block` tokens; the call that
ends a prompt yields the first token; a finished request frees its slot
and pages at the end of the step. Left out, and refused: arrivals other
than `backlog`, shared prefixes (a prefix hit shortens a reservation),
speculation.

`--steps-log` holds the replay to a run: a JSON object with the run's
`num_pages` and `steps`, [slots in use, pages in use] after each engine
step of the window (the benchmark samples both after every step).
`--record` makes such a file: one run of the cell through
`run.run_cell`, as the benchmark runs it. Exit code 1 and the first
mismatch if replay and log differ anywhere.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
REPO = os.path.dirname(ROOT)


def replay(prompts, outputs, num_seqs, num_pages, page_size, prefill_chunk,
           decode_block):
    """Lengths and engine sizes in, one dict per engine step out:
    `queued` (after admission), `calls`, `decoding`, `tokens` (delivered
    by the step), `slots` and `pages` in use after it."""
    chunk, page = prefill_chunk, page_size
    queue = list(range(len(prompts)))
    free = num_pages - 1                    # minus the scratch page
    resident = {}                           # i -> [consumed, made, pages]
    steps = []
    while queue or resident:
        while queue and len(resident) < num_seqs:
            i = queue[0]
            need = -(-max(prompts[i] + outputs[i] - 1,
                          -(-prompts[i] // chunk) * chunk) // page)
            if need > num_pages - 1:
                raise SystemExit('request %d needs %d pages and the pool '
                                 'has %d' % (i, need, num_pages - 1))
            if need > free:
                break
            free -= need
            resident[i] = [0, 0, need]
            queue.pop(0)
        calls = decoding = tokens = 0
        prefilling = [i for i, r in resident.items() if r[0] < prompts[i]]
        for i in prefilling:
            r = resident[i]
            r[0] = min(prompts[i], r[0] + chunk)
            calls += 1
            if r[0] == prompts[i]:          # the final chunk's pick
                r[1] = 1
                tokens += 1
        for i, r in list(resident.items()):
            if r[1] >= 1 and r[1] < outputs[i]:
                k = min(decode_block, outputs[i] - r[1])
                r[1] += k
                tokens += k
                decoding += 1
            if r[1] >= outputs[i]:
                free += r[2]
                del resident[i]
        steps.append({'queued': len(queue), 'calls': calls,
                      'decoding': decoding, 'tokens': tokens,
                      'slots': len(resident),
                      'pages': num_pages - 1 - free})
    return steps


def load_cell(workload, root=ROOT):
    """(engine section, traffic parameters, run_seconds) of a cell."""
    import run as bench_run
    from benchlib import traffic
    benchmark = bench_run.load_json(os.path.join(REPO, 'BENCHMARK.json'))
    cell = bench_run.find(benchmark['workloads'], workload, 'workload')
    entry = bench_run.find(benchmark['configs'], cell['config'], 'config')
    config = bench_run.load_json(os.path.join(REPO, entry['file']))
    return (config['engine'], traffic.load(cell['traffic'], root),
            benchmark['run_seconds'])


def replay_cell(engine, tcfg, num_pages=None, repeats=None):
    """The steps of a cell's whole job (the seed decides ids and
    weights, never a length or the order, when `order.seeded` is
    false)."""
    from benchlib import traffic
    arrival = tcfg['arrival']
    if arrival['process'] != 'backlog' or tcfg.get('prefix') or \
            engine.get('spec_k'):
        raise SystemExit('replay: a backlog without shared prefixes or '
                         'speculation only')
    if tcfg.get('order', {}).get('seeded', True):
        raise SystemExit('replay: the order is the seed\'s; give '
                         '"order": {"seeded": false} or replay each seed')
    tcfg = dict(tcfg, arrival=dict(arrival, repeats=int(
        repeats or arrival.get('repeats', 1))))
    trace = traffic.serve_trace(tcfg, 0, 0)
    return replay([len(p) for p in trace.prompts],
                  [int(o) for o in trace.outputs], engine['num_seqs'],
                  int(num_pages or engine['num_pages']),
                  engine['page_size'], engine['prefill_chunk'],
                  engine['decode_block'])


def summary(steps, warmup_steps, run_seconds):
    """What decides `repeats`, and the job's means while the queue
    holds requests."""
    full = next((i for i, s in enumerate(steps) if s['queued'] == 0),
                len(steps))
    held = steps[warmup_steps:full] or steps
    mean = lambda key: sum(s[key] for s in held) / len(held)  # noqa: E731
    return {
        'engine_steps': len(steps),
        'queue_empty_from_step': full,
        'dry_below_step_ms': 1e3 * run_seconds / max(1, full - warmup_steps),
        'residents_mean': mean('slots'),
        'prefill_calls_per_step': mean('calls'),
        'decoding_rows_per_step': mean('decoding'),
        'tokens_per_step': mean('tokens'),
        'pages_in_use_max': max(s['pages'] for s in steps),
    }


def first_mismatch(steps, warmup_steps, logged):
    """Index into `logged` ([slots, pages] after each step of the
    window) of the first step the replay has otherwise, or None."""
    for i, row in enumerate(logged):
        s = steps[warmup_steps + i]
        if [s['slots'], s['pages']] != list(row):
            return i
    return None


def record(workload, seed, seconds, engine, out):
    """One run of the cell, on the chip: its step log to `out`."""
    import run as bench_run
    benchmark = bench_run.load_json(os.path.join(REPO, 'BENCHMARK.json'))
    result, obs = bench_run.run_cell(benchmark, ROOT, workload, seed,
                                     seconds, 0)
    log = {'workload': workload, 'seed': seed, 'seconds': seconds,
           'device': result['device']['kind'],
           'num_pages': engine['num_pages'],
           'steps': [[s[2], s[3]] for s in obs['steps']]}
    with open(out, 'w') as f:
        json.dump(log, f)
    print(json.dumps({k: v for k, v in log.items() if k != 'steps'}))
    return 0 if result['correct'] else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--num-pages', type=int)
    ap.add_argument('--repeats', type=int)
    ap.add_argument('--steps-log')
    ap.add_argument('--record', type=int, metavar='SEED')
    ap.add_argument('--out')
    args = ap.parse_args(argv)
    engine, tcfg, run_seconds = load_cell(args.workload)
    if args.record is not None:
        return record(args.workload, args.record, run_seconds, engine,
                      args.out)
    logged = None
    if args.steps_log:
        with open(args.steps_log) as f:
            logged = json.load(f)
    steps = replay_cell(engine, tcfg,
                        args.num_pages or (logged or {}).get('num_pages'),
                        args.repeats)
    warm = int(tcfg['arrival'].get('warmup_steps', 0))
    out = summary(steps, warm, run_seconds)
    if logged:
        out['steps_compared'] = len(logged['steps'])
        out['first_mismatch'] = first_mismatch(steps, warm, logged['steps'])
    print(json.dumps(out))
    return 1 if out.get('first_mismatch') is not None else 0


if __name__ == '__main__':
    sys.exit(main())
