"""Find the knee of an open-loop cell once, on the chip: run the cell at
several fixed rates and print what each read. The rate a cell then runs
at is written into its traffic file by hand (0.6 of the knee for
`prefix-turns`); the benchmark itself never searches.

    python3 benchmarks/tools/sweep.py --workload serve-xl.prefix-turns \
        --rates 3,4.5,6,7.5 --seeds 1,2 --seconds 30

The knee is the highest swept rate at which the backlog does not grow
over the window: the requests still unfinished when the window closes
stay near rate x residency and the queue wait stays flat from the first
half of the window to the second.
"""
import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.getcwd())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seeds', default='1')
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)
    import run as bench_run
    from benchlib import stats
    from benchlib import traffic as traffic_mod
    benchmark = bench_run.load_json(os.path.join(bench_run.REPO,
                                                 'BENCHMARK.json'))
    cell = bench_run.find(benchmark['workloads'], args.workload, 'workload')
    base = traffic_mod.load(cell['traffic'], bench_run.HERE)
    root = tempfile.mkdtemp(prefix='bench_sweep_')
    os.makedirs(os.path.join(root, 'traffic'))
    try:
        for rate in (float(r) for r in args.rates.split(',')):
            cfg = copy.deepcopy(base)
            cfg['arrival']['rate_per_s'] = rate
            with open(os.path.join(root, 'traffic',
                                   cell['traffic'] + '.json'), 'w') as f:
                json.dump(cfg, f)
            for seed in (int(s) for s in args.seeds.split(',')):
                result, obs = bench_run.run_cell(
                    benchmark, root, args.workload, seed, args.seconds, 0)
                t0, t_end = obs['t0'], obs['t_end']
                mid = (t0 + t_end) / 2
                inw = [r for r in obs['recs'] if t0 <= r.due < t_end]
                open_at_close = sum(
                    1 for r in inw
                    if not r.finished or r.stamps.t[-1] > t_end)
                wait = lambda rs: stats.percentile(
                    [1e3 * (r.admit_t - r.due) for r in rs
                     if r.admit_t is not None], 90)
                steps = obs['steps']
                line = {
                    'rate': rate, 'seed': seed,
                    'requests': len(inw), 'failed': result['failed'],
                    'correct': result['correct'],
                    'ttft_p50_ms': stats.percentile(obs['ttft_ms'], 50),
                    'ttft_p90_ms': stats.percentile(obs['ttft_ms'], 90),
                    'ttft_max_ms': max(obs['ttft_ms']),
                    'tpot_p50_ms': stats.percentile(obs['tpot_ms'], 50),
                    'tpot_p90_ms': stats.percentile(obs['tpot_ms'], 90),
                    'queue_wait_p90_first_half_ms': wait(
                        [r for r in inw if r.due < mid]),
                    'queue_wait_p90_second_half_ms': wait(
                        [r for r in inw if r.due >= mid]),
                    'unfinished_at_close': open_at_close,
                    'occupancy_pct': 100.0 * sum(s[2] for s in steps) / (
                        len(steps) * obs['engine']['num_seqs']),
                    'step_ms_p50': stats.percentile(
                        [1e3 * (s[1] - s[0]) for s in steps], 50),
                    'tokens_per_s': obs['serve_tokens_per_s'],
                    'gen_lag_p99_ms': stats.percentile(obs['gen_lag_ms'],
                                                       99),
                    'logit_gap_max': result['compared'][
                        'logit_gap_max']['value'],
                }
                text = json.dumps(line)
                print(text, flush=True)
                if args.out:
                    with open(args.out, 'a') as f:
                        f.write(text + '\n')
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == '__main__':
    main()
