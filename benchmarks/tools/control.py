"""Read the program's and the control's numbers, side by side, on the chip
at a cell's own size: the readings the limits in `configs/*.json` are set
from (PERF.md lists them). The benchmark's own runs never run this.

    python3 benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 12 [--control fp8] [--out chiprun_out/control.jsonl]

Each seed is one short run of the cell through `run.run_cell` with the
control switched on: beside the numbers compared it prints, under
`control.<what>.<number>`, what the reference reads when it is computed
in the next lower precision (and, for training, on half of the rows) and
put in the program's place. One process reads all the seeds, so that
only the first one compiles.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.getcwd())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=12.0)
    ap.add_argument('--control', default='fp8')
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)
    import run as bench_run
    benchmark = bench_run.load_json(os.path.join(bench_run.REPO,
                                                 'BENCHMARK.json'))
    control = None if args.control == 'none' else args.control
    for seed in (int(s) for s in args.seeds.split(',')):
        result, _ = bench_run.run_cell(
            benchmark, bench_run.HERE, args.workload, seed, args.seconds, 0,
            control=control)
        line = json.dumps({'workload': args.workload, 'seed': seed,
                           'correct': result['correct'],
                           'metrics': result['metrics'],
                           'compared': result['compared']})
        print(line, flush=True)
        if args.out:
            with open(args.out, 'a') as f:
                f.write(line + '\n')


if __name__ == '__main__':
    main()
