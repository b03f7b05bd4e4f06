"""Plant a fault in the program and read the numbers that decide
`correct`, on the chip at a cell's own size: the comparison has been
shown to fail for each fault a cell's mechanism can have. The
benchmark's own runs never run this.

    python3 benchmarks/tools/faults.py --workload <cell> --seed 7 \
        --seconds 12 --faults state_not_carried,padded_tail,beta_half \
        [--out chiprun_out/faults.jsonl]

Each fault is one short run of the cell through `run.run_cell`, in one
process, with one function of the program replaced while it runs:

  state_not_carried  the chunked delta rule starts every prefill call
                     from a zero state (the one-token recurrence of the
                     decode step is left alone)
  padded_tail        the padded tail of a prefill chunk, and a frozen
                     decode lane's token, enter the state: the gates are
                     not masked
  beta_half          the write strength is sigmoid(b), without the
                     factor 2 of `linear_allow_neg_eigval`
  none               nothing planted: the run must be `correct`
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.getcwd())


def _state_not_carried(mod):
    import jax.numpy as jnp
    rule = mod.chunked_delta_rule
    return {'chunked_delta_rule':
            lambda q, k, v, g, beta, state, *a: rule(
                q, k, v, g, beta, jnp.zeros_like(state), *a)}


def _padded_tail(mod):
    return {'_mask_gates': lambda g, beta, real: (g, beta)}


def _beta_half(mod):
    import jax
    return {'_beta': lambda b, allow_neg_eigval: jax.nn.sigmoid(b)}


# fault -> (the program's module, what to put in place of which names)
FAULTS = {
    'state_not_carried': ('paddle_tpu.text.models.olmo_hybrid',
                          _state_not_carried),
    'padded_tail': ('paddle_tpu.text.models.olmo_hybrid', _padded_tail),
    'beta_half': ('paddle_tpu.text.models.olmo_hybrid', _beta_half),
}


@contextlib.contextmanager
def planted(fault):
    """The program with `fault` in it ('none': as it is)."""
    if fault == 'none':
        yield
        return
    import importlib
    module, make = FAULTS[fault]
    mod = importlib.import_module(module)
    swap = make(mod)
    kept = {name: getattr(mod, name) for name in swap}
    for name, value in swap.items():
        setattr(mod, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(mod, name, value)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, default=12.0)
    ap.add_argument('--faults', required=True)
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)
    import run as bench_run
    benchmark = bench_run.load_json(os.path.join(bench_run.REPO,
                                                 'BENCHMARK.json'))
    for fault in args.faults.split(','):
        with planted(fault):
            result, _ = bench_run.run_cell(
                benchmark, bench_run.HERE, args.workload, args.seed,
                args.seconds, 0)
        line = json.dumps({'workload': args.workload, 'seed': args.seed,
                           'fault': fault, 'correct': result['correct'],
                           'compared': result['compared']})
        print(line, flush=True)
        if args.out:
            with open(args.out, 'a') as f:
                f.write(line + '\n')


if __name__ == '__main__':
    main()
