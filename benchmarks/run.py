"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Everything that belongs to one cell is data found by name from
`BENCHMARK.json`: `configs/<config>.json`, `traffic/<traffic>.json` and,
for each per-layer metric, the reader `metrics/<metric>.py`. This file
and `benchlib/` are the general part: a later PR adds a configuration, a
traffic mix, a per-layer metric or a cell by adding files and appending
entries to `BENCHMARK.json`, and edits nothing here.

It needs a TPU with as many chips as the cell asks for: otherwise it
exits non-zero and prints no result. The last line of standard output is
the result, one JSON object; the numbers that decided `correct` are its
last key and the last lines of standard error.
"""
import time

T_PROCESS = time.time()          # set-up runs from here to the window

import argparse                  # noqa: E402
import importlib.util            # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import shutil                    # noqa: E402
import sys                       # noqa: E402
import tempfile                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for _p in (HERE, REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling or loading from
    the persistent cache (the arithmetic of `chip_smoke.CompileClock`)."""

    EVENTS = ('/jax/core/compile/jaxpr_trace_duration',
              '/jax/core/compile/jaxpr_to_mlir_module_duration',
              '/jax/core/compile/backend_compile_duration')

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e['name'] == name:
            return e
    raise SystemExit('%s %r is not in BENCHMARK.json' % (what, name))


def load_reader(name):
    path = os.path.join(HERE, 'metrics', name + '.py')
    spec = importlib.util.spec_from_file_location('bench_metric_' + name.replace(
        '.', '_').replace('-', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reports(metric, workload):
    cells = metric.get('workloads')
    return cells is None or workload in cells


def run_cell(benchmark, root, workload, seed, seconds, trace,
             require_chip=True, t_process=None, control=None):
    """One run of one cell; returns (result dict, observations)."""
    import jax
    t_process = time.time() if t_process is None else t_process
    cell = find(benchmark['workloads'], workload, 'workload')
    config_entry = find(benchmark['configs'], cell['config'], 'config')
    config = load_json(os.path.join(REPO, config_entry['file']))
    from benchlib import peaks, serve, train
    from benchlib import trace as trace_mod
    from benchlib import traffic as traffic_mod
    tcfg = traffic_mod.load(cell['traffic'], root)

    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != 'tpu':
            raise SystemExit('the benchmark measures on a TPU and jax found '
                             '%s (%s): no result' % (dev.platform,
                                                     dev.device_kind))
        if len(devices) < cell['chips']:
            raise SystemExit('cell %s asks for %d chips and jax found %d'
                             % (workload, cell['chips'], len(devices)))
        chip_peaks = peaks.peaks_of(dev.device_kind)
    else:
        chip_peaks = peaks.TPU_PEAKS['TPU v5 lite']   # rehearsal: counts only

    from paddle_tpu.framework import compile_cache
    if require_chip:             # a rehearsal leaves the process's cache be
        compile_cache.configure()
    cc = CompileClock()
    marks = {}

    def window_opened():         # set-up ends where the window starts
        marks['setup_s'] = time.time() - t_process
        marks['compile_s'] = cc.seconds
        marks['misses'] = compile_cache.stats()['misses']
        marks['programs_at_open'] = cc.programs

    def window_closed():
        marks['programs_at_close'] = cc.programs

    def memory_peak():
        peak = 0
        for d in devices[:cell['chips']]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get('peak_bytes_in_use', 0)))
        return peak

    trace_root = []

    def start_trace():
        d = tempfile.mkdtemp(prefix='bench_trace_')
        trace_root.append(d)
        jax.profiler.start_trace(d)
        return d

    def stop_trace(d):
        jax.profiler.stop_trace()
        return d

    env = {'config': config, 'traffic': tcfg, 'seed': int(seed),
           'seconds': float(seconds), 'trace': bool(trace),
           'peaks': chip_peaks, 'window_opened': window_opened,
           'window_closed': window_closed, 'memory_peak': memory_peak,
           'start_trace': start_trace, 'stop_trace': stop_trace}
    env.update(tcfg.get('trace', {}))
    if control:
        env['control'] = control
    driver = {'serve': serve.run, 'train': train.run}[config['kind']]
    try:
        obs = driver(env)
        cc.close()
        obs['setup_s'] = marks['setup_s']
        obs['compile_s'] = marks['compile_s']
        obs['compile_cache_misses'] = marks['misses']
        obs['recompiles_in_window'] = marks['programs_at_close'] \
            - marks['programs_at_open']
        obs['peaks'] = chip_peaks
        obs['chips'] = cell['chips']
        reduced = None
        if trace:
            loaded = trace_mod.load_xplane(
                trace_mod.find_xplane(obs['trace_dir']))
            # a CPU rehearsal has no device plane: nothing to reduce
            if require_chip or trace_mod.device_planes(loaded):
                reduced = trace_mod.reduce_trace(loaded)
        obs['reduced'] = reduced
    finally:
        for d in trace_root:
            shutil.rmtree(d, ignore_errors=True)

    metrics = {}
    if trace:
        for metric in benchmark['per_layer']:
            if not reports(metric, workload):
                continue
            value = load_reader(metric['name'])(obs)
            if value is not None:
                metrics[metric['name']] = {'value': float(value),
                                           'unit': metric['unit']}
    else:
        for metric in benchmark['end_to_end']:
            if not reports(metric, workload):
                continue
            value = obs[metric['name']] if metric['name'] in obs \
                else load_reader(metric['name'])(obs)
            metrics[metric['name']] = {'value': float(value),
                                       'unit': metric['unit']}

    compared = obs['compared']
    correct = serve.is_correct(compared)
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': cell['chips'],
              'memory_peak_bytes': obs['memory_peak_bytes']}
    result = {'correct': bool(correct), 'attempted': int(obs['attempted']),
              'failed': int(obs['failed']), 'metrics': metrics,
              'device': device}
    if reduced is not None:
        device['busy_s'] = reduced['busy_s']
        device['window_s'] = reduced['window_s']
        result['breakdown'] = trace_mod.breakdown(reduced)
    result['workload'] = workload
    result['seed'] = int(seed)
    result['diag'] = dict(obs.get('diag') or {},
                          compiled_in_window=obs['recompiles_in_window'],
                          compile_s=obs['compile_s'],
                          compile_cache_misses=obs['compile_cache_misses'])
    result['compared'] = compared
    return result, obs


def print_compared(compared, stream):
    for name, c in compared.items():
        extra = ' (%s)' % c['leaf'] if 'leaf' in c else ''
        print('compared %s = %r, limit %r%s' % (name, c['value'], c['limit'],
                                                extra), file=stream)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    benchmark = load_json(os.path.join(REPO, 'BENCHMARK.json'))
    result, _ = run_cell(benchmark, HERE, args.workload, args.seed,
                         args.seconds, args.trace, t_process=T_PROCESS)
    sys.stdout.flush()
    print_compared(result['compared'], sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == '__main__':
    sys.exit(main())
