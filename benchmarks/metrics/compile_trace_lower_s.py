"""Outer trace and lower seconds of the programs jax built before the window, from the program's compile log."""
import math

from benchlib import program_spans as P


def before_window(obs):
    """The compile log's totals (`paddle_tpu.monitor.perf.watchdog`) of
    what ended before the window opened: a serving cell's `obs['t0']`,
    the first of the training window's last `steps_done` `train.step`
    spans. None where a program has no log, the tracer is off, the
    window's start is unknown or the log left out an entry before it."""
    try:
        from paddle_tpu.monitor.perf.watchdog import compile_log
    except ImportError:
        return None
    if obs.get('kind') == 'serve':
        t0 = obs.get('t0')
    else:
        n = obs.get('steps_done')
        got = P.ring() if obs.get('kind') == 'train' and n else None
        steps = [s for s in got[0] if s['name'] == P.TRAIN_STEP][-n:] \
            if got else []
        t0 = steps[0]['start_mono'] if steps and len(steps) == n else None
    if t0 is None or math.isinf(t0):
        return None
    return compile_log().totals(t0)


def read(obs):
    t = before_window(obs)
    return None if t is None else t['outer_s']['trace'] + t['outer_s']['lower']
