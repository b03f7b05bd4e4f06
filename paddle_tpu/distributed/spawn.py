"""paddle.distributed.spawn (reference: distributed/spawn.py:333 +
fleet/launch_utils.py env contract).

On TPU, one process drives all local chips (single-controller SPMD), so
nprocs<=1 degenerates to calling func inline; nprocs>1 spawns real
processes with the reference's env contract (PADDLE_TRAINER_ID,
PADDLE_TRAINERS_NUM, PADDLE_CURRENT_ENDPOINT, PADDLE_TRAINER_ENDPOINTS)
— the per-rank bootstrap a jax.distributed.initialize picks up on
multi-host. Failures propagate with the failing rank's traceback text
(launch_utils TrainerProc watch-loop behavior).

One process per chip: a chip belongs to the process that first touched
jax on it, so spawned children run on the CPU backend
(JAX_PLATFORMS=cpu, set here) — they are host-side workers beside the
one controller. PADDLE_TPU_SPAWN_PLATFORM=tpu hands the children the
default backend instead (multi-host: each host's process owns its
chips); asking for that from a parent that already holds the
accelerator raises instead of starting children that would hang on it.
"""
import multiprocessing as mp
import os
import traceback

__all__ = ['spawn', 'SpawnContext']


class SpawnContext:
    def __init__(self, procs, error_queue):
        self.processes = procs
        self._errors = error_queue

    def join(self, timeout=None):
        for p in self.processes:
            p.join(timeout)
        failures = []
        while not self._errors.empty():
            failures.append(self._errors.get())
        for p in self.processes:
            if p.exitcode not in (0, None):
                rank_tb = next((tb for r, tb in failures), None)
                raise RuntimeError(
                    'spawned rank failed (exitcode %s)%s'
                    % (p.exitcode,
                       (':\n' + rank_tb) if rank_tb else ''))
        return True


def _free_ports(n):
    import socket
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _platform_env():
    """Backend env for spawned children (module docstring): the CPU
    backend unless PADDLE_TPU_SPAWN_PLATFORM=tpu, which is refused when
    this process already holds the accelerator."""
    if os.environ.get('PADDLE_TPU_SPAWN_PLATFORM', 'cpu') == 'cpu':
        return {'JAX_PLATFORMS': 'cpu'}
    from ..framework.device import process_holds_accelerator
    if process_holds_accelerator():
        raise RuntimeError(
            'PADDLE_TPU_SPAWN_PLATFORM=tpu asks spawned children for the '
            'accelerator, but this process has already initialised jax on '
            'it and a chip belongs to one process at a time. Spawn before '
            'touching jax in the parent, or leave the children on the CPU '
            'backend (the default).')
    return {}


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs in (-1, 0, 1, None):
        func(*args)
        return None
    ctx = mp.get_context('spawn')
    error_queue = ctx.SimpleQueue()
    ports = _free_ports(nprocs)
    endpoints = ','.join('127.0.0.1:%d' % p for p in ports)
    procs = []
    plat_env = _platform_env()
    # children inherit os.environ at exec time — seat the platform env in
    # the parent around start() so it is active BEFORE the child's module
    # re-imports (per-rank vars still applied in _wrap, which runs after)
    saved = {k: os.environ.get(k) for k in plat_env}
    os.environ.update(plat_env)
    try:
        trace_base = os.environ.get('PADDLE_TRAINER_TRACE_DIR')
        for rank in range(nprocs):
            env = {'PADDLE_TRAINER_ID': str(rank),
                   'PADDLE_TRAINERS_NUM': str(nprocs),
                   'PADDLE_CURRENT_ENDPOINT': '127.0.0.1:%d' % ports[rank],
                   'PADDLE_TRAINER_ENDPOINTS': endpoints}
            if trace_base:
                # per-rank trace dirs, merge_traces-ready (profiler)
                env['PADDLE_TRAINER_TRACE_DIR'] = os.path.join(
                    trace_base, 'rank_%d' % rank)
            env.update(plat_env)
            p = ctx.Process(target=_wrap,
                            args=(func, args, env, rank, error_queue),
                            daemon=daemon)
            p.start()
            procs.append(p)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    context = SpawnContext(procs, error_queue)
    if join:
        context.join()
        return None
    return context


def _wrap(func, args, env, rank, error_queue):
    os.environ.update(env)
    try:
        func(*args)
    except Exception:
        error_queue.put((rank, traceback.format_exc()))
        raise
