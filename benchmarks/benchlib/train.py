"""The training driver: one configuration of kind `train` fed by a
training traffic mix, through the program's own entry points
(`data.write_shards` + `IngestPipeline` into `TrainStep.__call__`).

Set-up builds ONE step object with its state, drives it from the seed
through its first three steps with the window's own call and feed, takes
what the comparison needs from it (each step's loss, the first gradient's
norms worked out from the first moments, the norms of the parameters'
change after three steps), and hands the same object to the window. The
plain reference follows those three steps after the window has closed
and the program's state is freed.
"""
import gc
import shutil
import tempfile
import time

import numpy as np

from . import reference
from . import traffic as traffic_mod
from . import weights as W
from .serve import build_model

clock = time.perf_counter
CHECK_STEPS = 3


def hyper_of(step_cfg):
    return (step_cfg['learning_rate'], step_cfg['beta1'], step_cfg['beta2'],
            step_cfg['epsilon'], step_cfg['weight_decay'])


def build_step(cfg, leaves):
    import paddle_tpu as paddle
    from paddle_tpu.framework.functional import TrainStep
    sc = cfg['step']
    model = build_model(cfg['model'], cfg['dtype'], leaves,
                        recompute=bool(sc['recompute']),
                        fused_loss=bool(sc['fused_loss']))
    model.train()
    lr, b1, b2, eps, wd = hyper_of(sc)
    opt = paddle.optimizer.AdamW(
        learning_rate=lr, beta1=b1, beta2=b2, epsilon=eps, weight_decay=wd,
        parameters=model.parameters())
    step = TrainStep(model, lambda out, labels: model.loss(out, labels), opt)
    return step, model, opt


def endless(pipe):
    while True:
        got = False
        for batch in pipe:
            got = True
            yield batch
        if not got:
            raise RuntimeError('the input pipeline delivered no batch')


def program_tree(model, n_layer, pick=None):
    """The program's leaves (or per-leaf arrays from `pick`) in the
    stacked naming: {kind: array or list of per-layer arrays}."""
    params = dict(model.named_parameters())
    out = {}
    for kind, layer, name in W.leaf_names(n_layer):
        arr = pick(name, params[name]) if pick else params[name]._data
        if layer is None:
            out[kind] = arr
        else:
            out.setdefault(kind, []).append(arr)
    return out


def moments_of(model, opt):
    """{program leaf name: first moment} from the optimizer's state."""
    state = opt.state_dict()
    out = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        out[name] = state['%s_moment1' % (p.name or 'param%d' % i)]._data
    return out


def _norms(tree, minus=None):
    """Per-leaf L2 norms, float32, of {kind: array | [per-layer arrays]},
    of the difference to the stacked tree `minus` where given; leaves as
    `reference.comparison_leaves` cuts them."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(t, s0):
        out = {}
        for kind, v in t.items():
            layers = v if isinstance(v, list) else [v]
            parts = {}
            for i, a in enumerate(layers):
                a = a.astype(jnp.float32)
                if s0 is not None:
                    b = s0[kind][i] if isinstance(v, list) else s0[kind]
                    a = a - b.astype(jnp.float32)
                for name, x in reference.comparison_leaves(kind, a).items():
                    parts.setdefault(name, []).append(
                        jnp.sqrt(jnp.sum(jnp.square(x))))
            for name, ns in parts.items():
                out[name] = jnp.stack(ns) if isinstance(v, list) else ns[0]
        return out
    return jax.device_get(f(tree, minus))


def run(env):
    import jax
    import paddle_tpu as paddle  # noqa: F401
    from paddle_tpu import data as pdata
    cfg, tcfg = env['config'], env['traffic']
    m, sc = cfg['model'], cfg['step']
    seed, seconds = env['seed'], env['seconds']
    batch, seq = int(sc['batch']), int(sc['seq_len'])
    ann = jax.profiler.TraceAnnotation
    b1 = float(sc['beta1'])

    stacked = W.make_stacked(m, seed, cfg['dtype'])
    leaves = W.program_leaves(stacked)
    del stacked
    step, model, opt = build_step(cfg, leaves)
    del leaves

    rows = traffic_mod.train_rows(tcfg, seed, batch, seq)
    known = {r.tobytes(): i for i, r in enumerate(rows[:, 0])}
    shard_dir = tempfile.mkdtemp(prefix='bench_shards_')
    obs = {'kind': 'train', 'model': m, 'step_cfg': sc, 'seconds': seconds,
           'trace_dir': None}
    try:
        paths = pdata.write_shards(
            ((r[0], r[1]) for r in rows), shard_dir, int(tcfg['shards']))
        pipe = pdata.IngestPipeline(
            paths, batch_size=batch,
            shuffle_window=int(tcfg['shuffle_window']),
            seed=int(seed) & 0x7FFFFFFF, drop_last=True,
            prefetch=int(tcfg['prefetch']),
            reader_threads=int(tcfg['reader_threads']))
        feed = endless(pipe)
        wait = [0.0]

        def one_step():
            with ann('bench.next_batch'):
                t = clock()
                ids, labels = next(feed)
                wait[0] += clock() - t
            with ann('bench.step_dispatch'):
                return step(ids, labels), ids, labels

        def fetch(loss):
            with ann('bench.loss_fetch'):
                return float(np.asarray(loss.numpy(), np.float32))

        # ---- the first steps: what the comparison takes from the program
        prog = {'loss': [], 'batches': []}
        for k in range(CHECK_STEPS):
            loss, ids, labels = one_step()
            prog['batches'].append((np.asarray(ids.numpy()),
                                    np.asarray(labels.numpy())))
            prog['loss'].append(fetch(loss))
            if k == 0:
                mom = moments_of(model, opt)
                tree = program_tree(model, m['n_layer'],
                                    lambda name, p: mom[name])
                prog['grad_norms'] = {
                    kd: v / (1.0 - b1) for kd, v in _norms(tree).items()}
                del mom, tree
        stacked0 = W.make_stacked(m, seed, cfg['dtype'])
        prog['change_norms'] = _norms(program_tree(model, m['n_layer']),
                                      stacked0)
        del stacked0
        for _ in range(int(tcfg.get('warmup_steps', 1))):
            fetch(one_step()[0])

        # ---- the window: whole steps, one kept in flight
        trace_steps = int(env.get('trace_steps', 4))
        tracing = span = None
        done_t, losses = [], []
        wait[0] = 0.0
        env['window_opened']()
        t0 = clock()
        pending = one_step()[0]
        while True:
            nxt = one_step()[0]
            losses.append(fetch(pending))
            done_t.append(clock())
            pending = nxt
            elapsed = done_t[-1] - t0
            if env['trace'] and tracing is None and len(done_t) >= 2:
                est = elapsed / len(done_t)
                if elapsed + (trace_steps + 1.5) * est >= seconds:
                    tracing = env['start_trace']()
                    span = ann('bench.window')
                    span.__enter__()
                    obs['trace_first_step'] = len(done_t)
            if elapsed >= seconds:
                break
        if span is not None:
            span.__exit__(None, None, None)
            obs['trace_steps'] = len(done_t) - obs['trace_first_step']
        env['window_closed']()
        fetch(pending)                       # drained, not counted
        if tracing is not None:
            obs['trace_dir'] = env['stop_trace'](tracing)
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)

    window_s = done_t[-1] - t0
    obs['window_s'] = window_s
    obs['steps_done'] = len(done_t)
    obs['tokens_per_step'] = batch * seq
    obs['train_tokens_per_s'] = len(done_t) * batch * seq / window_s
    obs['step_ms'] = [1e3 * (b - a) for a, b in zip([t0] + done_t, done_t)]
    obs['data_wait_s'] = wait[0]
    obs['losses'] = losses
    obs['attempted'] = len(done_t)
    obs['failed'] = sum(1 for v in losses if not np.isfinite(v))
    obs['memory_peak_bytes'] = env['memory_peak']()
    obs['diag'] = {'steps': len(done_t), 'step_ms_max': max(obs['step_ms']),
                   'step_ms_min': min(obs['step_ms']),
                   'longest_step': int(np.argmax(obs['step_ms'])),
                   'data_wait_s': wait[0]}

    # the program's state goes before the reference comes
    del step, model, opt, pipe, feed
    gc.collect()
    jax.clear_caches()
    unknown = sum(1 for ids, _ in prog['batches'] for r in ids
                  if r.tobytes() not in known)
    seen = [known.get(r.tobytes()) for ids, _ in prog['batches'] for r in ids]
    repeated = len(seen) - len(set(seen))
    obs['compared'] = compare(prog, cfg, seed, unknown + repeated,
                              env.get('control'))
    return obs


def follow(cfg, seed, batches, quant='none', rows=None):
    """The plain reference through the same first steps: losses, first
    gradient norms, change norms. `quant` makes it the control; `rows`
    (a slice) plants the half-batch fault."""
    m = cfg['model']
    with reference.highest():
        stacked0 = W.make_stacked(m, seed, cfg['dtype'])
        ref = reference.TrainReference(
            m, W.make_stacked(m, seed, cfg['dtype']),
            hyper_of(cfg['step']), int(cfg['reference']['micro_rows']),
            quant)
        losses = []
        for ids, labels in batches:
            if rows is not None:
                ids, labels = ids[rows], labels[rows]
            losses.append(ref.step(ids, labels))
        import jax
        change = jax.device_get(reference.change_norms(ref.params, stacked0))
    return {'loss': losses, 'grad_norms': ref.first_grad_norms,
            'change_norms': change}


def leaf_gaps(got, want, skip=None):
    """The gaps between two sets of per-leaf norms, |got - want| over the
    larger of the reference's norm of that leaf and of the median leaf:
    the widest, with its leaf. `skip` masks leaves out."""
    names, g, w = [], [], []
    for kind in want:
        gv, wv = np.atleast_1d(got[kind]), np.atleast_1d(want[kind])
        for i in range(len(wv)):
            names.append('%s[%d]' % (kind, i) if len(wv) > 1 else kind)
            g.append(float(gv[i]))
            w.append(float(wv[i]))
    g, w = np.asarray(g), np.asarray(w)
    gaps = np.abs(g - w) / np.maximum(w, np.median(w))
    keep = np.ones(len(gaps), bool) if skip is None else ~np.asarray(skip)
    i = int(np.argmax(np.where(keep, gaps, -1.0)))
    return float(gaps[i]), names[i]


def dead_leaves(ref_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference:
    under a thousandth of the median leaf's (a key's bias under softmax).
    They move under Adam by round-off alone and are left out of the
    change comparison."""
    w = np.concatenate([np.atleast_1d(v) for v in ref_grad_norms.values()])
    return w < 1e-3 * np.median(w)


def readings(prog, ref):
    """The numbers compared, program (or control, or fault) against the
    reference: {name: value}."""
    out = {}
    for k in range(len(ref['loss'])):
        out['loss_gap_step%d' % (k + 1)] = abs(prog['loss'][k]
                                               - ref['loss'][k])
    out['grad_norm_gap'], out['grad_norm_leaf'] = leaf_gaps(
        prog['grad_norms'], ref['grad_norms'])
    out['change_norm_gap'], out['change_norm_leaf'] = leaf_gaps(
        prog['change_norms'], ref['change_norms'],
        dead_leaves(ref['grad_norms']))
    return out


def compare(prog, cfg, seed, bad_rows, control=None):
    """The numbers that decide `correct`, each beside its limit. `control`
    (tools/control.py only) also puts the reference in that lower
    precision, and the reference on half of the rows, in the program's
    place and reads the same numbers of them."""
    limits = cfg['correct']
    ref = follow(cfg, seed, prog['batches'])
    got = readings(prog, ref)
    out = {'rows_unknown_or_repeated': {'value': bad_rows, 'limit': 0}}
    for name, value in got.items():
        if name.endswith('_leaf'):
            continue
        out[name] = {'value': value, 'limit': limits.get(name)}
    out['grad_norm_gap']['leaf'] = got['grad_norm_leaf']
    out['change_norm_gap']['leaf'] = got['change_norm_leaf']
    if control:
        half = slice(0, len(prog['batches'][0][0]) // 2)
        for label, planted in ((control, {'quant': control}),
                               ('half_batch', {'rows': half})):
            alt = readings(follow(cfg, seed, prog['batches'], **planted),
                           ref)
            for name, value in alt.items():
                out['control.%s.%s' % (label, name)] = {'value': value,
                                                        'limit': None}
    return out
