"""The serving driver: one configuration of kind `serve` under one
serving traffic mix, through the program's own front door
(`PagedContinuousBatchingEngine.add_request` / `step`).

One thread drives the engine; a second one is the load generator. The
engine holds its lock for the whole of `step()` and `add_request` takes
that lock, so the generator never calls it: it wakes at each request's
due time, stamps the hand-over and puts the request into an inbox, and
the driving loop submits what the inbox holds before every `step()`.
Every latency runs from the DUE time on one clock (`time.monotonic`,
which is also the engine's).

Token times are the benchmark's own: each request streams into a
`Stamps` sink that stamps every token when the engine delivers it.
"""
import collections
import gc
import math
import threading
import time

from . import counts, reference, stats
from . import traffic as traffic_mod
from . import weights as W

clock = time.monotonic


class Stamps:
    """The stream sink of one request: the time of every token put."""

    def __init__(self):
        self.t = []

    def put(self, tok):
        if tok is not None:
            self.t.append(clock())


class Rec:
    """The benchmark's record of one request."""

    def __init__(self, idx, due):
        self.idx = idx
        self.due = due            # absolute, on `clock`
        self.handed = None        # generator's hand-over
        self.req = None
        self.stamps = None
        self.seen = 0             # tokens already accounted per step
        self.closed = False
        # read off the request once the engine is shut down
        self.tokens, self.finished = [], False
        self.prefix_hit, self.admit_t = 0, None


def build_model(m, dtype, leaves, **extra):
    """The program's GPTForCausalLM at the configuration's sizes holding
    the benchmark's weights; `extra` goes to GPTConfig (the training
    cells' recompute and fused_loss)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        model = GPTForCausalLM(GPTConfig(
            vocab_size=m['vocab_size'], hidden_size=m['n_embd'],
            num_layers=m['n_layer'], num_heads=m['n_head'],
            intermediate_size=counts.inner(m),
            max_position_embeddings=m['n_positions'], dropout=0.0,
            layer_norm_epsilon=m['layer_norm_epsilon'],
            initializer_range=m['initializer_range'],
            tie_word_embeddings=True, **extra))
    finally:
        paddle.set_default_dtype(before)
    load_leaves(model, leaves)
    return model


def load_leaves(model, leaves):
    """Put the benchmark's arrays into the program's parameters, leaf by
    leaf, so that each initial array is freed as it is replaced."""
    params = dict(model.named_parameters())
    if set(params) != set(leaves):
        raise ValueError('the program names other leaves than the '
                         'benchmark makes: %r' % sorted(
                             set(params) ^ set(leaves))[:8])
    for name, p in params.items():
        arr = leaves[name]
        if tuple(p.shape) != tuple(arr.shape):
            raise ValueError('%s: program shape %r, benchmark shape %r'
                             % (name, tuple(p.shape), tuple(arr.shape)))
        p._data = arr


def _generator(recs, inbox, stop):
    """Wake at each due time and hand the request over."""
    for rec in recs:
        while True:
            wait = rec.due - clock()
            if wait <= 0 or stop.is_set():
                break
            time.sleep(min(wait, 0.05))
        if stop.is_set():
            return
        rec.handed = clock()
        inbox.append(rec)


def run(env):
    """Set up, warm up, measure; returns the observation dict that the
    metric readers and the correctness check take."""
    import jax
    from paddle_tpu.serving import PagedContinuousBatchingEngine
    cfg, tcfg = env['config'], env['traffic']
    m, eng_cfg = cfg['model'], cfg['engine']
    seconds, seed = env['seconds'], env['seed']
    ann = jax.profiler.TraceAnnotation

    stacked = W.make_stacked(m, seed, cfg['dtype'])
    leaves = W.program_leaves(stacked)
    del stacked
    model = build_model(m, cfg['dtype'], leaves)
    del leaves
    eng = PagedContinuousBatchingEngine(
        model, num_seqs=eng_cfg['num_seqs'], max_len=eng_cfg['max_len'],
        page_size=eng_cfg['page_size'], num_pages=eng_cfg['num_pages'],
        prefill_chunk=eng_cfg['prefill_chunk'],
        decode_block=eng_cfg['decode_block'],
        spec_k=eng_cfg.get('spec_k', 0),
        prefix_cache=eng_cfg.get('prefix_cache', True))
    trace = traffic_mod.serve_trace(tcfg, seed, seconds)

    # a deployment's system prompts are resident: one request per prefix
    # group fills the prefix cache (and compiles both programs)
    for prefix in trace.prefixes:
        eng.add_request(prefix + [0], max_new_tokens=2)
    if not trace.prefixes:
        eng.add_request(trace.prompts[0][:8], max_new_tokens=2)
    eng.run()

    open_loop = tcfg['arrival']['process'] != 'backlog'
    if open_loop:                # the window opens at a time
        base = t0 = clock() + trace.warmup_s + 0.05
        t_end = t0 + seconds
    else:                        # ... or after the warm-up's engine steps
        base, t0, t_end = clock(), math.inf, math.inf
    recs = [Rec(i, base + float(trace.due[i])) for i in range(len(trace))]
    drain_s = float(tcfg.get('drain_s', 60.0))
    trace_s = min(float(env.get('trace_seconds', 6.0)), seconds)
    inbox, stop = collections.deque(), threading.Event()
    gen = threading.Thread(target=_generator, args=(recs, inbox, stop),
                           name='bench-generator', daemon=True)

    live, steps = [], []
    obs = {'kind': 'serve', 'model': m, 'engine': eng_cfg,
           'seconds': seconds, 'trace_dir': None}
    acc = {'burst_least': [], 'flops': 0.0}
    peak_flops, peak_bw = env['peaks']
    tracing = window_span = None
    in_window = closed = False
    n_steps = 0
    gen.start()
    if not open_loop:
        gen.join()               # a backlog is all there before step one
    try:
        while True:
            now = clock()
            if t0 == math.inf and n_steps >= trace.warmup_steps:
                t0, t_end = now, now + seconds
            if not in_window and now >= t0:
                in_window = True
                env['window_opened']()
            if env['trace'] and tracing is None and now >= t_end - trace_s:
                tracing = env['start_trace']()
                window_span = ann('bench.window')
                window_span.__enter__()
                obs['trace_t0'] = clock()
            if window_span is not None and now >= t_end:
                window_span.__exit__(None, None, None)
                window_span = None
                obs['trace_t1'] = clock()
            while inbox:
                rec = inbox.popleft()
                with ann('bench.add_request'):
                    req = eng.add_request(
                        trace.prompts[rec.idx],
                        max_new_tokens=trace.outputs[rec.idx], stream=True)
                    rec.stamps = req._stream_q = Stamps()
                rec.req = req
                live.append(rec)
            handed_all = not gen.is_alive() and not inbox
            if now >= t_end and not closed:
                closed = True
                env['window_closed']()
            if not open_loop and now >= t_end:
                break
            if open_loop and now >= t_end + drain_s:
                break
            if eng.scheduler.pending:
                ts = clock()
                with ann('bench.engine_step'):
                    eng.step()
                te = clock()
                n_steps += 1
                _account(live, steps, ts, te, eng, m, acc, peak_flops,
                         peak_bw, t0, t_end)
            elif handed_all and not open_loop:
                raise RuntimeError(
                    'the backlog ran out before the window closed: the cell '
                    'no longer keeps the slots busy; give it more requests')
            elif handed_all:
                break
            else:
                time.sleep(0.001)
    finally:
        stop.set()
        gen.join(timeout=10)
        if window_span is not None:
            window_span.__exit__(None, None, None)
            obs['trace_t1'] = clock()
    if not closed:
        env['window_closed']()
    if tracing is not None:
        obs['trace_dir'] = env['stop_trace'](tracing)

    obs.update(acc)
    obs['t0'], obs['t_end'] = t0, t_end
    obs['recs'] = recs
    obs['trace_obj'] = trace
    obs['steps'] = steps
    obs['open_loop'] = open_loop
    obs['burst_ms_p50'] = _burst_p50(eng)
    obs['memory_peak_bytes'] = env['memory_peak']()
    _summarise(obs)
    obs['diag'] = _diag(obs)

    # the program's state goes before the reference comes
    eng.shutdown()
    for rec in recs:
        req, rec.req = rec.req, None
        if req is not None:
            rec.tokens = list(req.tokens)
            rec.finished = req.done and req.outcome == 'ok'
            rec.prefix_hit = getattr(req, '_prefix_hit', 0)
            rec.admit_t = getattr(req, '_admit_t', None)
    obs['queue_wait_ms'] = [
        1e3 * (r.admit_t - r.due) for r in recs
        if r.admit_t is not None and t0 <= r.due < t_end]
    live.clear()
    del eng, model
    gc.collect()
    jax.clear_caches()
    obs['compared'] = compare(obs, cfg, tcfg, trace, seed,
                              env.get('control'))
    return obs


def _burst_p50(eng):
    p = eng.timeline.percentile(50)
    return None if p is None else 1e3 * p


def _account(live, steps, ts, te, eng, m, acc, peak_flops, peak_bw, t0,
             t_end):
    """What one engine step did, from the tokens it delivered. A step
    belongs to the window when it STARTS inside it: the step that crosses
    the close is whole work of the window, the one that straddles the
    opening is not."""
    inside = t0 <= ts < t_end
    ctx_by_substep = collections.defaultdict(list)
    for rec in live:
        if rec.closed:
            continue
        req = rec.req
        new = len(req.tokens) - rec.seen
        if new > 0:
            n0 = len(req.prompt)
            first = rec.seen == 0
            if first and inside:
                hit = getattr(req, '_prefix_hit', 0)
                acc['flops'] += sum(counts.serve_flops_token(m, p + 1)
                                    for p in range(hit, n0))
            base = rec.seen + (1 if first else 0)
            for j in range(new - (1 if first else 0)):
                ctx_by_substep[j].append(n0 + base + j)
            rec.seen = len(req.tokens)
        if req.done:
            rec.closed = True
    if inside:
        burst = 0.0
        for ctxs in ctx_by_substep.values():
            burst += counts.decode_step_least_seconds(
                m, ctxs, peak_flops, peak_bw)[0]
            acc['flops'] += sum(counts.serve_flops_token(m, c) for c in ctxs)
        if ctx_by_substep:
            acc['burst_least'].append((ts, te, burst))
        steps.append((ts, te, eng.allocator.in_use, eng.pages.in_use,
                      len(ctx_by_substep)))
    live[:] = [r for r in live if not r.closed]


def _summarise(obs):
    """End-to-end numbers and the per-request lists the readers take."""
    t0, t_end = obs['t0'], obs['t_end']
    recs = obs['recs']
    if obs['open_loop']:
        counted = [r for r in recs if t0 <= r.due < t_end]
    else:
        counted = [r for r in recs if r.req is not None
                   and (r.stamps.t or r.req.slot is not None)]
    worst = clock()
    ttft, tpot, lag, failed = [], [], [], 0
    # the rate is over whole engine steps: from the start of the first
    # step the window holds to the end of the one that crosses its close
    # (a burst delivers its tokens together, so a window cut at a fixed
    # instant would gain or lose a whole burst, a percent of the count)
    steps = obs['steps']
    w0, w1 = (steps[0][0], steps[-1][1]) if steps else (t0, t_end)
    tokens_in_window = 0
    for r in recs:
        if r.stamps is not None:
            tokens_in_window += sum(w0 <= t <= w1 for t in r.stamps.t)
    for r in counted:
        if r.handed is not None:
            lag.append(1e3 * (r.handed - r.due))
        done = r.req is not None and r.req.done and r.req.outcome == 'ok'
        if obs['open_loop'] and not done:
            failed += 1
        if r.stamps is not None and r.stamps.t:
            ttft.append(1e3 * (r.stamps.t[0] - r.due))
            if done and len(r.stamps.t) > 1:
                tpot.append(1e3 * (r.stamps.t[-1] - r.stamps.t[0])
                            / (len(r.stamps.t) - 1))
        elif obs['open_loop']:
            ttft.append(1e3 * (worst - r.due))
        if obs['open_loop'] and not done:      # a failure is the worst
            tpot.append(1e3 * (worst - r.due))
    obs['attempted'], obs['failed'] = len(counted), failed
    obs['ttft_ms'], obs['tpot_ms'], obs['gen_lag_ms'] = ttft, tpot, lag
    obs['tokens_in_window'] = tokens_in_window
    obs['span_s'] = w1 - w0
    obs['serve_tokens_per_s'] = tokens_in_window / (w1 - w0)
    obs['ttft_p90_ms'] = stats.percentile(ttft, 90)
    obs['tpot_p90_ms'] = stats.percentile(tpot, 90)


def _diag(obs):
    """What a reader of the ledger needs to tell a slow run from a noisy
    one: the engine steps of the window and where the longest ones fell."""
    steps, t0 = obs['steps'], obs['t0']
    if not steps:
        return {}
    ms = [1e3 * (te - ts) for ts, te, *_ in steps]
    longest = sorted(range(len(ms)), key=lambda i: -ms[i])[:3]
    return {'engine_steps': len(steps),
            'step_ms_p50': stats.percentile(ms, 50),
            'step_ms_max': max(ms),
            'longest_steps_at_s': [round(steps[i][0] - t0, 3)
                                   for i in longest],
            'slots_in_use_max': max(s[2] for s in steps),
            'pages_in_use_max': max(s[3] for s in steps),
            'ttft_max_ms': max(obs['ttft_ms']) if obs['ttft_ms'] else None,
            'gen_lag_max_ms': max(obs['gen_lag_ms'])
            if obs['gen_lag_ms'] else None}


def pick_sample(recs, obs, n, seed):
    """The finished requests to check: the longest and a seeded draw."""
    t0, t_end = obs['t0'], obs['t_end']
    done = [r for r in recs if r.finished and r.tokens
            and (not obs['open_loop'] or t0 <= r.due < t_end)]
    if not done:
        return []
    size = lambda r: len(r.tokens)
    longest = max(done, key=lambda r: (size(r), -r.idx))
    rest = [r for r in done if r is not longest]
    rng = traffic_mod.stream(seed, 'check_sample')
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(take)]


def compare(obs, cfg, tcfg, trace, seed, control=None):
    """The numbers that decide `correct`, each beside its limit.

    logit_gap_max: over a seeded sample of the requests the window
    finished (the longest among them), the widest gap by which a served
    token's logit in the float32 reference lies below the reference's
    best logit at that position; logit_gap_mean: the mean of those gaps
    over the same tokens (a widest gap swings from seed to seed, the
    mean does not). bad_answers: finished requests whose
    token count is not what was asked or that hold an id outside the
    vocabulary (exact: limit 0). `control` (tools/control.py only) also
    reads, at the same positions of the same prompts and tokens, the gap
    of the token that the reference in that lower precision puts first."""
    m = cfg['model']
    limits = cfg['correct']
    recs = obs['recs']
    bad = sum(1 for r in recs if r.finished and (
        len(r.tokens) != trace.outputs[r.idx]
        or any(not 0 <= t < m['vocab_size'] for t in r.tokens)))
    sample = pick_sample(recs, obs, int(tcfg['check']['sample']), seed)
    out = {'bad_answers': {'value': bad, 'limit': 0}}
    if not sample:
        out['logit_gap_max'] = {'value': None,
                                'limit': limits['logit_gap_max']}
        out['logit_gap_mean'] = {'value': None,
                                 'limit': limits.get('logit_gap_mean')}
        out['tokens_compared'] = {'value': 0, 'limit': None}
        return out
    seqs = [(trace.prompts[r.idx], list(r.tokens)) for r in sample]
    with reference.highest():
        stacked = W.make_stacked(m, seed, cfg['dtype'])
        gaps, cgaps = reference.served_gaps(stacked, m, seqs, control)
        del stacked
    n_tokens = int(sum(len(g) for g in gaps))
    widest = lambda gs: max(float(g.max()) for g in gs)
    mean = lambda gs: float(sum(g.sum() for g in gs)) / n_tokens
    out['logit_gap_max'] = {'value': widest(gaps),
                            'limit': limits['logit_gap_max']}
    out['logit_gap_mean'] = {'value': mean(gaps),
                             'limit': limits.get('logit_gap_mean')}
    out['tokens_compared'] = {'value': n_tokens, 'limit': None}
    out['requests_compared'] = {'value': len(seqs), 'limit': None}
    if control:
        for name, f in (('logit_gap_max', widest), ('logit_gap_mean', mean)):
            out['control.%s.%s' % (control, name)] = {
                'value': f(cgaps), 'limit': None}
    return out


def is_correct(compared):
    for name, c in compared.items():
        if c['limit'] is None:
            continue
        if c['value'] is None or not c['value'] <= c['limit']:
            return False
    return True
