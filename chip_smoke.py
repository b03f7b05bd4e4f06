"""chip_smoke.py — the quickest proof that the program starts on the chip.

One process, on one TPU chip, drives the system's main paths once through
the entry points a user calls, at the full width of the model the repo
benchmarks (BERT-base width: hidden 768, 12 layers, 12 heads, vocab 30528;
random weights from --seed), and checks what comes out:

  train  GPTForCausalLM(...).bfloat16() + AdamW through
         framework.functional.TrainStep, batch 32 x seq 512: losses start
         at ln(vocab), stay finite and fall; the compiled step holds the
         Pallas flash forward AND backward kernels; donation ran and did
         not strand the model.
  serve  the paged engine (prefix cache on) answers 16 mixed-length
         requests added over time so slots and pages are freed and reused,
         one program per phase, one request streamed; the same engine once
         more behind an in-process ServingGateway, tokens equal to the
         direct drive. With f32 weights under
         jax.default_matmul_precision("highest") every request's tokens
         equal model.generate()'s token for token; for the bf16 run the
         share that still agrees is printed, not asserted.

  --chips 4  runs ONLY the multichip phase: the same train step under
         fleet.init + fleet.fleet_train_step on a four-device mesh
         (dp2 x mp2, then dp2 x sharding2) against the single-device
         TrainStep on the same seed and batch. Its last line has "count": 4.

  --rehearse  the same phases and control flow at toy widths on whatever
         backend JAX_PLATFORMS gives — the CPU rehearsal. Never prints the
         ok line.

The last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and is printed only if every phase passed on a TPU. Any failure is a
traceback and a non-zero exit code; without a TPU (and without --rehearse)
the script exits non-zero before doing anything. The serving fabric is not
here: its workers are processes, and a chip belongs to one process
(serving/fabric/worker.py).
"""
import argparse
import gc
import json
import math
import sys
import time

import numpy as np

FULL = dict(vocab_size=30528, hidden_size=768, num_layers=12, num_heads=12)
TOY = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4)

# train: batch x seq; serve: the engine's shape and the request mix
FULL_SIZES = dict(batch=32, seq=512, steps=8, slots=8, max_len=256, chunk=32,
                  block=8, page=16, pages=65, prefix=64, new_tokens=32,
                  lengths=(32, 72, 96, 128))
TOY_SIZES = dict(batch=4, seq=64, steps=5, slots=4, max_len=64, chunk=8,
                 block=4, page=4, pages=33, prefix=16, new_tokens=8,
                 lengths=(8, 18, 24, 32))
N_REQUESTS = 16

COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
               'collective-permute')


def check(cond, what):
    """A phase check: assert is stripped under -O, this is not."""
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or loading from
    the persistent cache) — so each phase reports compile and run apart."""

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

    def mark(self):
        return self.seconds, self.programs

    def since(self, mark):
        return self.seconds - mark[0], self.programs - mark[1]


def memory_line(device):
    stats = device.memory_stats()
    if not stats:
        return 'device memory: not reported by this backend'
    return 'device memory: peak %.2f GB, in use %.2f GB, limit %.2f GB' % (
        stats['peak_bytes_in_use'] / 1e9, stats['bytes_in_use'] / 1e9,
        stats['bytes_limit'] / 1e9)


def build_lm(widths, max_pos, seed, fused_loss=False):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    return GPTForCausalLM(GPTConfig(
        max_position_embeddings=max_pos, dropout=0.0, fused_loss=fused_loss,
        **widths))


def train_batch(widths, sizes, seed):
    import paddle_tpu as paddle
    rng = np.random.RandomState(seed)
    shape = (sizes['batch'], sizes['seq'])
    return tuple(paddle.to_tensor(
        rng.randint(0, widths['vocab_size'], shape).astype(np.int32))
        for _ in range(2))


def make_train_step(widths, sizes, seed, build_step):
    """The model bench.py builds — bf16, AdamW, fused CE — and its step:
    `build_step(model, loss_fn, opt)` is TrainStep or the fleet's."""
    import paddle_tpu as paddle
    model = build_lm(widths, sizes['seq'], seed, fused_loss=True)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = build_step(model, lambda out, labels: model.loss(out, labels),
                      opt)
    return step, model


def run_steps(step, batch, steps):
    """`steps` steps on the same batch, each waited for; (losses, secs)."""
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(*batch)
        loss._data.block_until_ready()
        times.append(time.perf_counter() - t0)
        losses.append(float(loss.numpy()))
    return losses, times


def check_losses(losses, vocab, label):
    check(all(math.isfinite(v) for v in losses),
          '%s: a loss is not finite: %r' % (label, losses))
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          '%s: first loss %.4f is not within 0.5 of ln(%d) = %.4f'
          % (label, losses[0], vocab, math.log(vocab)))
    check(losses[-1] < losses[0],
          '%s: loss did not fall: %r' % (label, losses))


def phase_train(device, widths, sizes, seed, clock):
    import jax
    from paddle_tpu.framework.functional import TrainStep, extract_params
    t0, mark = time.perf_counter(), clock.mark()
    step, model = make_train_step(widths, sizes, seed, TrainStep)
    batch = train_batch(widths, sizes, seed)

    # what the chip will run, read off the compiled program before it
    # runs: the flash kernels are in it, or this is not the flash path
    compiled = step.compiled_executable(*batch)
    kernels = compiled.as_text().count('tpu_custom_call')
    mem = compiled.memory_analysis()
    print('train: compiled step has %d tpu_custom_call(s); compiler predicts '
          'arguments %.2f GB, temporaries %.2f GB, code %.2f GB'
          % (kernels, mem.argument_size_in_bytes / 1e9,
             mem.temp_size_in_bytes / 1e9,
             mem.generated_code_size_in_bytes / 1e9))
    if device.platform == 'tpu':
        # one forward and one fused backward kernel per layer at seq 512
        check(kernels == 2 * widths['num_layers'],
              'train: expected %d Pallas flash custom calls (fwd + bwd per '
              'layer) in the compiled step, found %d — the flash path did '
              'not run' % (2 * widths['num_layers'], kernels))
    del compiled

    before = next(iter(extract_params(model).values()))
    losses, times = run_steps(step, batch, sizes['steps'])
    check_losses(losses, widths['vocab_size'], 'train')
    check(before.is_deleted(),
          'train: the parameters handed to the first step were not donated')
    # donation must not strand the model: every parameter still reads back
    state = {k: np.asarray(v.numpy(), np.float32)
             for k, v in model.state_dict().items()}
    check(state and all(np.isfinite(a).all() for a in state.values()),
          'train: a parameter is unreadable or not finite after the steps')

    compile_s, programs = clock.since(mark)
    print('train: %d steps, loss %.4f -> %.4f (ln vocab %.4f), %d parameters '
          'read back finite' % (len(losses), losses[0], losses[-1],
                                math.log(widths['vocab_size']), len(state)))
    print('train: %.1f s wall, %.1f s compiling %d program(s), steady step '
          '%.1f ms (median of %d)'
          % (time.perf_counter() - t0, compile_s, programs,
             1e3 * float(np.median(times[2:])), len(times[2:])))
    print('train: ' + memory_line(device))
    del step, model, state, before
    gc.collect()
    jax.clear_caches()


# ---- serve -----------------------------------------------------------------

def make_prompts(widths, sizes, seed):
    """16 prompts of mixed length; every second one starts with the same
    `prefix`-token block (what the paged engine's prefix cache shares)."""
    rng = np.random.RandomState(seed + 1)
    vocab = widths['vocab_size']
    prefix = [int(t) for t in rng.randint(0, vocab, sizes['prefix'])]
    lengths = sizes['lengths']
    prompts = []
    for i in range(N_REQUESTS):
        n = lengths[i % len(lengths)]
        if i % 2 and n > sizes['prefix']:
            body = rng.randint(0, vocab, n - sizes['prefix'])
            prompts.append(prefix + [int(t) for t in body])
        else:
            prompts.append([int(t) for t in rng.randint(0, vocab, n)])
    return prompts


def drive_engine(eng, prompts, new_tokens, cache_arrays):
    """Add requests over time (half up front, then one every other step,
    the last one streamed), drive to completion. `cache_arrays(eng)` lists
    the engine's device cache buffers: the ones held across the first step
    are deleted afterwards iff donation really ran. Returns (per-request
    tokens, donated)."""
    reqs = [eng.add_request(p, max_new_tokens=new_tokens)
            for p in prompts[:len(prompts) // 2]]
    waiting = list(prompts[len(prompts) // 2:])
    held = cache_arrays(eng)
    eng.step()
    donated = all(a.is_deleted() for a in held)
    del held
    steps = 1
    while waiting:
        eng.step()
        steps += 1
        if steps % 2 == 0:
            last = len(waiting) == 1
            reqs.append(eng.add_request(waiting.pop(0),
                                        max_new_tokens=new_tokens,
                                        stream=last))
    streamed = list(eng.stream(reqs[-1]))
    eng.run()
    check(streamed == reqs[-1].tokens,
          'streamed tokens differ from the request\'s tokens')
    return [list(r.tokens) for r in reqs], donated


def check_answers(label, tokens, widths, new_tokens):
    check(len(tokens) == N_REQUESTS, '%s: %d of %d requests answered'
          % (label, len(tokens), N_REQUESTS))
    for i, toks in enumerate(tokens):
        check(len(toks) == new_tokens,
              '%s: request %d produced %d tokens, wanted %d'
              % (label, i, len(toks), new_tokens))
        check(all(0 <= t < widths['vocab_size'] for t in toks),
              '%s: request %d has an out-of-vocabulary token' % (label, i))


def answer_all(label, eng, prompts, widths, sizes, cache_arrays, programs,
               expect_donation):
    """Drive `eng` over the prompts and hold it to what the engine owes:
    every request answered, one program per phase, no recompile after
    warm-up, donation as expected. Returns (tokens, donated)."""
    nt = sizes['new_tokens']
    tokens, donated = drive_engine(eng, prompts, nt, cache_arrays)
    check_answers(label, tokens, widths, nt)
    check(eng.compiled_sizes() == programs,
          '%s: programs retraced: %r' % (label, eng.compiled_sizes()))
    check(eng.perf.recompile_count == 0, '%s: %d recompile(s) after warm-up'
          % (label, eng.perf.recompile_count))
    check(donated is expect_donation, '%s: cache donation ran: %r, expected '
          '%r' % (label, donated, expect_donation))
    return tokens, donated


def run_engine(model, widths, sizes, prompts, expect_donation):
    """The engine driven directly and behind the gateway over the same
    prompts on `model` as it stands; returns {'paged': tokens, 'gateway':
    tokens}."""
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import (PagedContinuousBatchingEngine,
                                    ServingGateway)
    nt = sizes['new_tokens']

    def engine():
        return PagedContinuousBatchingEngine(
            model, num_seqs=sizes['slots'], max_len=sizes['max_len'],
            page_size=sizes['page'], num_pages=sizes['pages'],
            prefill_chunk=sizes['chunk'], decode_block=sizes['block'],
            prefix_cache=True)

    out = {}
    eng = engine()
    out['paged'], donated = answer_all(
        'paged', eng, prompts, widths, sizes,
        lambda e: [a for kv in e._pools for a in kv],
        {'prefill': 1, 'decode': 1, 'verify': 0}, expect_donation)
    check(eng.perf_estimate() is not None,
          'paged: perf_estimate could not price the decode program')
    demand = sum(-(-(len(p) + nt - 1) // sizes['page']) for p in prompts)
    check(demand > sizes['pages'] - 1,
          'paged: the pool (%d pages) covers the whole demand (%d): no page '
          'is reused' % (sizes['pages'] - 1, demand))
    check(eng.prefix.hits > 0, 'paged: the shared prefix never hit the cache')
    print('serve/paged: %d requests over %d slots, %d pages demanded from a '
          'pool of %d, prefix blocks hit %d / missed %d, programs %r, pool '
          'donated: %r'
          % (N_REQUESTS, sizes['slots'], demand, sizes['pages'] - 1,
             eng.prefix.hits, eng.prefix.misses, eng.compiled_sizes(),
             donated))
    eng.shutdown()

    # the door users call; sync drive with a bound, so a replica the
    # gateway marked lost shows as a failure, not as a hang
    gw = ServingGateway(engine, replicas=1, registry=MetricRegistry())
    reqs = [gw.submit(p, max_new_tokens=nt) for p in prompts]
    for _ in range(100 * N_REQUESTS):
        if not gw.step():
            break
    else:
        raise AssertionError('gateway: requests still outstanding')
    check(not gw.failover_log,
          'gateway: a replica was lost: %r' % gw.failover_log)
    out['gateway'] = [list(r.tokens) for r in reqs]
    check_answers('gateway', out['gateway'], widths, nt)
    # same engine, same dtype, other co-batching and other prefix hits: a
    # request's tokens may not depend on who shared the batch or the pages
    check(out['gateway'] == out['paged'],
          'gateway: tokens differ from the engine driven directly')
    print('serve/gateway: %d requests through ServingGateway(replicas=1), '
          'tokens equal the engine driven directly' % N_REQUESTS)
    gw.shutdown()
    return out


def reference_tokens(model, prompts, new_tokens):
    """model.generate() tails, one batched call per prompt length."""
    import paddle_tpu as paddle
    ref = [None] * len(prompts)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        ids = paddle.to_tensor(np.asarray([prompts[i] for i in idx],
                                          np.int32))
        full = model.generate(ids, max_new_tokens=new_tokens).numpy()
        for row, i in enumerate(idx):
            ref[i] = [int(t) for t in full[row, n:]]
    return ref


def agreement(tokens, ref):
    """(share of requests equal to ref, first diverging position or None)."""
    same = sum(a == b for a, b in zip(tokens, ref))
    first = [next(k for k, (x, y) in enumerate(zip(a, b)) if x != y)
             for a, b in zip(tokens, ref) if a != b]
    return same / len(ref), (min(first) if first else None)


def phase_serve(device, widths, sizes, seed, clock):
    import jax
    # the engine donates its pools on tpu/gpu, not on cpu
    expect_donation = device.platform in ('tpu', 'gpu')
    prompts = make_prompts(widths, sizes, seed)
    nt = sizes['new_tokens']
    model = build_lm(widths, 4 * sizes['max_len'], seed)
    model.eval()

    # parity first, on the f32 weights: engine tokens == generate() tails
    t0, mark = time.perf_counter(), clock.mark()
    with jax.default_matmul_precision('highest'):
        ref = reference_tokens(model, prompts, nt)
        exact = run_engine(model, widths, sizes, prompts, expect_donation)
    for name, tokens in exact.items():
        share, first = agreement(tokens, ref)
        check(share == 1.0,
              'parity: %s drive at f32/highest differs from generate() on '
              '%d of %d requests, first at token %r — a bug (donation, cache '
              'rows, page reuse), not rounding'
              % (name, round((1 - share) * N_REQUESTS), N_REQUESTS, first))
    compile_s, programs = clock.since(mark)
    print('serve/parity: the engine, direct and behind the gateway, equals '
          'generate() token for token on %d requests x %d tokens (f32, '
          'highest precision); '
          '%.1f s wall, %.1f s compiling %d program(s)'
          % (N_REQUESTS, nt, time.perf_counter() - t0, compile_s, programs))

    t0, mark = time.perf_counter(), clock.mark()
    model.bfloat16()
    bf16 = run_engine(model, widths, sizes, prompts, expect_donation)
    share, first = agreement(bf16['paged'], ref)
    print('serve/bf16: the engine agrees with the f32 reference on %.0f%% of '
          'requests (first divergence at token %s) — printed, not asserted'
          % (100 * share, first))
    compile_s, programs = clock.since(mark)
    total = sum(len(t) for toks in bf16.values() for t in toks)
    print('serve/bf16: %d tokens, %.1f s wall, %.1f s compiling %d '
          'program(s)' % (total, time.perf_counter() - t0, compile_s,
                          programs))
    print('serve: ' + memory_line(device))


# ---- multichip -------------------------------------------------------------

def phase_multichip(device, widths, sizes, seed, clock):
    import jax
    from paddle_tpu.distributed import fleet
    from paddle_tpu.framework.functional import TrainStep
    steps = 3
    vocab = widths['vocab_size']
    batch = train_batch(widths, sizes, seed)

    t0 = time.perf_counter()
    step, model = make_train_step(widths, sizes, seed, TrainStep)
    ref, _ = run_steps(step, batch, steps)
    check_losses(ref, vocab, 'multichip/single-device')
    print('multichip: single-device TrainStep losses %s (%.1f s)'
          % (' '.join('%.4f' % v for v in ref), time.perf_counter() - t0))
    del step, model
    gc.collect()
    jax.clear_caches()

    # the model is bf16 and so is its loss: near ln(vocab) ~ 10 one bf16 ulp
    # is 2**-4 = 0.0625. Two layouts reduce in different orders, so the
    # trajectories may differ by rounding: allow two ulps, no more
    tol = 2 * 2.0 ** -4
    layouts = (('dp2 x mp2', {'dp_degree': 2, 'mp_degree': 2}, {}),
               ('dp2 x sharding2', {'dp_degree': 2, 'sharding_degree': 2},
                {'sharding': True, 'sharding_configs': {'stage': 3}}))
    for label, hybrid, extra in layouts:
        t0, mark = time.perf_counter(), clock.mark()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(
            {'dp_degree': 1, 'mp_degree': 1, 'pp_degree': 1,
             'sharding_degree': 1, 'sp_degree': 1, 'ep_degree': 1}, **hybrid)
        for key, value in extra.items():
            if isinstance(value, dict):
                getattr(strategy, key).update(value)
            else:
                setattr(strategy, key, value)
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().mesh
        check({d.id for d in mesh.devices.flat} ==
              {d.id for d in jax.devices()} and
              all(mesh.shape[k.replace('_degree', '')] == v
                  for k, v in hybrid.items()),
              '%s: fleet.init built another mesh than asked for: %r'
              % (label, mesh))

        step, model = make_train_step(
            widths, sizes, seed,
            lambda m, loss_fn, opt: fleet.fleet_train_step(
                m, loss_fn, opt, strategy=strategy))
        hlo, _ = step.compiled_hlo(*batch)
        found = {c: hlo.count(c + '(') + hlo.count(c + '-start(')
                 for c in COLLECTIVES}
        check(sum(found.values()) > 0,
              '%s: no collective in the compiled step' % label)
        kernels = hlo.count('tpu_custom_call')
        del hlo

        losses, _ = run_steps(step, batch, steps)
        check_losses(losses, vocab, 'multichip/' + label)
        worst = max(abs(a - b) for a, b in zip(losses, ref))
        check(worst <= tol,
              '%s: losses %r differ from single-device %r by %.4f > %.3f'
              % (label, losses, ref, worst, tol))

        params = dict(model.named_parameters())
        spread = [len(p._data.sharding.device_set) for p in params.values()]
        split = sum(not p._data.sharding.is_fully_replicated
                    for p in params.values())
        check(min(spread) == 4,
              '%s: a parameter lives on %d device(s), not 4'
              % (label, min(spread)))
        check(split > 0, '%s: every parameter is fully replicated' % label)
        per_dev = [sum(s.data.nbytes for p in params.values()
                       for s in p._data.addressable_shards
                       if s.device == d) for d in jax.devices()]
        compile_s, programs = clock.since(mark)
        print('multichip/%s: losses %s, max |diff| vs single %.4f (tol %.3f)'
              % (label, ' '.join('%.4f' % v for v in losses), worst, tol))
        print('multichip/%s: collectives %s; %d tpu_custom_call(s); %d of %d '
              'parameters split across devices, parameter bytes per device '
              '%s MB' % (label,
                         ', '.join('%s x%d' % kv for kv in found.items()
                                   if kv[1]),
                         kernels, split, len(params),
                         '/'.join('%.1f' % (b / 1e6) for b in per_dev)))
        print('multichip/%s: %.1f s wall, %.1f s compiling %d program(s)'
              % (label, time.perf_counter() - t0, compile_s, programs))
        del step, model, params
        gc.collect()
        jax.clear_caches()
    for d in jax.devices():
        print('multichip: device %d %s' % (d.id, memory_line(d)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--seed', type=int, default=0,
                    help='makes the weights, the batch and the prompts')
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1,
                    help='4: run only the multichip phase, on four chips')
    ap.add_argument('--rehearse', action='store_true',
                    help='toy widths on whatever backend JAX_PLATFORMS '
                         'gives; never prints the ok line')
    args = ap.parse_args(argv)

    import jax
    device = jax.devices()[0]
    if device.platform != 'tpu' and not args.rehearse:
        sys.exit('chip_smoke.py needs a TPU and jax found %s (%s). '
                 '--rehearse runs the same phases at toy widths on this '
                 'backend.' % (device.platform, device.device_kind))
    check(args.chips == 1 or len(jax.devices()) == 4,
          '--chips 4 needs four devices, jax found %d' % len(jax.devices()))

    # before the first compile; JAX_COMPILATION_CACHE_DIR wins when set
    from paddle_tpu.framework import compile_cache
    cache_dir = compile_cache.configure()
    print('chip_smoke: %s %s x%d, seed %d, %s widths, compile cache at %s'
          % (device.platform, device.device_kind, len(jax.devices()),
             args.seed, 'toy (rehearsal)' if args.rehearse else 'full',
             cache_dir))

    widths, sizes = (TOY, TOY_SIZES) if args.rehearse else (FULL, FULL_SIZES)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_multichip(device, widths, sizes, args.seed, clock)
        else:
            phase_train(device, widths, sizes, args.seed, clock)
            phase_serve(device, widths, sizes, args.seed, clock)
    finally:
        clock.close()
    stats = compile_cache.stats()
    print('chip_smoke: all phases passed in %.1f s; compile cache hits %d, '
          'misses %d' % (time.perf_counter() - t0, stats['hits'],
                         stats['misses']))
    if args.rehearse:
        print('chip_smoke: rehearsal on %s — not a chip run, no ok line'
              % device.platform)
        return
    print(json.dumps({'ok': True, 'device': {
        'platform': device.platform, 'kind': device.device_kind,
        'count': len(jax.devices())}}))


if __name__ == '__main__':
    main()
