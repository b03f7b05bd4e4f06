"""Secondary benchmark harness for the BASELINE.md tracked configs that
bench.py's single-line contract does not cover:

  config 2 — ResNet-50 train throughput (images/sec), @to_static -> XLA
  config 4 — YOLO-family inference latency/QPS through AnalysisPredictor
  (plus)   — GPT decode tokens/sec through the single-dispatch scan path

Prints one JSON line per config. CPU runs are tagged degraded (tiny
shapes); TPU runs use the real config. A rung that raises prints an error
row, the remaining rungs still run, and the script then exits non-zero.
Not invoked by the driver — manual runs (python bench_extra.py).

One process per chip: this process touches jax, so on a TPU host it holds
the chip and the serving-fabric rung — whose worker processes would need
the same chip — is refused there (serving/fabric/worker.py).
"""
import json
import statistics
import time

import numpy as np


def bench_resnet(on_tpu):
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50, resnet18
    from paddle_tpu.framework import functional as func_mod

    paddle.seed(0)
    if on_tpu:
        model, batch, steps, size = resnet50(), 64, 20, 224
        model.bfloat16()
    else:
        model, batch, steps, size = resnet18(), 2, 2, 32
    opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                    parameters=model.parameters())
    ce = paddle.nn.CrossEntropyLoss()
    step = func_mod.TrainStep(model, lambda lo, la: ce(lo, la), opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 3, size, size).astype(np.float32))
    if on_tpu:
        # params are bf16 — conv requires matching operand dtypes
        x = x.astype('bfloat16')
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))
    step(x, y).numpy()                      # compile
    warm = 10 if on_tpu else 1
    for _ in range(warm):
        loss = step(x, y)
    _ = loss.numpy()
    t0 = time.time()
    for _ in range(steps):
        loss = step(x, y)
    _ = loss.numpy()
    dt = time.time() - t0
    return {'metric': 'resnet_train_images_per_sec',
            'value': round(batch * steps / dt, 2), 'unit': 'images/sec',
            'batch': batch, 'image_size': size,
            'model': type(model).__name__,
            'degraded': not on_tpu}


def bench_yolo_infer(on_tpu):
    """Config 4: PP-YOLOv2 inference, batch 1 AND 8, median-of-repeats.

    Single-run captures varied 1.5x (205.9 vs 140.2 ms same config) —
    each batch size reports the median of `reps` timed passes plus the
    spread, so run-to-run noise shows up as spread instead of silently
    biasing the number. Budget: the v5e roofline for this graph is
    ~10 ms/img; <50 ms/img batch-1 is the pass bar, QPS scales with batch.
    """
    import paddle_tpu as paddle
    from paddle_tpu.vision.models.yolo import ppyolov2
    paddle.seed(0)
    size = 320 if on_tpu else 64
    model = ppyolov2(num_classes=80)
    model.eval()
    import jax
    from paddle_tpu.framework.functional import (extract_params,
                                                 extract_buffers,
                                                 functional_call)
    params = extract_params(model)
    buffers = extract_buffers(model)

    def fwd(p, b, img):
        out, _ = functional_call(model, p, b, (paddle.Tensor(img),),
                                 training=False)
        return out
    jfwd = jax.jit(fwd)
    rows = []
    for batch in ((1, 8) if on_tpu else (1,)):
        img = np.random.RandomState(0).rand(
            batch, 3, size, size).astype(np.float32)
        out = jfwd(params, buffers, img)    # compile
        _ = np.asarray(jax.tree_util.tree_leaves(out)[0])
        n = 10 if on_tpu else 2
        reps = 3 if on_tpu else 1
        per_rep = []
        for _ in range(reps):
            t0 = time.time()
            for _ in range(n):
                out = jfwd(params, buffers, img)
            _ = np.asarray(jax.tree_util.tree_leaves(out)[0])
            per_rep.append((time.time() - t0) / n)
        med = statistics.median(per_rep)
        rows.append({'metric': 'yolo_infer_latency_ms',
                     'value': round(med * 1e3 / batch, 2), 'unit': 'ms/img',
                     'batch': batch,
                     'batch_latency_ms': round(med * 1e3, 2),
                     'qps': round(batch / med, 2),
                     'spread_ms': round((max(per_rep) - min(per_rep)) * 1e3,
                                        2),
                     'reps': reps, 'image_size': size,
                     'degraded': not on_tpu})
    return rows


def bench_gpt_decode(on_tpu):
    """Autoregressive decode throughput (tokens/sec) through the
    single-dispatch scan decode (GPTForCausalLM.generate: jitted prefill
    + ONE lax.scan program — reference serving path analog:
    AnalysisPredictor, analysis_predictor.cc:381).

    Reports the HBM roofline alongside: cached decode is weight-bound —
    each token step must stream the bf16 weights once, so
    steps/s <= HBM_BW / param_bytes, tokens/s <= batch * that.
    """
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        batch, prompt_len, new_tokens = 8, 128, 128
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=64,
                        dropout=0.0)
        batch, prompt_len, new_tokens = 2, 8, 16
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    rows = []

    from paddle_tpu.slim import streamed_bytes as stream_bytes
    param_bytes = stream_bytes(model)
    from paddle_tpu.monitor.perf import costmodel
    hbm = costmodel.platform_peaks()[2]     # this device's peak bytes/s
    # decode is weight-streaming-bound, so tokens/s should scale near-
    # linearly with batch until compute catches up: measure two points
    batches = (batch, batch * 4) if on_tpu else (batch,)
    import os
    profile_dir = os.environ.get('PADDLE_TPU_BENCH_PROFILE_DECODE')

    def measure(metric, weight_bytes, extra_fields, profiled_batch=None):
        """One metric's batch sweep; shared protocol for every variant
        (a drifting copy of the timing loop is how the profiled-run-
        equals-timed-run bug slipped in)."""
        for b in batches:
            try:
                prompt = paddle.to_tensor(
                    rng.randint(0, cfg.vocab_size, (b, prompt_len)).astype(
                        np.int32))
                out = model.generate(prompt,
                                     max_new_tokens=new_tokens)  # compile
                _ = out.numpy()
                if profiled_batch == b:
                    # on-chip trace of the already-compiled decode
                    # program: the data that names the next decode
                    # byte-mover. The traced run is SEPARATE from the
                    # timed one below — profiler overhead must not leak
                    # into the reported tokens/sec
                    import jax
                    jax.profiler.start_trace(profile_dir)
                    try:
                        _ = model.generate(
                            prompt, max_new_tokens=new_tokens).numpy()
                    finally:
                        # an unmatched start_trace would leave the
                        # profiler running for every later point
                        jax.profiler.stop_trace()
                t0 = time.time()
                out = model.generate(prompt, max_new_tokens=new_tokens)
                _ = out.numpy()
                dt = time.time() - t0
            except Exception as e:
                # a failed larger-batch point must not discard the
                # smaller one already measured
                rows.append({'metric': metric, 'batch': b,
                             'error': repr(e)[:300]})
                continue
            toks = b * new_tokens / dt
            roofline = b * hbm / weight_bytes
            row = {'metric': metric, 'value': round(toks, 2),
                   'unit': 'tokens/sec', 'batch': b,
                   'tokens_per_sec_per_seq': round(toks / b, 2),
                   'roofline_tokens_per_sec': round(roofline, 0),
                   'roofline_frac': round(toks / roofline, 4),
                   'prompt_len': prompt_len, 'new_tokens': new_tokens,
                   'degraded': not on_tpu}
            row.update(extra_fields)
            rows.append(row)

    measure('gpt_decode_tokens_per_sec', param_bytes, {},
            profiled_batch=batch if profile_dir else None)

    # weight-only int8 serving variant (slim.weight_only): halves the
    # streamed bytes on the transformer Linears — a DIFFERENT model
    # (quantized weights), reported under its own metric with its own
    # roofline. Reference analog: AnalysisPredictor int8 deployments.
    try:
        from paddle_tpu.slim import quantize_weight_only
        quantize_weight_only(model)
        q_bytes = stream_bytes(model)
    except Exception as e:
        rows.append({'metric': 'gpt_decode_int8w_tokens_per_sec',
                     'error': repr(e)[:300]})
        return rows
    measure('gpt_decode_int8w_tokens_per_sec', q_bytes,
            {'stream_bytes_int8': q_bytes, 'stream_bytes_bf16': param_bytes})
    return rows


def _serving_workload(n_req, lens, mnt, mean_gap, vocab, tenants=None):
    """The serving rungs' shared workload spec: seeded Poisson arrivals
    with a prompt-length ladder, expressed in the capacity.workload
    language. Parameters and RNG streams match the retired hand-rolled
    generators exactly (capacity.workload pins the parity), so stored
    bench bests stay comparable; rows carry the spec hash."""
    from paddle_tpu.capacity import workload
    return workload.WorkloadSpec(
        requests=n_req, seed=0, vocab_size=vocab,
        arrival={'process': 'poisson', 'mean_gap_s': mean_gap},
        lengths={'dist': 'ladder', 'lens': list(lens)},
        output={'dist': 'fixed', 'len': mnt}, tenants=tenants)


def _perf_fields(eng, t_cold=None, bursts=None, wall=None):
    """Perf-introspection fields for a serving bench row: cold/warm
    compile seconds, post-warmup recompile count, and the cost-model
    MFU/roofline block over the engine's steady-state program (decode,
    or the verify forward under speculation)."""
    out = {}
    if t_cold is not None:
        out['compile_s_cold'] = round(t_cold, 3)
    out['recompiles'] = eng.perf.recompiles
    try:
        est = eng.perf_estimate(bursts=bursts, wall_seconds=wall)
    except Exception:
        est = None
    if est:
        out['compile_s_warm'] = round(est['compile_s_warm'], 3)
        intensity = est.get('arithmetic_intensity')
        if intensity is not None and intensity != float('inf'):
            out['arithmetic_intensity'] = round(intensity, 2)
        out['roofline_bound'] = est['roofline_bound']
        if 'mfu_est' in est:
            out['mfu_est'] = round(est['mfu_est'], 4)
    try:
        from paddle_tpu.framework import compile_cache
        hr = compile_cache.hit_rate()
        if hr is not None:
            out['compile_cache_hit_rate'] = round(hr, 4)
    except Exception:
        pass
    return out


def _drive_cb(engine, prompts, arrivals, mnt):
    """Feed the engine its arrival trace in real time and drain it."""
    from paddle_tpu.serving.metrics import ServingMetrics
    engine.metrics = ServingMetrics()     # drop warmup samples
    reqs = []
    i = 0
    t0 = time.time()
    while i < len(prompts) or engine.scheduler.pending:
        now = time.time() - t0
        while i < len(prompts) and arrivals[i] <= now:
            reqs.append(engine.add_request(prompts[i], max_new_tokens=mnt))
            i += 1
        if engine.scheduler.pending:
            engine.step()
        elif i < len(prompts):
            time.sleep(min(arrivals[i] - now, 0.01))
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in reqs)
    return toks / dt, engine.metrics.report()


def _drive_sequential(model, prompts, arrivals, mnt):
    """Baseline: one generate() per request, strictly in arrival order
    (the pre-continuous-batching serving shape: each request owns the
    model until it finishes)."""
    import paddle_tpu as paddle
    lat = []
    t0 = time.time()
    for p, arr in zip(prompts, arrivals):
        now = time.time() - t0
        if now < arr:
            time.sleep(arr - now)
        s0 = time.time()
        _ = model.generate(paddle.to_tensor([p]),
                           max_new_tokens=mnt).numpy()
        lat.append(time.time() - s0)
    dt = time.time() - t0
    return len(prompts) * mnt / dt, statistics.median(lat)


def bench_serving(on_tpu):
    """Continuous-batching serving rung: tok/s, p50/p99 per-token
    latency and slot occupancy vs the sequential generate() baseline
    under a Poisson arrival trace, plus the tok/s-vs-slot-count
    saturation curve (8/16/32) and an int8 weight-only variant.

    The headline comparison is throughput under load: sequential serving
    runs [1, hidden] decode GEMMs while requests queue; the engine keeps
    the same GEMMs at slot-count batch. Same prompts, same trace, same
    greedy sampling — and the engine's greedy tokens are asserted
    identical to generate()'s in tests/test_serving.py, so the speedup
    is not bought with drift.
    """
    import paddle_tpu as paddle
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.slim import quantize_weight_only, streamed_bytes
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block = 256, 32, 8
        slot_curve, mean_gap = (8, 16, 32), 0.02
    else:
        # big enough that decode GEMMs outweigh host dispatch (a
        # hidden-64 toy is dispatch-bound and hides the batching win),
        # arrival rate high enough that serving is service-bound — the
        # regime continuous batching exists for
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 32, 24
        max_len, chunk, block = 64, 32, 8
        slot_curve, mean_gap = (8, 16, 32), 0.002
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    spec = _serving_workload(n_req, lens, mnt, mean_gap, cfg.vocab_size)
    trace = spec.generate()
    prompts = trace.prompts()
    arrivals = trace.arrivals()
    rows = []

    def run_variant(tag, extra):
        # sequential baseline: compile every (prompt_len, mnt) signature
        # before timing — serving steady state, not cold-start
        for n0 in lens:
            _ = model.generate(paddle.to_tensor([[0] * n0]),
                               max_new_tokens=mnt).numpy()
        seq_tps, seq_lat = _drive_sequential(model, prompts, arrivals, mnt)
        for num_slots in slot_curve:
            eng = ContinuousBatchingEngine(
                model, num_slots=num_slots, max_len=max_len,
                prefill_chunk=chunk, decode_block=block)
            t0c = time.time()
            eng.generate(prompts[:2], max_new_tokens=2)     # compile
            t_cold = time.time() - t0c
            b0 = eng.timeline.steps
            w0 = time.time()
            if num_slots == slot_curve[0]:
                # headline point: the real-time Poisson trace
                tps, rep = _drive_cb(eng, prompts, arrivals, mnt)
                row = {'metric': 'serving_cb_tokens_per_sec' + tag,
                       'value': round(tps, 2), 'unit': 'tokens/sec',
                       'num_slots': num_slots,
                       'latency_p50_ms': round(rep['latency_p50_ms'], 3),
                       'latency_p99_ms': round(rep['latency_p99_ms'], 3),
                       'occupancy_mean': round(rep['occupancy_mean'], 3),
                       'sequential_tokens_per_sec': round(seq_tps, 2),
                       'sequential_latency_median_s': round(seq_lat, 4),
                       'speedup_vs_sequential': round(tps / seq_tps, 2),
                       'trace': 'poisson', 'mean_gap_s': mean_gap,
                       'requests': n_req, 'new_tokens': mnt,
                       'workload_spec': spec.hash,
                       'traces': eng.compiled_sizes(),
                       'degraded': not on_tpu}
            else:
                # saturation curve: everything queued at t=0
                tps, rep = _drive_cb(eng, prompts, [0.0] * n_req, mnt)
                row = {'metric': 'serving_cb_tokens_per_sec' + tag,
                       'value': round(tps, 2), 'unit': 'tokens/sec',
                       'num_slots': num_slots,
                       'occupancy_mean': round(rep['occupancy_mean'], 3),
                       'trace': 'burst', 'requests': n_req,
                       'new_tokens': mnt, 'workload_spec': spec.hash,
                       'degraded': not on_tpu}
            row.update(_perf_fields(eng, t_cold,
                                    eng.timeline.steps - b0,
                                    time.time() - w0))
            row.update(extra)
            rows.append(row)

    run_variant('', {'stream_bytes': streamed_bytes(model)})
    try:
        quantize_weight_only(model)
        # quantization invalidates generate()'s compiled caches (the
        # buffer pytree changed shape); they re-key automatically
        run_variant('_int8w', {'stream_bytes': streamed_bytes(model)})
    except Exception as e:
        rows.append({'metric': 'serving_cb_tokens_per_sec_int8w',
                     'error': repr(e)[:300]})
    return rows


def _drive_paged(engine, prompts, arrivals, mnt):
    """_drive_cb plus the paged engine's capacity counters: returns
    (tok/s, report, peak pages in use across steps)."""
    from paddle_tpu.serving.metrics import ServingMetrics
    engine.metrics = ServingMetrics()     # drop warmup samples
    reqs, peak = [], 0
    i = 0
    t0 = time.time()
    while i < len(prompts) or engine.scheduler.pending:
        now = time.time() - t0
        while i < len(prompts) and arrivals[i] <= now:
            reqs.append(engine.add_request(prompts[i], max_new_tokens=mnt))
            i += 1
        if engine.scheduler.pending:
            engine.step()
            peak = max(peak, engine.pages.in_use)
        elif i < len(prompts):
            time.sleep(min(arrivals[i] - now, 0.01))
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in reqs)
    return toks / dt, engine.metrics.report(), peak


def bench_serving_paged(on_tpu):
    """Paged-KV serving rung: page-granular KV + prefix sharing + spec
    decode vs the PR-3 slot engine at the SAME occupancy, on a shared-
    system-prompt workload (every request opens with the same system
    prefix, the traffic shape prefix caching exists for).

    Rows (all keyed by workload/page_size/spec_k for the regression
    gate): the headline paged tok/s row carries the slot engine's tok/s
    on the identical trace plus prefix hit-rate, prefilled-token count
    and peak pages-in-use as fields; prefix hit-rate and spec accept-
    rate also get their own gated rows (both regress DOWN). Greedy
    parity across all three modes is asserted in tests/test_serving.py,
    so none of these numbers is bought with output drift.
    """
    import paddle_tpu as paddle
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedContinuousBatchingEngine)
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        sys_len, tail_lens, mnt, n_req = 64, (8, 16, 24, 32), 64, 32
        max_len, chunk, block, num_seqs, page = 256, 32, 8, 8, 16
    else:
        # same regime as bench_serving's CPU branch: decode GEMMs big
        # enough to outweigh host dispatch, burst arrivals so the run is
        # service-bound at full occupancy
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        sys_len, tail_lens, mnt, n_req = 32, (4, 8, 12, 16), 32, 24
        max_len, chunk, block, num_seqs, page = 96, 32, 8, 8, 16
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    from paddle_tpu.capacity import workload
    spec = workload.WorkloadSpec(
        requests=n_req, seed=0, vocab_size=cfg.vocab_size,
        arrival={'process': 'burst'},        # everything at t=0
        lengths={'dist': 'ladder', 'lens': list(tail_lens)},
        output={'dist': 'fixed', 'len': mnt},
        prefix={'len': sys_len, 'groups': 1, 'prob': 1.0})
    trace = spec.generate()
    prompts = trace.prompts()
    arrivals = trace.arrivals()              # burst: full occupancy
    base = {'new_tokens': mnt, 'num_slots': num_seqs, 'page_size': page,
            'workload': 'shared_prefix', 'trace': 'burst',
            'workload_spec': spec.hash,
            'requests': n_req, 'degraded': not on_tpu}
    rows = []

    # slot engine on the identical trace = the same-occupancy baseline
    slot = ContinuousBatchingEngine(model, num_slots=num_seqs,
                                    max_len=max_len, prefill_chunk=chunk,
                                    decode_block=block)
    slot.generate(prompts[:2], max_new_tokens=2)             # compile
    slot_tps, _ = _drive_cb(slot, prompts, arrivals, mnt)

    for spec_k in (0, 4):
        eng = PagedContinuousBatchingEngine(
            model, num_seqs=num_seqs, max_len=max_len, page_size=page,
            prefill_chunk=chunk, decode_block=block, spec_k=spec_k)
        t0c = time.time()
        eng.generate(prompts[:2], max_new_tokens=2)          # compile
        t_cold = time.time() - t0c
        b0 = eng.timeline.steps
        w0 = time.time()
        tps, rep, peak = _drive_paged(eng, prompts, arrivals, mnt)
        wall = time.time() - w0
        tag = '_spec' if spec_k else ''
        rows.append(dict(base, metric='serving_paged_tokens_per_sec' + tag,
                         value=round(tps, 2), unit='tokens/sec',
                         spec_k=spec_k,
                         slot_tokens_per_sec=round(slot_tps, 2),
                         speedup_vs_slot=round(tps / slot_tps, 3),
                         prefix_hit_rate=round(rep['prefix_hit_rate'], 3),
                         prefill_tokens=rep['prefill_tokens'],
                         pages_in_use_peak=peak,
                         spec_accept_rate=round(rep['spec_accept_rate'], 3),
                         occupancy_mean=round(rep['occupancy_mean'], 3),
                         traces=eng.compiled_sizes(),
                         **_perf_fields(eng, t_cold,
                                        eng.timeline.steps - b0, wall)))
        if not spec_k:
            rows.append(dict(base, metric='serving_paged_prefix_hit_rate',
                             value=round(rep['prefix_hit_rate'], 4),
                             unit='ratio', spec_k=spec_k,
                             prefill_tokens=rep['prefill_tokens']))
        else:
            rows.append(dict(base, metric='serving_paged_spec_accept_rate',
                             value=round(rep['spec_accept_rate'], 4),
                             unit='ratio', spec_k=spec_k,
                             spec_proposed=rep['spec_proposed'],
                             spec_accepted=rep['spec_accepted']))
    return rows


def bench_serving_gateway(on_tpu):
    """Multi-replica gateway rung: the Poisson-arrival chaos workload
    from ISSUE 8 — a 2-replica ServingGateway under the bench_serving
    arrival trace, measured clean and with one replica killed mid-burst.

    Rows (keyed by replicas/kill_at/policy for the regression gate): the
    clean gateway tok/s, the chaos-run tok/s (kill at 50% of
    submissions, failover count as a field), and the chaos completed
    ratio — the acceptance number, which must stay 1.0: every request
    finishes even though half the pool died mid-run. Exact-token parity
    of failed-over requests is asserted in
    tests/test_serving_gateway.py, so the throughput is not bought with
    drift or drops.
    """
    import paddle_tpu as paddle
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import ContinuousBatchingEngine, ServingGateway
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block, num_slots = 256, 32, 8, 8
        mean_gap = 0.02
    else:
        # same service-bound regime as bench_serving's CPU branch
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 32, 24
        max_len, chunk, block, num_slots = 64, 32, 8, 8
        mean_gap = 0.002
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    from paddle_tpu.capacity.replay import replay as replay_trace
    spec = _serving_workload(n_req, lens, mnt, mean_gap, cfg.vocab_size)
    trace = spec.generate()
    prompts = trace.prompts()
    replicas, kill_frac = 2, 0.5

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=num_slots, max_len=max_len,
            prefill_chunk=chunk, decode_block=block)

    def drive(kill_at):
        reg = MetricRegistry()
        gw = ServingGateway(factory, replicas=replicas, registry=reg)
        t0c = time.time()
        gw.generate(prompts[:replicas], max_new_tokens=2)     # compile
        t_cold = time.time() - t0c
        b0 = sum(r.engine.timeline.steps for r in gw.pool)
        gw.start()
        kill_i = None if kill_at is None else int(n_req * kill_at)

        def maybe_kill(i):
            if kill_i is not None and i == kill_i:
                gw.kill_replica(1)

        res = replay_trace(gw, trace, max_new_tokens=mnt,
                                     timeout=600,
                                     before_submit=maybe_kill)
        bursts = sum(r.engine.timeline.steps for r in gw.pool) - b0
        gw.shutdown()
        failovers = int(reg.get('gateway_failover_total').value())
        # replica 0 always survives the chaos run: its decode program is
        # representative, and bursts summed pool-wide make the MFU an
        # aggregate utilization over the whole gateway
        perf = _perf_fields(gw.pool[0].engine, t_cold, bursts, res.wall_s)
        return (res.tokens_per_sec, res.completed_ratio, failovers,
                gw.report(), perf)

    base = {'unit': 'tokens/sec', 'trace': 'poisson',
            'mean_gap_s': mean_gap, 'requests': n_req, 'new_tokens': mnt,
            'num_slots': num_slots, 'replicas': replicas,
            'policy': 'least_loaded', 'workload_spec': spec.hash,
            'degraded': not on_tpu}
    rows = []
    tps, ratio, fo, rep, perf = drive(None)
    rows.append(dict(base, metric='serving_gateway_tokens_per_sec',
                     value=round(tps, 2), kill_at='none', failovers=fo,
                     completed_ratio=round(ratio, 4), **perf))
    tps, ratio, fo, rep, perf = drive(kill_frac)
    rows.append(dict(base, metric='serving_gateway_tokens_per_sec_chaos',
                     value=round(tps, 2), kill_at=kill_frac, failovers=fo,
                     completed_ratio=round(ratio, 4),
                     replicas_alive=rep['replicas_alive'], **perf))
    rows.append(dict(base, metric='serving_gateway_completed_ratio',
                     value=round(ratio, 4), unit='ratio',
                     kill_at=kill_frac, failovers=fo))
    return rows


def bench_serving_gateway_tenants(on_tpu):
    """Mixed-tenant gateway rung (ISSUE 15): the Poisson workload split
    across two tenants ('premium' short prompts, 'batch' long prompts)
    through a clean 2-replica gateway, observed through the wide-event
    request log rather than the aggregate counters.

    Rows: one per-tenant TTFT p50 row per tenant (unit 'ms', keyed by
    the `tenant` aux field — the regression gate checks these
    lower-is-better), plus a kv attribution row whose value is the
    per-tenant KV page·second split. Every row carries the cross-check
    fields `kv_events_page_seconds` (sum over wide events) and
    `kv_pool_page_seconds` (sum of the slot allocators' pool-occupancy
    integrals): for the slot engine the two are equal by construction,
    and tools/request_report.py --kv-integral gates exactly that."""
    import paddle_tpu as paddle
    from paddle_tpu.monitor.events import (RequestLog,
                                           set_default_request_log)
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import ContinuousBatchingEngine, ServingGateway
    from paddle_tpu.serving.metrics import percentile
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block, num_slots = 256, 32, 8, 8
        mean_gap = 0.02
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 32, 24
        max_len, chunk, block, num_slots = 64, 32, 8, 8
        mean_gap = 0.002
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    from paddle_tpu.capacity.replay import replay as replay_trace
    # premium gets the short half of the length ladder, batch the long
    # half — distinguishable TTFT profiles from one workload
    spec = _serving_workload(
        n_req, lens, mnt, mean_gap, cfg.vocab_size,
        tenants={'mode': 'round_robin', 'tenants': [
            {'name': 'premium',
             'lengths': {'dist': 'ladder',
                         'lens': list(lens[:len(lens) // 2])}},
            {'name': 'batch',
             'lengths': {'dist': 'ladder',
                         'lens': list(lens[len(lens) // 2:])}}]})
    trace = spec.generate()
    prompts = trace.prompts()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=num_slots, max_len=max_len,
            prefill_chunk=chunk, decode_block=block)

    # the log must be installed BEFORE construction: engines and the
    # gateway cache default_request_log() like they cache the tracer
    log = RequestLog(capacity=4 * n_req)
    prev_log = set_default_request_log(log)
    try:
        reg = MetricRegistry()
        gw = ServingGateway(factory, replicas=2, registry=reg)
        gw.generate(prompts[:2], max_new_tokens=2,
                    tenant='warmup')                          # compile
        gw.start()
        res = replay_trace(gw, trace, max_new_tokens=mnt,
                                     timeout=600)
        dt = res.wall_s
        gw.shutdown()
        # pool-occupancy integral across the pool; wide-event sum must
        # match it exactly for slot engines (warmup events included —
        # the integral saw those slots too)
        pool_ps = sum(rep.engine.allocator.page_seconds()
                      for rep in gw.pool)
        events = log.events()
    finally:
        set_default_request_log(prev_log)
    toks = res.tokens
    ev_ps = sum(e['kv_page_seconds'] for e in events)
    kv_by_tenant = {}
    ttft_by_tenant = {}
    for e in events:
        kv_by_tenant[e['tenant']] = (kv_by_tenant.get(e['tenant'], 0.0)
                                     + e['kv_page_seconds'])
        if e['first_token_t'] is not None and e['arrival_t'] is not None:
            ttft_by_tenant.setdefault(e['tenant'], []).append(
                (e['first_token_t'] - e['arrival_t']) * 1e3)
    base = {'trace': 'poisson', 'mean_gap_s': mean_gap,
            'requests': n_req, 'new_tokens': mnt,
            'num_slots': num_slots, 'replicas': 2, 'workload': 'mixed',
            'policy': 'least_loaded', 'workload_spec': spec.hash,
            'degraded': not on_tpu,
            'kv_events_page_seconds': round(ev_ps, 6),
            'kv_pool_page_seconds': round(pool_ps, 6)}
    rows = [dict(base, metric='serving_gateway_mixed_tokens_per_sec',
                 value=round(toks / dt, 2), unit='tokens/sec')]
    for tenant in ('premium', 'batch'):
        rows.append(dict(
            base, metric='serving_gateway_tenant_ttft_p50',
            value=round(percentile(ttft_by_tenant.get(tenant, [0.0]),
                                   50), 3),
            unit='ms', tenant=tenant,
            tenant_requests=sum(1 for e in events
                                if e['tenant'] == tenant),
            tenant_kv_page_seconds=round(
                kv_by_tenant.get(tenant, 0.0), 6)))
    return rows


def bench_serving_gateway_qos(on_tpu):
    """Overload-QoS rung (ISSUE 17): a mixed-tenant burst through a
    2-replica gateway behind the admission layer — 'premium' (priority
    1, unthrottled) vs 'bg' (token-bucket rate-limited, priority 0) —
    where the BACKGROUND arrival rate DOUBLES halfway through the run
    (a second bg-only trace overlaid from the midpoint). Graceful
    degradation is the claim: the gateway sheds background traffic
    (outcome='rejected' wide events) while every premium request
    completes (asserted == 1.0 inline) and the premium TTFT tail stays
    bounded.

    Rows for the regression gate: premium TTFT p99 (ms,
    lower-is-better), shed rate (ratio, lower-is-better — a regression
    here means the policy started over-shedding the same workload), and
    the premium completed ratio (ratio, higher-is-better)."""
    import paddle_tpu as paddle
    from paddle_tpu.capacity.replay import replay as replay_trace
    from paddle_tpu.capacity.workload import Trace
    from paddle_tpu.monitor.events import (RequestLog,
                                           set_default_request_log)
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import (ContinuousBatchingEngine, QosPolicy,
                                    ServingGateway, TenantClass)
    from paddle_tpu.serving.metrics import percentile
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block, num_slots = 256, 32, 8, 8
        mean_gap, bg_rate, slo_ms = 0.02, 30.0, 2000.0
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 32, 24
        max_len, chunk, block, num_slots = 64, 32, 8, 8
        mean_gap, bg_rate, slo_ms = 0.002, 300.0, 5000.0
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    # steady half: premium + bg round-robin; burst half: a bg-only
    # trace at the SAME per-request gap overlaid from the midpoint, so
    # the background arrival rate doubles while premium's is unchanged
    spec = _serving_workload(
        n_req, lens, mnt, mean_gap, cfg.vocab_size,
        tenants={'mode': 'round_robin', 'tenants': [
            {'name': 'premium'}, {'name': 'bg'}]})
    burst_spec = _serving_workload(
        n_req // 2, lens, mnt, mean_gap, cfg.vocab_size,
        tenants={'mode': 'round_robin', 'tenants': [{'name': 'bg'}]})
    a, b = spec.generate(), burst_spec.generate()
    t_mid = float(a.arrival[-1]) * 0.5
    bg_id = a.tenant_names.index('bg')
    arr = np.concatenate([a.arrival, b.arrival + t_mid])
    order = np.argsort(arr, kind='stable')
    trace = Trace(
        arr[order],
        np.concatenate([a.prompt_len, b.prompt_len])[order],
        np.concatenate([a.new_tokens, b.new_tokens])[order],
        np.concatenate([a.tenant_id,
                        np.full(len(b), bg_id, np.int64)])[order],
        a.tenant_names,
        np.full(len(order), -1, np.int64),
        np.zeros(len(order), np.int64),
        meta={'vocab_size': cfg.vocab_size, 'spec': {'seed': 0}})
    prompts = trace.prompts()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=num_slots, max_len=max_len,
            prefill_chunk=chunk, decode_block=block)

    def policy():
        return QosPolicy(classes=[
            TenantClass('premium', priority=1),
            TenantClass('bg', rate=bg_rate, burst=max(4, num_slots),
                        priority=0)])

    log = RequestLog(capacity=4 * len(trace))
    prev_log = set_default_request_log(log)
    try:
        reg = MetricRegistry()
        gw = ServingGateway(factory, replicas=2, admission=policy(),
                            registry=reg)
        gw.generate(prompts[:2], max_new_tokens=2,
                    tenant='warmup')                          # compile
        gw.start()
        res = replay_trace(gw, trace, max_new_tokens=mnt, timeout=600)
        gw.shutdown()
        events = [e for e in log.events() if e['tenant'] != 'warmup']
    finally:
        set_default_request_log(prev_log)
    tenants = trace.tenants()
    premium = [h for h, t in zip(res.handles, tenants) if t == 'premium']
    shed = sum(1 for h in res.handles if h.error is not None)
    shed_rate = shed / float(len(res.handles))
    prem_done = sum(1 for h in premium if h.done and h.error is None)
    prem_ratio = prem_done / float(len(premium))
    if prem_ratio != 1.0:
        raise AssertionError(
            'premium completed_ratio %.4f != 1.0 under background burst'
            % prem_ratio)
    prem_ttft = [(e['first_token_t'] - e['arrival_t']) * 1e3
                 for e in events
                 if e['tenant'] == 'premium'
                 and e['first_token_t'] is not None]
    p99 = percentile(prem_ttft, 99) or 0.0
    rejected_events = sum(1 for e in events if e['outcome'] == 'rejected')
    if rejected_events != shed:
        raise AssertionError(
            'rejected wide events (%d) != shed handles (%d)'
            % (rejected_events, shed))
    base = {'trace': 'poisson+bg_burst', 'mean_gap_s': mean_gap,
            'requests': len(trace), 'new_tokens': mnt,
            'num_slots': num_slots, 'replicas': 2,
            'policy': 'least_loaded', 'bg_rate': bg_rate,
            'bg_doubles_at_s': round(t_mid, 4),
            'workload_spec': spec.hash, 'burst_spec': burst_spec.hash,
            'degraded': not on_tpu}
    return [
        dict(base, metric='serving_gateway_qos_premium_ttft_p99',
             value=round(p99, 3), unit='ms', slo_ttft_ms=slo_ms,
             slo_ok=bool(p99 <= slo_ms),
             premium_requests=len(premium)),
        dict(base, metric='serving_gateway_qos_shed_rate',
             value=round(shed_rate, 4), unit='ratio', shed=shed),
        dict(base, metric='serving_gateway_qos_premium_completed_ratio',
             value=round(prem_ratio, 4), unit='ratio',
             premium_requests=len(premium)),
    ]


def bench_serving_gateway_multimodel(on_tpu):
    """Multi-model serving rung (ISSUE 19): N models behind one
    2-replica gateway of ModelHost replicas, a zipf-mixed Poisson burst
    routed by model affinity, and a zero-downtime `rollout()` of the
    head model's weights fired MID-burst from the replay hook.

    Acceptance, asserted inline (a broken swap must fail the rung, not
    ship a row):
      * completed_ratio == 1.0 — every request before, during and
        after the weight swap finishes (drain-never-kill applied to
        weights instead of replicas);
      * per-model wide-event attribution matches the workload's model
        mix EXACTLY (the trace is the oracle for who asked for what);
      * the warm bring-up of the new version reports zero persistent
        compile-cache misses — same program shapes, new weights;
      * weight paging proof on a budgeted host: resident bytes never
        exceed the byte budget and the eviction counters match the LRU
        oracle replayed in plain python.
    """
    import paddle_tpu as paddle
    from paddle_tpu.capacity.replay import replay as replay_trace
    from paddle_tpu.framework import io_save
    from paddle_tpu.monitor.events import (RequestLog,
                                           set_default_request_log)
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    ModelAffinityRouter, ModelHost,
                                    ModelRegistry, ServingGateway)
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    import shutil
    import tempfile

    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block, num_slots = 256, 32, 8, 8
        mean_gap = 0.02
    else:
        # smaller than the other gateway rungs: the rung builds
        # n_models+1 engine instances, so weights are kept light
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 16, 24
        max_len, chunk, block, num_slots = 64, 32, 8, 8
        mean_gap = 0.002
    n_models, swap_frac = 3, 0.5
    swap_at = int(n_req * swap_frac)
    head = 'model_000'

    root = tempfile.mkdtemp(prefix='bench_registry_')
    try:
        # publish one distinctly-seeded artifact per model, plus the
        # head model's v2 (the weights the mid-burst rollout ships)
        reg = ModelRegistry(root=root)
        for i in range(n_models):
            paddle.seed(100 + i)
            m = GPTForCausalLM(cfg)
            reg.publish('model_%03d' % i, 'v1', m.state_dict())
        paddle.seed(200)
        reg.publish(head, 'v2', GPTForCausalLM(cfg).state_dict())
        nbytes = reg.entry(head, 'v1').nbytes

        def engine_for(entry):
            m = GPTForCausalLM(cfg)
            m.set_state_dict(io_save.load(entry.path))
            if on_tpu:
                m.bfloat16()
            m.eval()
            return ContinuousBatchingEngine(
                m, num_slots=num_slots, max_len=max_len,
                prefill_chunk=chunk, decode_block=block)

        spec = _serving_workload(
            n_req, lens, mnt, mean_gap, cfg.vocab_size)
        spec.models = {'mode': 'zipf', 'count': n_models}
        trace = spec.generate()

        def host_factory():
            # serving hosts get headroom: every model plus the rollout's
            # incoming version must be co-resident under load
            return ModelHost(reg, engine_for,
                             byte_budget=(n_models + 2) * nbytes,
                             max_len=max_len)

        log = RequestLog(capacity=4 * n_req)
        prev_log = set_default_request_log(log)
        try:
            mreg = MetricRegistry()
            gw = ServingGateway(host_factory, replicas=2, registry=mreg,
                                router=ModelAffinityRouter())
            t0c = time.time()
            gw.generate(trace.prompts()[:2], max_new_tokens=2,
                        model=head, tenant='warmup')          # compile
            t_cold = time.time() - t0c
            gw.start()
            rollout = {}

            def swap(i):
                if i == swap_at:
                    rollout.update(gw.rollout(head, 'v2'))

            res = replay_trace(gw, trace, max_new_tokens=mnt,
                               timeout=600, before_submit=swap)
            gw.shutdown()
            events = [e for e in log.events() if e['tenant'] != 'warmup']
        finally:
            set_default_request_log(prev_log)

        if res.completed_ratio != 1.0:
            raise AssertionError(
                'rollout lost requests: completed_ratio %.4f != 1.0'
                % res.completed_ratio)
        if not rollout or rollout.get('to_version') != 'v2':
            raise AssertionError('mid-burst rollout did not run: %r'
                                 % (rollout,))
        if int(rollout.get('cache_misses') or 0) > 0:
            raise AssertionError(
                'warm bring-up missed the compile cache: %r' % (rollout,))
        # the trace is the attribution oracle: wide events per model
        # must equal the workload's model mix exactly
        ev_mix = {}
        for e in events:
            ev_mix[e['model']] = ev_mix.get(e['model'], 0) + 1
        if ev_mix != trace.model_mix():
            raise AssertionError(
                'wide-event attribution %r != trace model mix %r'
                % (ev_mix, trace.model_mix()))

        # ---- weight paging proof: budget holds 2 of the 3 models ----
        pager = ModelHost(reg, engine_for,
                          byte_budget=2 * nbytes + nbytes // 2)
        oracle_resident, oracle_evicted = [], []
        max_resident = 0
        for i in list(range(n_models)) * 2:
            key = ('model_%03d' % i, 'v1')
            pager.load(*key)
            if key in oracle_resident:
                oracle_resident.remove(key)
            while len(oracle_resident) >= 2:
                oracle_evicted.append(oracle_resident.pop(0))
            oracle_resident.append(key)
            if pager.resident_bytes > pager.byte_budget:
                raise AssertionError(
                    'resident bytes %d exceed budget %d'
                    % (pager.resident_bytes, pager.byte_budget))
            max_resident = max(max_resident, len(pager.resident_models()))
        evictions = {
            'model_%03d' % i: int(pager._m_evictions.labels(
                model='model_%03d' % i).value())
            for i in range(n_models)}
        want = {'model_%03d' % i:
                sum(1 for k in oracle_evicted if k[0] == 'model_%03d' % i)
                for i in range(n_models)}
        if evictions != want:
            raise AssertionError('eviction counters %r != LRU oracle %r'
                                 % (evictions, want))
        pager.shutdown()

        base = {'trace': 'poisson', 'mean_gap_s': mean_gap,
                'requests': n_req, 'new_tokens': mnt,
                'num_slots': num_slots, 'replicas': 2,
                'n_models': n_models, 'swap_at': swap_frac,
                'policy': 'model_affinity', 'workload_spec': spec.hash,
                'degraded': not on_tpu}
        toks = sum(int(e['output_tokens'] or 0) for e in events)
        rows = [
            dict(base, metric='serving_gateway_multimodel_tokens_per_sec',
                 value=round(res.tokens_per_sec, 2), unit='tokens/sec',
                 compile_s_cold=round(t_cold, 3),
                 model_mix=trace.model_mix(), event_tokens=toks),
            dict(base,
                 metric='serving_gateway_multimodel_completed_ratio',
                 value=round(res.completed_ratio, 4), unit='ratio'),
            dict(base, metric='serving_gateway_rollout_warm_load_s',
                 value=round(float(rollout.get('load_s') or 0.0), 3),
                 unit='s', model=head,
                 cache_hits=int(rollout.get('cache_hits') or 0),
                 cache_misses=int(rollout.get('cache_misses') or 0)),
            dict(base, metric='registry_paging_evictions',
                 value=sum(evictions.values()), unit='count',
                 byte_budget=pager.byte_budget,
                 artifact_bytes=nbytes, max_models_resident=max_resident,
                 resident_bytes_final=pager.resident_bytes),
        ]
        return rows
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serving_fabric(on_tpu):
    """Serving-fabric rung (ISSUE 20): the gateway fronting REAL worker
    processes over the socket transport.

    Three measurements, each on a fresh 2-process worker pair:

    - clean Poisson burst tok/s (the cross-process tax vs the in-proc
      bench_serving_gateway rung is this row's whole point);
    - the same burst with one worker SIGKILLed mid-run — the chaos
      acceptance: completed_ratio must stay 1.0 (token parity of
      failed-over requests is pinned in tests/test_serving_fabric.py);
    - a shared-system-prompt workload routed by LeastLoaded vs the
      gateway's PrefixAffinityRouter over paged workers: the prefix
      directory's hit-rate win is the tracked value.

    Rows are keyed by transport/n_procs (+ policy for the router pair)
    in the regression gate's aux config.
    """
    from paddle_tpu.capacity import workload
    from paddle_tpu.capacity.replay import replay as replay_trace
    from paddle_tpu.monitor import events as _events
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import ServingGateway
    from paddle_tpu.serving.fabric import (PrefixAffinityRouter,
                                           SocketReplica, spawn_worker)

    n_procs = 2
    vocab = 211                      # the preset zoo's vocab
    # prompt + 16 new tokens must fit the gpt-nano preset's max_len=32
    spec = _serving_workload(16, (4, 8, 12, 14), 16, 0.002, vocab)
    trace = spec.generate()
    prompts = trace.prompts()

    def fabric_gateway(handles, router=None):
        gw = ServingGateway(None, replicas=0, router=router,
                            registry=MetricRegistry())
        for h in handles:
            gw.adopt_replica(SocketReplica(
                h.endpoint, metrics_url=h.metrics_url,
                poll_interval=0.002).connect())
        return gw

    def drive(preset, wl_trace, mnt, kill_at=None, router=None):
        handles = [spawn_worker(preset=preset) for _ in range(n_procs)]
        log = _events.RequestLog(capacity=4096)
        prev = _events.set_default_request_log(log)
        try:
            gw = fabric_gateway(handles, router=router)
            t0c = time.time()
            gw.generate(wl_trace.prompts()[:n_procs],
                        max_new_tokens=2)             # compile workers
            t_cold = time.time() - t0c
            log.clear()
            gw.start()
            kill_i = None if kill_at is None else \
                int(len(wl_trace) * kill_at)

            def maybe_kill(i):
                if kill_i is not None and i == kill_i:
                    handles[0].kill()                 # SIGKILL, no drain

            res = replay_trace(gw, wl_trace, max_new_tokens=mnt,
                               timeout=600, before_submit=maybe_kill)
            failovers = int(gw.registry.get(
                'gateway_failover_total').value())
            gw.shutdown()
            evs = log.events()
            hit = sum(e.get('prefix_hit_tokens') or 0 for e in evs)
            prompt_toks = sum(e.get('prompt_tokens') or 0 for e in evs)
            return (res, failovers, t_cold,
                    hit / prompt_toks if prompt_toks else 0.0)
        finally:
            _events.set_default_request_log(prev)
            for h in handles:
                h.cleanup()

    base = {'unit': 'tokens/sec', 'trace': 'poisson',
            'transport': 'socket', 'n_procs': n_procs, 'requests': 16,
            'new_tokens': 16, 'policy': 'least_loaded',
            'workload_spec': spec.hash, 'degraded': not on_tpu}
    rows = []
    res, fo, t_cold, _ = drive('gpt-nano', trace, 16)
    rows.append(dict(base, metric='serving_fabric_tokens_per_sec',
                     value=round(res.tokens_per_sec, 2), kill_at='none',
                     failovers=fo, compile_s_cold=round(t_cold, 3),
                     completed_ratio=round(res.completed_ratio, 4)))
    res, fo, t_cold, _ = drive('gpt-nano', trace, 16, kill_at=0.5)
    rows.append(dict(base, metric='serving_fabric_tokens_per_sec_chaos',
                     value=round(res.tokens_per_sec, 2), kill_at=0.5,
                     failovers=fo, compile_s_cold=round(t_cold, 3),
                     completed_ratio=round(res.completed_ratio, 4)))
    rows.append(dict(base, metric='serving_fabric_completed_ratio',
                     value=round(res.completed_ratio, 4), unit='ratio',
                     kill_at=0.5, failovers=fo))

    # shared-system-prompt workload over paged workers: 90% of requests
    # share a 24-token system prefix (3 pages at the preset's page
    # size 8) in 4 groups — more groups than replicas, so least-loaded
    # pays a cold miss per (group, replica) pair while affinity pays
    # one per group; short tails keep the prefix dominant. Max prompt
    # 24 + 8 = 32, + 8 new tokens fits gpt-nano-paged's max_len=64.
    pspec = workload.WorkloadSpec(
        requests=24, seed=1, vocab_size=vocab,
        arrival={'process': 'poisson', 'mean_gap_s': 0.002},
        lengths={'dist': 'ladder', 'lens': [4, 8]},
        output={'dist': 'fixed', 'len': 8},
        prefix={'len': 24, 'groups': 4, 'prob': 0.9})
    ptrace = pspec.generate()
    for router, policy in ((None, 'least_loaded'),
                           (PrefixAffinityRouter(page_size=8),
                            'prefix_affinity')):
        res, _, _, hit_rate = drive('gpt-nano-paged', ptrace, 8,
                                    router=router)
        rows.append(dict(base, metric='serving_fabric_prefix_hit_rate',
                         value=round(hit_rate, 4), unit='ratio',
                         policy=policy, kill_at='none', requests=24,
                         new_tokens=8, workload_spec=pspec.hash,
                         tokens_per_sec=round(res.tokens_per_sec, 2),
                         completed_ratio=round(res.completed_ratio, 4)))
    return rows


def bench_supervisor_recovery(on_tpu):
    """Elastic-supervisor MTTR rung (ISSUE 14): a journaled PS shard is
    snapshotted, hard-killed, and recovered by the ShardSupervisor
    (restart on the same endpoint -> restore newest snapshot -> replay
    the client journal). The value is the recover() walltime — liveness
    miss to shard serving restored state — which the regression gate
    checks LOWER-is-better ('mttr' in the metric name). Exactly-once is
    asserted inline: the replayed rows must match the pre-kill state
    bit-for-bit, with dedup hits covering every snapshot-covered entry.
    """
    import os
    import tempfile
    from paddle_tpu.distributed.ps.embedding_service import (
        EmbeddingClient, EmbeddingServer)
    from paddle_tpu.distributed.supervisor import (PushJournal, ShardSpec,
                                                   ShardSupervisor)
    from paddle_tpu.testing import chaos

    dim, n_ids, pushes = 16, 256, 8
    snap_dir = tempfile.mkdtemp(prefix='bench_sup_')

    def make_server(port=0):
        s = EmbeddingServer(port=port)
        s.create_table(0, dim=dim, optimizer='sgd', lr=0.1)
        s.start()
        return s

    srv = make_server()
    port = srv.port
    journal = PushJournal('bench-trainer')
    cli = EmbeddingClient(endpoints=['127.0.0.1:%d' % port],
                          journal=journal)
    rng = np.random.RandomState(0)
    ids = list(range(n_ids))
    cli.pull(0, ids)
    for _ in range(pushes):
        cli.push(0, ids, rng.randn(n_ids, dim).astype(np.float32))

    sup = ShardSupervisor(miss_threshold=1, restart_budget=3,
                          ping_timeout=0.5)
    sup.add_shard(ShardSpec('emb0', '127.0.0.1:%d' % port, role='ps',
                            restart=lambda: make_server(port) and None,
                            snapshot_dir=snap_dir, clients=(cli,)))
    sup.snapshot_all()
    # post-snapshot writes: the recovery must replay exactly these
    for _ in range(2):
        cli.push(0, ids, rng.randn(n_ids, dim).astype(np.float32))
    want = cli.pull(0, ids)

    chaos.kill_server(srv)
    t0 = time.time()
    sup.poll()                      # detects the miss and recovers
    mttr = time.time() - t0
    got = cli.pull(0, ids)
    if not np.array_equal(want, got):
        raise AssertionError('recovered shard state diverged')

    return [{'metric': 'supervisor_mttr_seconds', 'value': round(mttr, 4),
             'unit': 's', 'shard': 'embedding', 'rows': n_ids,
             'journal_replayed': journal.replayed,
             'journal_dedup_hits': journal.dedup_hits,
             'degraded': not on_tpu}]


def bench_capacity_calibration(on_tpu):
    """Capacity-simulator calibration rung (ISSUE 16): replay a small
    Poisson trace through a real 1-replica in-proc gateway, fit the
    two-parameter service model from its wide events, re-run the SAME
    trace through the discrete-event simulator, and report the TTFT
    divergence (max of p50/p99 relative error — the regression gate
    checks it LOWER-is-better; K-S statistic rides along as a field).

    A second, ungated-by-measurement row answers the acceptance
    question directly: a million-request synthetic sweep under a PINNED
    service model (so the reported minimum-replica answer is
    deterministic run to run), with the measured model's answer as an
    informational field.
    """
    import paddle_tpu as paddle
    from paddle_tpu.capacity import simulator, workload
    from paddle_tpu.capacity.replay import measure as replay_measure
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        dropout=0.0)
        lens, mnt, n_req = (32, 64, 96, 128), 64, 32
        max_len, chunk, block, num_slots = 256, 32, 8, 8
        mean_gap = 0.02
    else:
        # the bench_serving CPU regime: decode-GEMM-bound, service-bound
        cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=128,
                        dropout=0.0)
        lens, mnt, n_req = (8, 16, 24, 32), 32, 24
        max_len, chunk, block, num_slots = 64, 32, 8, 8
        mean_gap = 0.002
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    spec = _serving_workload(n_req, lens, mnt, mean_gap, cfg.vocab_size)
    trace = spec.generate()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=num_slots, max_len=max_len,
            prefill_chunk=chunk, decode_block=block)

    reg = MetricRegistry()
    real_events, res = replay_measure(
        factory, trace, replicas=1, max_new_tokens=mnt, registry=reg)
    fitted = simulator.ServiceModel.from_events(
        real_events, prefill_chunk=chunk, decode_block=block,
        num_slots=num_slots, trace=trace, replicas=1)
    sim = simulator.simulate(trace, fitted, replicas=1,
                             router='least_loaded', registry=reg)
    div = simulator.compare_events(sim.to_events(), real_events)['overall']
    rows = [{'metric': 'capacity_sim_ttft_divergence',
             'value': round(max(div['p50_rel_err'], div['p99_rel_err']), 4),
             'unit': 'rel_err', 'trace': 'poisson',
             'mean_gap_s': mean_gap, 'requests': n_req,
             'new_tokens': mnt, 'num_slots': num_slots, 'replicas': 1,
             'workload_spec': spec.hash,
             'ks': round(div['ks'], 4),
             'p50_rel_err': round(div['p50_rel_err'], 4),
             'p99_rel_err': round(div['p99_rel_err'], 4),
             'sim_p50_ms': round(div['sim_p50_s'] * 1e3, 3),
             'real_p50_ms': round(div['real_p50_s'] * 1e3, 3),
             'sim_p99_ms': round(div['sim_p99_s'] * 1e3, 3),
             'real_p99_ms': round(div['real_p99_s'] * 1e3, 3),
             'service_model': fitted.to_dict(),
             'replay_tokens_per_sec': round(res.tokens_per_sec, 2),
             'degraded': not on_tpu}]

    # million-request sweep under a pinned model: the reported
    # minimum-replica answer must be deterministic for the gate
    big = workload.WorkloadSpec(
        requests=1000000, seed=0,
        arrival={'process': 'diurnal', 'mean_gap_s': 0.0005,
                 'period_s': 120.0, 'peak_to_trough': 4.0},
        lengths={'dist': 'zipf', 'a': 1.8, 'min': 8, 'max': 256},
        output={'dist': 'lognormal', 'median': 12, 'sigma': 0.5,
                'min': 1, 'max': 64},
        tenants={'mode': 'zipf', 'count': 20, 'a': 1.5})
    pinned = simulator.ServiceModel(0.002, 0.004, prefill_chunk=chunk,
                                    decode_block=block,
                                    num_slots=num_slots)
    sweep = simulator.sweep_replicas(big.generate(), pinned,
                                     counts=(8, 16, 32), slo_ttft_s=0.25)
    measured_min = simulator.sweep_replicas(
        trace, fitted, counts=(1, 2, 4),
        slo_ttft_s=10 * div['real_p99_s'])['min_replicas']
    rows.append({'metric': 'capacity_sweep_min_replicas',
                 'value': sweep['min_replicas'], 'unit': 'replicas',
                 'requests': sweep['requests'],
                 'slo_ttft_s': sweep['slo_ttft_s'],
                 'workload_spec': big.hash,
                 'sweep_points': sweep['points'],
                 'sweep_wall_s': round(sum(p['sim_wall_s']
                                           for p in sweep['points']), 3),
                 'measured_model_min_replicas': measured_min,
                 'service_model': pinned.to_dict(),
                 'degraded': not on_tpu})
    return rows


def bench_ingest(on_tpu):
    """Streaming-ingestion rung (ISSUE 18): the async double-buffered
    IngestPipeline vs the repo's synchronous baseline — io.DataLoader
    doing sampler-driven random access over the SAME disk-resident
    shard set — feeding an identical device step.

    The baseline is what training disk-resident data looked like before
    the ingestion plane: DataLoader(shuffle=True) indexes records one at
    a time (ShardReader.at pays the strided seek + skip every access)
    and nothing overlaps the step. The pipeline streams shards
    sequentially, window-shuffles, and prefetches batch k+1 while step
    k runs. The device step is calibrated to the pipeline's measured
    producer cost (the balance point where overlap matters most) and
    emulated host-idle on CPU (time.sleep — a dispatched TPU step keeps
    the host free, which one CPU core cannot also fake with real
    compute); on TPU it is a real jitted matmul stack.

    Gated rows: ingest_examples_per_sec (async, higher-is-better) with
    the DataLoader-sync and pipeline-sync numbers + speedups as fields,
    and ingest_data_wait_frac (async, lower-is-better) with the sync
    fraction alongside — near-zero async data_wait is the point.
    """
    import bisect
    import shutil
    import tempfile
    import jax
    import jax.numpy as jnp
    from paddle_tpu.data import write_shards, IngestPipeline
    from paddle_tpu.data.shards import ShardReader, decode_sample
    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.monitor.registry import MetricRegistry

    if on_tpu:
        n_records, n_shards, batch, dim, window = 65536, 8, 512, 256, 4096
    else:
        n_records, n_shards, batch, dim, window = 8192, 4, 256, 128, 1024
    tmp = tempfile.mkdtemp(prefix='bench_ingest_')
    try:
        rng = np.random.RandomState(0)
        paths = write_shards(
            ({'x': rng.randn(dim).astype(np.float32),
              'y': np.int64(i % 10)} for i in range(n_records)),
            tmp, n_shards)

        class ShardDataset(Dataset):
            """Random-access view the synchronous baseline indexes."""

            def __init__(self):
                self.readers = [ShardReader(p, decode=decode_sample)
                                for p in paths]
                self.cum = list(np.cumsum([r.records
                                           for r in self.readers]))

            def __len__(self):
                return self.cum[-1]

            def __getitem__(self, i):
                s = bisect.bisect_right(self.cum, i)
                return self.readers[s].at(
                    i - (self.cum[s - 1] if s else 0))

        def pipeline(prefetch):
            return IngestPipeline(paths, batch_size=batch,
                                  shuffle_window=window, seed=0,
                                  prefetch=prefetch, device_put=on_tpu,
                                  registry=MetricRegistry())

        # producer-only epoch: read + decode + shuffle + collate — the
        # per-batch input cost, which also calibrates the device step
        p = pipeline(0)
        t0 = time.time()
        n_batches = sum(1 for _ in p)
        step_s = (time.time() - t0) / max(n_batches, 1)

        if on_tpu:
            w = jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.01)

            @jax.jit
            def unit_step(x, w):
                return jnp.tanh(x @ w).sum()

            x0 = jnp.zeros((batch, dim), jnp.float32)
            unit_step(x0, w).block_until_ready()        # compile
            t0 = time.time()
            for _ in range(8):
                unit_step(x0, w).block_until_ready()
            repeats = max(1, int(round(step_s * 8 / (time.time() - t0))))

            def device_step(b):
                for _ in range(repeats):
                    out = unit_step(b['x']._data, w)
                out.block_until_ready()
        else:
            repeats = 0

            def device_step(b):
                time.sleep(step_s)

        def drive_pipeline(prefetch):
            pipe = pipeline(prefetch)
            t0 = time.time()
            for b in pipe:
                device_step(b)
            wall = time.time() - t0
            return n_records / wall, pipe.last_epoch_stats[
                'data_wait_frac'], wall

        def drive_dataloader():
            loader = DataLoader(ShardDataset(), batch_size=batch,
                                shuffle=True, num_workers=0)
            t0 = time.time()
            wait = 0.0
            it = iter(loader)
            while True:
                w0 = time.time()
                try:
                    b = next(it)
                except StopIteration:
                    break
                wait += time.time() - w0
                device_step(b)
            wall = time.time() - t0
            return n_records / wall, wait / wall, wall

        drive_pipeline(0)                               # warm the path
        dl_eps, dl_wait, dl_wall = drive_dataloader()
        sync_eps, sync_wait, sync_wall = drive_pipeline(0)
        async_eps, async_wait, async_wall = drive_pipeline(2)

        base = {'unit': 'examples/sec', 'records': n_records,
                'shards': n_shards, 'batch': batch, 'dim': dim,
                'shuffle_window': window, 'prefetch': 2,
                'baseline': 'random_access_dataloader',
                'step_s': round(step_s, 6), 'step_repeats': repeats,
                'degraded': not on_tpu}
        return [
            dict(base, metric='ingest_examples_per_sec',
                 value=round(async_eps, 2),
                 dataloader_sync_examples_per_sec=round(dl_eps, 2),
                 pipeline_sync_examples_per_sec=round(sync_eps, 2),
                 speedup_vs_dataloader=round(async_eps / dl_eps, 3),
                 speedup_vs_pipeline_sync=round(async_eps / sync_eps, 3),
                 async_wall_s=round(async_wall, 4),
                 sync_wall_s=round(sync_wall, 4),
                 dataloader_wall_s=round(dl_wall, 4),
                 # rides on the throughput row so perf_report's bench
                 # table surfaces input-boundedness alongside examples/s
                 data_wait_frac=round(async_wait, 4)),
            dict(base, metric='ingest_data_wait_frac',
                 value=round(async_wait, 4), unit='ratio',
                 pipeline_sync_data_wait_frac=round(sync_wait, 4),
                 dataloader_data_wait_frac=round(dl_wait, 4)),
        ]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    import jax
    from paddle_tpu.framework import compile_cache
    compile_cache.configure()
    on_tpu = jax.devices()[0].platform == 'tpu'
    failed = None
    for fn in (bench_resnet, bench_yolo_infer, bench_gpt_decode,
               bench_serving, bench_serving_paged, bench_serving_gateway,
               bench_serving_gateway_tenants, bench_serving_gateway_qos,
               bench_serving_gateway_multimodel, bench_serving_fabric,
               bench_supervisor_recovery, bench_capacity_calibration,
               bench_ingest):
        try:
            res = fn(on_tpu)
            for row in (res if isinstance(res, list) else [res]):
                print(json.dumps(row))
        except Exception as e:  # the other rungs still run; exit is honest
            print(json.dumps({'metric': fn.__name__, 'error': repr(e)[:300]}))
            failed = failed or e
    if failed is not None:
        raise failed


if __name__ == '__main__':
    main()
