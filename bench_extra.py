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


def bench_serving_fabric(on_tpu):
    """Serving-fabric rung (ISSUE 20): the gateway fronting REAL worker
    processes over the socket transport.

    Three measurements, each on a fresh 2-process worker pair:

    - clean Poisson burst tok/s over the socket transport;
    - the same burst with one worker SIGKILLed mid-run — the chaos
      acceptance: completed_ratio must stay 1.0 (token parity of
      failed-over requests is pinned in tests/test_serving_fabric.py);
    - a shared-system-prompt workload routed by LeastLoaded vs the
      gateway's PrefixAffinityRouter: the prefix
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

    # shared-system-prompt workload: 90% of requests
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
               bench_serving_fabric, bench_supervisor_recovery,
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
