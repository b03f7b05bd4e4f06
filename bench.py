"""Benchmark: BERT-base-equivalent causal-LM training throughput on 1 chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Metric: samples/sec/chip on a BERT-base-sized (110M-param-class) transformer
training step (fwd+bwd+AdamW), seq 512, bf16 compute — BASELINE.json
config-3 family. vs_baseline is measured MFU vs the 50% north-star target
(reference publishes no absolute numbers; BASELINE.md).

The measurement runs in this process, on the accelerator jax finds, and the
row names it (platform, device_kind, device_count). Without a TPU the script
exits non-zero and prints no row: there is no CPU fallback, no retry with
the flash kernel off and no replay of an older capture. A failure is a
traceback and a non-zero exit code. For the quickest proof that the program
starts on the chip at all, see chip_smoke.py.
"""
import json
import os
import sys
import time

# the north-star target (BASELINE.md config 3): vs_baseline = mfu_6n / this
_BASELINE_MFU = 0.50


def main():
    """The measurement, in this process. Prints one JSON line."""
    import jax
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('bench.py measures on a TPU and jax found %s (%s): no row '
                 'is printed for another backend.'
                 % (device.platform, device.device_kind))
    from paddle_tpu.framework import compile_cache
    compile_cache.configure()

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.framework import functional as func_mod

    paddle.seed(0)
    # seq override: long-context runs (seq 2048/8192) ride the same harness
    seq = int(os.environ.get('PADDLE_TPU_BENCH_SEQ', 512))
    # fused head+CE (ops/fused_ce.py): never materializes [B*S, vocab]
    # logits (~13 ms/step of vocab-tensor HBM traffic in the 2026-08-01
    # profile, docs/profile_summary_r5.txt)
    fused_ce = os.environ.get('PADDLE_TPU_FUSED_CE', '1') != '0'
    # a shape the Pallas flash kernel cannot take raises instead of routing
    # to blockwise (flash_attention.unsupported_reason), and the jaxpr
    # assertion below proves the pallas_call is in the measured program
    os.environ.setdefault('PADDLE_TPU_FLASH_STRICT', '1')
    cfg = GPTConfig(vocab_size=30528, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=seq,
                    dropout=0.0, fused_loss=fused_ce)
    batch = int(os.environ.get('PADDLE_TPU_BENCH_BATCH', 32))
    steps = int(os.environ.get('PADDLE_TPU_BENCH_STEPS', 30))

    model = GPTForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return model.loss(logits, labels)

    remat = os.environ.get('PADDLE_TPU_BENCH_REMAT', '0') == '1'
    step = func_mod.TrainStep(model, loss_fn, opt, remat=remat)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    # the measured program must contain the Pallas flash kernel — with
    # strict mode this makes a "flash" number that didn't run flash
    # impossible (PADDLE_TPU_FLASH_DISABLE=1 reports flash_in_program=false)
    flash_in_program = 'pallas_call' in step.trace_jaxpr(ids, labels)
    if not flash_in_program and \
            os.environ.get('PADDLE_TPU_FLASH_DISABLE') != '1':
        raise RuntimeError('flash pallas_call absent from the step jaxpr')

    # device training loop: K steps per dispatch via lax.scan
    # (TrainStep.multi_step) amortizes per-dispatch host time K-fold
    scan_k = int(os.environ.get('PADDLE_TPU_BENCH_SCAN_STEPS', '0'))

    # warmup/compile, then the CompileWatchdog arms: a recompile inside
    # the measured window invalidates the number, and gets reported
    from paddle_tpu.monitor.perf import CompileWatchdog, costmodel
    wd = CompileWatchdog(strict=False, name='bench')
    warmup = int(os.environ.get('PADDLE_TPU_BENCH_WARMUP', 15))
    if scan_k > 1:
        import numpy as _np
        ids_k = paddle.to_tensor(_np.broadcast_to(
            ids.numpy(), (scan_k,) + tuple(ids.shape)).copy())
        labels_k = paddle.to_tensor(_np.broadcast_to(
            labels.numpy(), (scan_k,) + tuple(labels.shape)).copy())
        t_cold = time.time()
        losses = step.multi_step(ids_k, labels_k)
        _ = losses.numpy()
        compile_s_cold = time.time() - t_cold
        # warm at least 3 dispatches regardless of K
        for _ in range(max(3, -(-warmup // scan_k))):
            losses = step.multi_step(ids_k, labels_k)
        _ = losses.numpy()
    else:
        t_cold = time.time()
        loss = step(ids, labels)
        _ = loss.numpy()
        compile_s_cold = time.time() - t_cold
        for _ in range(warmup):
            loss = step(ids, labels)
        _ = loss.numpy()
    wd.declare_warmup('bench warmup done')

    profile_dir = os.environ.get('PADDLE_TPU_BENCH_PROFILE')
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    # per-dispatch variance view (costs one host fetch per dispatch, so
    # it is opt-in; the headline number keeps the single end-of-loop fetch)
    per_dispatch = os.environ.get('PADDLE_TPU_BENCH_PER_DISPATCH') == '1'
    dispatch_ms = []
    t0 = last = time.time()
    if scan_k > 1:
        n_dispatch = max(1, steps // scan_k)
        for _ in range(n_dispatch):
            losses = step.multi_step(ids_k, labels_k)
            if per_dispatch:
                _ = losses.numpy()
                now = time.time()
                dispatch_ms.append(round(1000 * (now - last), 2))
                last = now
        _ = losses.numpy()
        steps = scan_k * n_dispatch
    else:
        for _ in range(steps):
            loss = step(ids, labels)
            if per_dispatch:
                _ = loss.numpy()
                now = time.time()
                dispatch_ms.append(round(1000 * (now - last), 2))
                last = now
        _ = loss.numpy()
    dt = time.time() - t0
    if profile_dir:
        jax.profiler.stop_trace()
    recompiles = wd.recompiles
    wd.close()
    # persistent-cache effectiveness of THIS process's compiles: 1.0 on
    # a fully warmed cache, ~0 on a fresh one
    cache_hit_rate = compile_cache.hit_rate()

    # cost-model block: analytic FLOPs/bytes of the single-step program
    # (per-step numbers even under scan), plus a warm compile time — the
    # second lower+compile resolves through the compilation cache, so it
    # measures the cache-hit path, not XLA
    compiled = step.compiled_executable(ids, labels)
    t_warm = time.time()
    step.compiled_executable(ids, labels)
    compile_s_warm = time.time() - t_warm
    perf_est = costmodel.estimate(compiled, step_seconds=dt / steps)

    samples_per_sec = batch * steps / dt
    n_params = model.num_params()
    # MFU counts the model's actual matmul flops: 6N per token PLUS the
    # attention quadratic term (12*L*h*s per token) — the PaLM-appendix-B
    # convention. mfu_6n (params-only) is reported alongside for
    # comparability with earlier rounds' captures.
    flops_per_step = float(model.flops_per_token(seq)) * batch * seq
    flops_6n_per_step = 6.0 * n_params * batch * seq
    # this chip's published bf16 peak, by device_kind (an unknown kind
    # raises: an MFU against another chip's peak is a wrong number)
    peak = costmodel.platform_peaks()[1]
    mfu = flops_per_step * steps / dt / peak
    mfu_6n = flops_6n_per_step * steps / dt / peak

    print(json.dumps({
        'metric': 'bert_base_lm_train_samples_per_sec_per_chip',
        'value': round(samples_per_sec, 3),
        'unit': 'samples/sec/chip',
        # vs_baseline stays in the 6N convention every earlier capture
        # used — the conservative number; 'mfu' (with attention flops,
        # PaLM convention) is reported alongside
        'vs_baseline': round(mfu_6n / _BASELINE_MFU, 4),
        'mfu': round(mfu, 4),
        'mfu_6n': round(mfu_6n, 4),
        'step_ms': round(1000.0 * dt / steps, 2),
        'batch': batch,
        'seq': seq,
        'flash_in_program': flash_in_program,
        'fused_ce': fused_ce,
        'scan_steps': scan_k,
        'attn_impl': os.environ.get('PADDLE_TPU_ATTN_IMPL', 'auto'),
        'qkv_split': os.environ.get('PADDLE_TPU_QKV_SPLIT', 'headaxis'),
        'fused_ce_chunk': _fce_chunk(),
        # effective flash knobs from the ONE defaults table (the same
        # resolve() the kernel module latches at import)
        **{'flash_%s' % k: v for k, v in _flash_knobs().items()},
        **({'blockwise_block': int(os.environ['PADDLE_TPU_BLOCKWISE_BLOCK'])}
           if 'PADDLE_TPU_BLOCKWISE_BLOCK' in os.environ else {}),
        'platform': device.platform,
        'device_kind': device.device_kind,
        'device_count': len(jax.devices()),
        'compile_s_cold': round(compile_s_cold, 3),
        'compile_s_warm': round(compile_s_warm, 3),
        'recompiles': recompiles,
        **({'compile_cache_hit_rate': round(cache_hit_rate, 4)}
           if cache_hit_rate is not None else {}),
        **({'mfu_est': round(perf_est['mfu_est'], 4),
            'arithmetic_intensity':
                round(perf_est['arithmetic_intensity'], 2),
            'roofline_bound': perf_est['roofline_bound']}
           if perf_est and 'mfu_est' in perf_est else {}),
        **({'dispatch_ms': dispatch_ms} if dispatch_ms else {}),
    }))


def _flash_defaults_mod():
    """Load ops/flash_defaults.py WITHOUT importing the paddle_tpu
    package: tools/check_bench_regression.py imports this module for the
    capture-row helpers below and must not pull in jax for that."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'paddle_tpu', 'ops', 'flash_defaults.py')
    spec = importlib.util.spec_from_file_location('_flash_defaults', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _flash_knobs():
    return _flash_defaults_mod().resolve()


def _fce_chunk():
    from paddle_tpu.ops.fused_ce import env_chunk_rows
    return env_chunk_rows()


def _capture_replay_env(entry):
    """Map a capture row back to the FULL knob env that produced it, every
    knob pinned in BOTH directions. tools/check_bench_regression.py
    buckets rows by this (with _effective_env), so a legacy row with
    unstated knobs and a new row spelling out today's defaults compare as
    the same config. Pure function (unit-tested)."""
    env = {
        'PADDLE_TPU_BENCH_SCAN_STEPS':
            str(int(entry.get('scan_steps') or 0)),
        'PADDLE_TPU_FUSED_CE': '1' if entry.get('fused_ce') else '0',
        'PADDLE_TPU_QKV_SPLIT': str(entry.get('qkv_split') or 'headaxis'),
        'PADDLE_TPU_ATTN_IMPL': str(entry.get('attn_impl') or 'auto'),
        # rows from before a knob existed must replay at the value that
        # era's code actually used, NOT today's default — legacy fwd
        # blocks were 256/512, the legacy long path reused the fwd
        # blocks, and the legacy router was '> 4096' (= today's
        # '>= 4097')
        'PADDLE_TPU_FLASH_BLOCK_Q':
            str(int(entry.get('flash_block_q') or 256)),
        'PADDLE_TPU_FLASH_BLOCK_K':
            str(int(entry.get('flash_block_k') or 512)),
        'PADDLE_TPU_FLASH_BLOCK_Q_BWD':
            str(int(entry.get('flash_block_q_bwd')
                    or entry.get('flash_block_q') or 256)),
        'PADDLE_TPU_FLASH_BLOCK_K_BWD':
            str(int(entry.get('flash_block_k_bwd')
                    or entry.get('flash_block_k') or 512)),
        'PADDLE_TPU_FLASH_BLOCK_Q_LONG':
            str(int(entry.get('flash_block_q_long')
                    or entry.get('flash_block_q') or 256)),
        'PADDLE_TPU_FLASH_BLOCK_K_LONG':
            str(int(entry.get('flash_block_k_long')
                    or entry.get('flash_block_k') or 512)),
        'PADDLE_TPU_FLASH_LONG_SEQ':
            str(int(entry.get('flash_long_seq') or 4097)),
        # rows predating the fused-backward kernel ran the two-pass path
        'PADDLE_TPU_FLASH_FUSED_BWD':
            '1' if entry.get('flash_fused_bwd') else '0',
    }
    if entry.get('flash_in_program'):
        env['PADDLE_TPU_FLASH_DISABLE'] = '0'
        env['PADDLE_TPU_FLASH_STRICT'] = '1'
    else:
        env['PADDLE_TPU_FLASH_DISABLE'] = '1'
        env['PADDLE_TPU_FLASH_STRICT'] = '0'
    chunk = entry.get('fused_ce_chunk')
    if chunk and entry.get('fused_ce'):
        env['PADDLE_TPU_FUSED_CE_CHUNK'] = str(int(chunk))
    if entry.get('blockwise_block'):
        env['PADDLE_TPU_BLOCKWISE_BLOCK'] = \
            str(int(entry['blockwise_block']))
    if entry.get('batch'):
        env['PADDLE_TPU_BENCH_BATCH'] = str(int(entry['batch']))
    if entry.get('seq'):
        env['PADDLE_TPU_BENCH_SEQ'] = str(int(entry['seq']))
    return env


# the effective defaults for every recorded knob — used to compare knob
# envs as COMPLETE configs, so two env dicts that differ only in unstated
# defaults still compare equal
_KNOB_DEFAULTS = {
    'PADDLE_TPU_BENCH_SCAN_STEPS': '0',
    'PADDLE_TPU_FUSED_CE': '1',
    'PADDLE_TPU_FUSED_CE_CHUNK': '4096',
    'PADDLE_TPU_QKV_SPLIT': 'headaxis',
    'PADDLE_TPU_ATTN_IMPL': 'auto',
    # flash knobs: one source of truth (ops/flash_defaults.py)
    **{'PADDLE_TPU_FLASH_%s' % k.upper(): str(v)
       for k, v in (lambda d: {
           'BLOCK_Q': d.BLOCK_Q, 'BLOCK_K': d.BLOCK_K,
           'BLOCK_Q_BWD': d.BLOCK_Q, 'BLOCK_K_BWD': d.BLOCK_K,
           'BLOCK_Q_LONG': d.BLOCK_Q_LONG, 'BLOCK_K_LONG': d.BLOCK_K_LONG,
           'LONG_SEQ': d.LONG_SEQ,
           'FUSED_BWD': '1' if d.FUSED_BWD else '0',
           })(_flash_defaults_mod()).items()},
    'PADDLE_TPU_FLASH_DISABLE': '0',
    'PADDLE_TPU_FLASH_STRICT': '1',
    'PADDLE_TPU_BENCH_BATCH': '32',
    'PADDLE_TPU_BENCH_SEQ': '512',
}


def _effective_env(extra):
    """Complete a partial knob-env dict with the knob defaults."""
    eff = dict(_KNOB_DEFAULTS)
    eff.update(extra or {})
    # the bwd blocks inherit the (possibly overridden) fwd blocks when
    # unset — mirror the kernel's env contract so two spellings of the
    # same effective config compare equal
    if 'PADDLE_TPU_FLASH_BLOCK_Q_BWD' not in (extra or {}):
        eff['PADDLE_TPU_FLASH_BLOCK_Q_BWD'] = eff['PADDLE_TPU_FLASH_BLOCK_Q']
    if 'PADDLE_TPU_FLASH_BLOCK_K_BWD' not in (extra or {}):
        eff['PADDLE_TPU_FLASH_BLOCK_K_BWD'] = eff['PADDLE_TPU_FLASH_BLOCK_K']
    return eff


def _inwindow_log_paths():
    """The committed builder-capture logs (v5e, 2026-08-01 and earlier):
    the stored bests tools/check_bench_regression.py gates against when
    no --baseline is given. Override with
    PADDLE_TPU_BENCH_INWINDOW_LOG."""
    override = os.environ.get('PADDLE_TPU_BENCH_INWINDOW_LOG')
    if override:
        return [override]
    docs = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'docs')
    return [os.path.join(docs, 'bench_inwindow_r5.jsonl'),
            os.path.join(docs, 'bench_inwindow_r4.jsonl')]


if __name__ == '__main__':
    main()
