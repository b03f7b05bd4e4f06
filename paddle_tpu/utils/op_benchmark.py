"""Op micro-benchmark harness + perf regression gate (reference:
operators/benchmark/op_tester.cc config-driven op timing,
tools/test_op_benchmark.sh + check_op_benchmark_result.py CI gate).

Usage:
    python -m paddle_tpu.utils.op_benchmark --out ops.json
    python -m paddle_tpu.utils.op_benchmark --out new.json \
        --baseline ops.json --threshold 0.15   # fails on >15% regressions

Each config is (name, builder) where builder() returns (fn, args): fn is
jitted once, timed over `repeat` runs with block_until_ready — the XLA
replacement for op_tester's per-op timing loop. The default suite covers
the ops the bench model leans on (matmul/flash-attention/layernorm/CE),
so a kernel regression is localizable without rerunning the full model
bench.
"""
import argparse
import json
import time

import numpy as np

__all__ = ['OP_CONFIGS', 'run_benchmarks', 'compare', 'main']


def _matmul(m=1024, k=1024, n=1024, dtype='bfloat16'):
    import jax.numpy as jnp
    a = jnp.asarray(np.random.RandomState(0).randn(m, k), dtype)
    b = jnp.asarray(np.random.RandomState(1).randn(k, n), dtype)
    return lambda a, b: a @ b, (a, b)


def _flash_attention(b=4, h=12, n=512, d=64, causal=True):
    import jax.numpy as jnp
    from ..ops import flash_attention as fa
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    return (lambda q, k, v: fa.flash_attention_bhnd(q, k, v, causal=causal),
            (q, k, v))


def _sdpa_ref(b=4, h=12, n=512, d=64):
    import jax.numpy as jnp
    from ..ops.flash_attention import _ref_bhnd
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, n, d) * 0.2, jnp.bfloat16)
    return (lambda q, k, v: _ref_bhnd(q, k, v, True, d ** -0.5), (q, k, v))


def _layernorm(b=16, n=512, h=768):
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(np.random.RandomState(0).randn(b, n, h), jnp.bfloat16)
    g = jnp.ones((h,), jnp.bfloat16)
    bb = jnp.zeros((h,), jnp.bfloat16)

    def ln(x, g, b2):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b2
    return ln, (x, g, bb)


def _softmax_ce(b=16, n=512, v=30528):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(b * n, v) * 0.1, jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, v, b * n), jnp.int32)

    def ce(logits, labels):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))
    return ce, (logits, labels)


def _conv2d(b=32, c=64, hw=56, k=3, co=64):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, c, hw, hw) * 0.1, jnp.bfloat16)
    w = jnp.asarray(rng.randn(co, c, k, k) * 0.1, jnp.bfloat16)

    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (1, 1), 'SAME')
    return conv, (x, w)


def _embedding(v=30528, h=768, b=16, n=512):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(v, h) * 0.02, jnp.bfloat16)
    ids = jnp.asarray(rng.randint(0, v, (b, n)), jnp.int32)
    return lambda t, i: t[i], (table, ids)


OP_CONFIGS = [
    ('matmul_1k_bf16', _matmul),
    ('flash_attention_b4h12n512d64', _flash_attention),
    ('sdpa_reference_b4h12n512d64', _sdpa_ref),
    ('layernorm_16x512x768', _layernorm),
    ('softmax_ce_16x512_v30k', _softmax_ce),
    ('conv2d_32x64x56', _conv2d),
    ('embedding_30k_768', _embedding),
]


def run_benchmarks(configs=None, repeat=20, warmup=3):
    import jax
    results = []
    for name, builder in (configs or OP_CONFIGS):
        try:
            fn, args = builder()
            jfn = jax.jit(fn)
            for _ in range(warmup):
                out = jfn(*args)
            jax.tree_util.tree_map(
                lambda a: a.block_until_ready()
                if hasattr(a, 'block_until_ready') else a, out)
            t0 = time.perf_counter()
            for _ in range(repeat):
                out = jfn(*args)
            jax.tree_util.tree_map(
                lambda a: a.block_until_ready()
                if hasattr(a, 'block_until_ready') else a, out)
            dt = (time.perf_counter() - t0) / repeat
            results.append({'op': name, 'mean_ms': round(dt * 1e3, 4),
                            'ok': True})
        except Exception as e:
            results.append({'op': name, 'ok': False, 'error': repr(e)[:300]})
    return results


def compare(baseline, current, threshold=0.15):
    """check_op_benchmark_result.py analog: list of regressions where
    current mean_ms exceeds baseline by more than `threshold` fraction."""
    base = {r['op']: r for r in baseline if r.get('ok')}
    regressions = []
    for r in current:
        if not r.get('ok'):
            continue
        b = base.get(r['op'])
        if b and r['mean_ms'] > b['mean_ms'] * (1.0 + threshold):
            regressions.append({
                'op': r['op'], 'baseline_ms': b['mean_ms'],
                'current_ms': r['mean_ms'],
                'regression': round(r['mean_ms'] / b['mean_ms'] - 1.0, 3)})
    return regressions


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--out', default=None)
    ap.add_argument('--baseline', default=None)
    ap.add_argument('--threshold', type=float, default=0.15)
    ap.add_argument('--repeat', type=int, default=20)
    args = ap.parse_args(argv)

    results = run_benchmarks(repeat=args.repeat)
    print(json.dumps(results, indent=1))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(results, f)
    if args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        regs = compare(base, results, args.threshold)
        if regs:
            print('PERF REGRESSIONS:', json.dumps(regs, indent=1))
            return 1
        print('perf gate: OK')
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
