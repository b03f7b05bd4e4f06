"""Flash-attention Pallas kernels: correctness of forward AND backward vs
the jnp reference, via the Pallas interpreter on CPU (hardware-free), for
head_dim 64 (BERT/GPT-base reality — VERDICT r2 item 3) and 128.

Reference parity target: operators/fused/ attention kernels; test style:
OpTest check_output/check_grad (numeric-vs-analytic).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as fa


def _mk(b=1, h=2, n=256, m=None, d=64, dtype=np.float32, seed=0):
    m = m or n
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, n, d).astype(dtype) * 0.3)
    k = jnp.asarray(rng.randn(b, h, m, d).astype(dtype) * 0.3)
    v = jnp.asarray(rng.randn(b, h, m, d).astype(dtype) * 0.3)
    return q, k, v


@pytest.fixture(autouse=True)
def _interpret_strict(monkeypatch):
    # interpreter mode => the pallas path really runs on CPU; strict =>
    # any fallback to the jnp reference fails the test
    monkeypatch.setenv('PADDLE_TPU_FLASH_INTERPRET', '1')
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '1')


# (causal, d, n, blocks): the first four at seq 256 (one tile); the rest
# at seq 1024 with the standard path's blocks set to each pair, so the
# causal tile plan runs every shape of walk — square tiles (the diagonal
# split into sub-blocks), a wider query block, a wider key block — and
# the non-causal walk runs beside it at one pair
_CASES = [(False, 64, 256, None), (False, 128, 256, None),
          (True, 64, 256, None), (True, 128, 256, None),
          (True, 64, 1024, (512, 512)), (True, 64, 1024, (256, 256)),
          (True, 64, 1024, (512, 256)), (True, 64, 1024, (256, 512)),
          (False, 64, 1024, (512, 256))]
_CASE_IDS = ['%s-%d' % (c, d) + ('-n%d-%dx%d' % ((n,) + b) if b else '')
             for c, d, n, b in _CASES]


def _set_blocks(monkeypatch, blocks):
    """Both passes of the standard path at (block_q, block_k), for this
    test only (the knob table and the environment stay as they are)."""
    if blocks is None:
        return
    bq, bk = blocks
    for name, value in (('_DEFAULT_BLOCK_Q', bq), ('_DEFAULT_BLOCK_K', bk),
                        ('_BLOCK_Q_BWD', bq), ('_BLOCK_K_BWD', bk)):
        monkeypatch.setattr(fa, name, value)
    assert fa._std_blocks(1024, 1024) == blocks
    assert fa._std_bwd_blocks(1024, 1024) == blocks


@pytest.mark.parametrize('causal,d,n,blocks', _CASES, ids=_CASE_IDS)
def test_forward_matches_reference(monkeypatch, causal, d, n, blocks):
    _set_blocks(monkeypatch, blocks)
    q, k, v = _mk(d=d, n=n)
    scale = 1.0 / np.sqrt(d)
    out = fa.flash_attention_bhnd(q, k, v, causal=causal)
    ref = fa._ref_bhnd(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('causal,d,n,blocks', _CASES, ids=_CASE_IDS)
def test_backward_matches_reference(monkeypatch, causal, d, n, blocks):
    _set_blocks(monkeypatch, blocks)
    q, k, v = _mk(d=d, n=n)
    scale = 1.0 / np.sqrt(d)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention_bhnd(q, k, v, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(fa._ref_bhnd(q, k, v, causal, scale) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, 'qkv'):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg='d%s/causal=%s grad %s'
                                           % (d, causal, name))


def _brute_tiles(n, block_q, block_k):
    """Each (q block, k block) tile of the causal [n, n] square by its
    positions: 'unmasked' (every pair key <= query), 'diagonal' (some
    pairs on each side) or 'skipped' (none visible)."""
    pos = np.arange(n)
    visible = pos[None, :] <= pos[:, None]
    kinds = {}
    for qb in range(n // block_q):
        for kb in range(n // block_k):
            tile = visible[qb * block_q:(qb + 1) * block_q,
                           kb * block_k:(kb + 1) * block_k]
            kinds[qb, kb] = ('unmasked' if tile.all() else
                             'diagonal' if tile.any() else 'skipped')
    return kinds


@pytest.mark.parametrize('blocks', [(512, 512), (256, 256), (512, 256),
                                    (256, 512)])
@pytest.mark.parametrize('n', [512, 768, 1024, 2048])
def test_causal_plan_matches_brute_force(n, blocks):
    """Every tile with a visible pair is visited, no other tile is, and
    the mask goes exactly on the tiles the diagonal crosses — for the K
    walk of each query block (forward, dq) and the Q walk of each key
    block (dk/dv), at the blocks the kernels would run (768 clamps)."""
    bq, bk = fa._clamped(blocks[0], blocks[1], n, n)
    kinds = _brute_tiles(n, bq, bk)
    for walk, cells in (('k', n // bq), ('q', n // bk)):
        for i in range(cells):
            seen = {}
            for lo, hi, masked in fa._causal_plan(i, bq, bk, n, walk):
                for j in range(lo, hi):
                    tile = (i, j) if walk == 'k' else (j, i)
                    assert tile not in seen, (walk, tile)
                    seen[tile] = 'diagonal' if masked else 'unmasked'
            want = {t: kind for t, kind in kinds.items()
                    if t[0 if walk == 'k' else 1] == i and kind != 'skipped'}
            assert seen == want, (n, bq, bk, walk, i)


def test_tile_counter_counts_one_traced_call(monkeypatch):
    """flash_kernel_tiles_total grows, per pass, by the brute-force tile
    counts of every (batch, head) square of one traced call: forward,
    dq and dk/dv at 512 / 256 over seq 1024, batch 2 x 3 heads."""
    from paddle_tpu.monitor.registry import MetricRegistry
    from paddle_tpu.monitor import registry as registry_mod
    monkeypatch.setattr(registry_mod, '_default', MetricRegistry())
    _set_blocks(monkeypatch, (512, 256))
    q, k, v = _mk(b=2, h=3, n=1024)
    jax.eval_shape(jax.grad(lambda q: jnp.sum(
        fa.flash_attention_bhnd(q, k, v, causal=True))), q)
    fam = registry_mod.default_registry().get('flash_kernel_tiles_total')
    kinds = list(_brute_tiles(1024, 512, 256).values())
    for pass_name in ('fwd', 'dq', 'dkv'):
        for kind in ('unmasked', 'diagonal', 'skipped'):
            assert fam.labels(kind, pass_name).value() == \
                2 * 3 * kinds.count(kind), (pass_name, kind)


def test_cross_attention_shapes():
    # decode-style: n != m
    q, k, v = _mk(n=256, m=512)
    out = fa.flash_attention_bhnd(q, k, v, causal=False)
    ref = fa._ref_bhnd(q, k, v, False, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_strict_mode_raises_on_shape_fallback():
    # head_dim 80 cannot run the kernel; strict mode must raise, NOT
    # silently return the jnp reference (VERDICT r2 weak #3)
    q, k, v = _mk(d=80)
    with pytest.raises(RuntimeError, match='head_dim'):
        fa.flash_attention_bhnd(q, k, v)


def test_nonstrict_shape_fallback_still_works(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_FLASH_STRICT', '0')
    q, k, v = _mk(d=80)
    out = fa.flash_attention_bhnd(q, k, v)
    ref = fa._ref_bhnd(q, k, v, False, 1.0 / np.sqrt(80))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bf16_forward_close():
    q, k, v = _mk(d=64)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = fa.flash_attention_bhnd(qb, kb, vb, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = fa._ref_bhnd(q, k, v, True, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_bf16_grads_close():
    """bf16 operands route the backward kernels' matmuls through the
    native-dtype + f32-accumulation path (_mm_f32); grads must track the
    f32 reference within bf16 resolution."""
    q, k, v = _mk(d=64)

    def loss_flash(qq, kk, vv):
        return jnp.sum(fa.flash_attention_bhnd(
            qq, kk, vv, causal=True).astype(jnp.float32) ** 2)

    def loss_ref(qq, kk, vv):
        return jnp.sum(fa._ref_bhnd(qq, kk, vv, True,
                                    1.0 / np.sqrt(64)) ** 2)

    gb = jax.grad(loss_flash, argnums=(0, 1, 2))(
        *(t.astype(jnp.bfloat16) for t in (q, k, v)))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gbi, gri in zip(gb, gr):
        assert gbi.dtype == jnp.bfloat16
        denom = max(float(jnp.abs(gri).max()), 1e-6)
        rel = float(jnp.abs(gbi.astype(jnp.float32) - gri).max()) / denom
        assert rel < 0.1, rel


@pytest.mark.parametrize('causal', [False, True])
def test_ring_flash_matches_jnp_ring(causal):
    """ring_flash_attention (Pallas blocks + ppermute + LSE merge, ring
    backward with rotating dk/dv accumulators) vs the jnp ring and the
    single-device reference — forward AND grads (SURVEY §5.7)."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.ops import ring_attention as ra

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ('sp',))
    b, n, h, d = 2, 512, 2, 64   # 128 tokens/shard
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, n, h, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, n, h, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, n, h, d).astype(np.float32) * 0.3)

    def loss_flash(q, k, v):
        return jnp.sum(
            ra.ring_flash_attention_sharded(q, k, v, mesh,
                                            causal=causal) ** 2)

    def loss_ref(q, k, v):
        from paddle_tpu.ops.flash_attention import _ref_bhnd
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        o = _ref_bhnd(qt, kt, vt, causal, d ** -0.5)
        return jnp.sum(jnp.swapaxes(o, 1, 2) ** 2)

    out = ra.ring_flash_attention_sharded(q, k, v, mesh, causal=causal)
    from paddle_tpu.ops.flash_attention import _ref_bhnd
    ref = jnp.swapaxes(_ref_bhnd(jnp.swapaxes(q, 1, 2),
                                 jnp.swapaxes(k, 1, 2),
                                 jnp.swapaxes(v, 1, 2),
                                 causal, d ** -0.5), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, 'qkv'):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg='grad %s causal=%s'
                                           % (name, causal))


@pytest.mark.parametrize('causal', [False, True])
def test_long_path_forward_matches_reference(causal):
    """The long-seq kernels (KV walk as a sequential grid dim + VMEM
    scratch carry — the r5 fix for the 8k scoped-vmem OOM,
    docs/bench_inwindow_r4.jsonl 11:58) vs the jnp reference. FORCE_LONG
    exercises them at a CPU-interpretable size with multiple kv blocks."""
    os.environ['PADDLE_TPU_FLASH_FORCE_LONG'] = '1'
    try:
        q, k, v = _mk(n=1024, d=64)
        scale = 1.0 / np.sqrt(64)
        out = fa.flash_attention_bhnd(q, k, v, causal=causal)
        ref = fa._ref_bhnd(q, k, v, causal, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        os.environ.pop('PADDLE_TPU_FLASH_FORCE_LONG', None)


@pytest.mark.parametrize('causal', [False, True])
def test_long_path_backward_matches_reference(causal):
    os.environ['PADDLE_TPU_FLASH_FORCE_LONG'] = '1'
    try:
        q, k, v = _mk(n=1024, d=64)
        scale = 1.0 / np.sqrt(64)

        def f_flash(q, k, v):
            return jnp.sum(
                fa.flash_attention_bhnd(q, k, v, causal=causal) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(fa._ref_bhnd(q, k, v, causal, scale) ** 2)

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, 'qkv'):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4,
                err_msg='long-path grad %s causal=%s' % (name, causal))
    finally:
        os.environ.pop('PADDLE_TPU_FLASH_FORCE_LONG', None)


def test_long_path_auto_threshold():
    """seq > PADDLE_TPU_FLASH_LONG_SEQ routes to the long kernels
    automatically (the 8k bench rung path); short seqs keep the proven
    short-seq kernels."""
    assert not fa._use_long_path(512, 512)
    assert fa._use_long_path(8192, 8192)
    assert fa._use_long_path(512, 8192)


def test_supported_gate_checks_the_dispatched_paths_blocks():
    # seq 4608 routes to the LONG path (>= 4096); the preferred KV block
    # (1024) doesn't divide it, so the kernels must CLAMP to 512/512 —
    # not truncate the KV walk, and not reject a shape the kernel can
    # serve (it ran at 256/512 before the wide defaults)
    assert fa._long_blocks(4608, 4608) == (512, 512)
    q = jnp.zeros((1, 1, 4608, 64), jnp.float32)
    assert fa._supported(q, q, q) is None
    # preferred blocks used when they fit
    assert fa._long_blocks(8192, 8192) == (512, 1024)
    q = jnp.zeros((1, 1, 8192, 64), jnp.float32)
    assert fa._supported(q, q, q) is None
    # a shape no power-of-two block >= 128 tiles: rejected, with the
    # long-path reason (4616 = 8 x 577 passes the %8 granularity check)
    assert fa._long_blocks(4616, 4616) is None
    q = jnp.zeros((1, 1, 4616, 64), jnp.float32)
    reason = fa._supported(q, q, q)
    assert reason is not None and 'tileable' in reason
    # the standard path still validates against its own blocks
    q = jnp.zeros((1, 1, 512, 64), jnp.float32)
    assert fa._supported(q, q, q) is None
    # n == 768 divides 256 but not the preferred 512 q block: the
    # standard path must clamp (as it did when 256 WAS the default),
    # not reject
    assert fa._std_blocks(768, 1024) == (256, 512)
    q = jnp.zeros((1, 1, 768, 64), jnp.float32)
    k = jnp.zeros((1, 1, 1024, 64), jnp.float32)
    assert fa._supported(q, k, k) is None
    # short-q cross-attention over a long KV: q runs as a single block
    q = jnp.zeros((1, 1, 64, 64), jnp.float32)
    k = jnp.zeros((1, 1, 8192, 64), jnp.float32)
    assert fa._long_blocks(64, 8192) == (64, 1024)
    assert fa._supported(q, k, k) is None


def test_long_path_short_q_cross_attention_parity(monkeypatch):
    # q shorter than the 128 lane tile over a longer KV, forced onto the
    # long path: single-block q, clamped KV walk
    monkeypatch.setenv('PADDLE_TPU_FLASH_FORCE_LONG', '1')
    import numpy as _np
    rng = _np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, 2, 64, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    out = fa.flash_attention_bhnd(q, k, v)
    ref = fa._ref_bhnd(q, k, v, False, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_long_path_clamped_blocks_parity(monkeypatch):
    # force the long path onto a seq where the preferred 512/1024 blocks
    # don't divide (640 -> clamps to bq=128, bk=640): outputs and grads
    # must match the reference exactly like the aligned case
    monkeypatch.setenv('PADDLE_TPU_FLASH_FORCE_LONG', '1')
    import numpy as _np
    rng = _np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    assert fa._long_blocks(640, 640) == (128, 640)
    scale = 1.0 / np.sqrt(64)

    def f(q, k, v):
        return (fa.flash_attention_bhnd(q, k, v, causal=True) ** 2).sum()

    def ref(q, k, v):
        return (fa._ref_bhnd(q, k, v, True, scale) ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_fused_bwd_matches_two_pass(monkeypatch, causal):
    # seq 256 <= the 512/512 default blocks: one tile covers the score
    # matrix, so the fused single-kernel backward dispatches. Its grads
    # must match the two-pass kernels bit-for-bit in intent (same math,
    # same f32 accumulation) — tight tolerance, not reference-loose
    q, k, v = _mk(n=256)

    def loss(q, k, v):
        return (fa.flash_attention_bhnd(q, k, v, causal=causal) ** 2).sum()

    fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv('PADDLE_TPU_FLASH_FUSED_BWD', '0')
    twopass = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(fused, twopass):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_fused_bwd_not_dispatched_multi_block(monkeypatch):
    # seq 1024 at 512/512 blocks: two tiles -> the two-pass kernels must
    # run (the fused kernel has no inter-block accumulation). Pin the
    # gate directly: a wrongly-dispatched fused kernel computes the
    # same numbers (parity can't catch it), so make dispatch itself
    # the assertion
    def boom(*a, **kw):
        raise AssertionError('fused bwd dispatched for a multi-block '
                             'shape')
    monkeypatch.setattr(fa, '_bwd_impl_fused', boom)
    q, k, v = _mk(n=1024)

    def loss(q, k, v):
        return (fa.flash_attention_bhnd(q, k, v, causal=True) ** 2).sum()

    def ref(q, k, v):
        return (fa._ref_bhnd(q, k, v, True, 1.0 / np.sqrt(64)) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_fused_bwd_dispatched_single_block(monkeypatch):
    # and the complement: a single-tile shape MUST take the fused path
    calls = []
    real = fa._bwd_impl_fused

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)
    monkeypatch.setattr(fa, '_bwd_impl_fused', spy)
    q, k, v = _mk(n=256)
    jax.grad(lambda q: (fa.flash_attention_bhnd(q, k, v) ** 2).sum())(q)
    assert calls
