"""Zigzag (load-balanced) causal ring attention.

Parity bar: must match the quadratic causal reference exactly (fwd and
grads) through the sp_attention entry, like the plain ring. Balance bar:
per-rank matmul flops must be the lower-triangle schedule — (2P+1)/(4P)
of the plain ring's compute-then-mask — asserted on the shard_map body's
jaxpr with scan trip counts weighted in.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import paddle_tpu  # noqa: F401  (forces the 8-device CPU mesh via conftest)
from paddle_tpu.distributed import sp as sp_mod
from paddle_tpu.ops import ring_attention as ra

from test_blockwise_attention import _weighted_dot_flops


def _mesh(n):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs, ('sp',))


def _ref_causal(q, k, v, scale):
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize('sp,n', [(2, 8), (4, 16), (8, 32), (4, 64)])
def test_zigzag_matches_reference_fwd(sp, n):
    rng = np.random.RandomState(0)
    b, h, d = 2, 2, 16
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    mesh = _mesh(sp)
    st = sp_mod.make_sp_state(mesh, axis='sp', mode='zigzag')
    out = sp_mod.sp_attention(q, k, v, causal=True, scale=scale, state=st)
    ref = _ref_causal(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_zigzag_matches_reference_grads():
    rng = np.random.RandomState(1)
    b, n, h, d = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    mesh = _mesh(4)
    st = sp_mod.make_sp_state(mesh, axis='sp', mode='zigzag')

    def loss_z(q, k, v):
        return jnp.sum(sp_mod.sp_attention(q, k, v, causal=True,
                                           scale=scale, state=st) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_causal(q, k, v, scale) ** 2)

    gz = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gz, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-5, atol=5e-5)


def test_zigzag_flops_are_lower_triangle():
    """Per-rank matmul flops: plain causal ring computes all 4 quadrants
    per ring step (then masks); zigzag computes 2P+1 quadrants total vs
    the ring's 4P."""
    sp, n, b, h, d = 4, 32, 1, 2, 16
    mesh = _mesh(sp)
    x = jnp.zeros((b, n, h, d), jnp.float32)
    spec = P(None, 'sp', None, None)

    def count(fn, **kw):
        import functools
        wrapped = shard_map(
            functools.partial(fn, axis_name='sp', **kw), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
        return _weighted_dot_flops(jax.make_jaxpr(wrapped)(x, x, x).jaxpr)

    ring = count(ra.ring_attention, causal=True)
    zig = count(ra.zigzag_ring_attention)
    assert zig == ring * (2 * sp + 1) // (4 * sp), (zig, ring)


@pytest.mark.slow
def test_zigzag_dropout_deterministic_and_varying():
    rng = np.random.RandomState(3)
    b, n, h, d = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    mesh = _mesh(4)
    st = sp_mod.make_sp_state(mesh, axis='sp', mode='zigzag')
    key = jax.random.PRNGKey(7)

    def run(key):
        return np.asarray(sp_mod.sp_attention(
            q, q, q, causal=True, scale=0.35, state=st,
            dropout_p=0.5, dropout_key=key))

    a, b_ = run(key), run(key)
    np.testing.assert_array_equal(a, b_)          # same key -> same masks
    c = run(jax.random.PRNGKey(8))
    assert np.abs(a - c).max() > 0                # new key -> new masks
    # p=0 path equals the no-dropout path
    nd = np.asarray(sp_mod.sp_attention(q, q, q, causal=True, scale=0.35,
                                        state=st))
    z = np.asarray(sp_mod.sp_attention(q, q, q, causal=True, scale=0.35,
                                       state=st, dropout_p=0.0,
                                       dropout_key=key))
    np.testing.assert_allclose(nd, z, rtol=1e-6)


def test_zigzag_falls_back_when_not_applicable():
    rng = np.random.RandomState(5)
    b, n, h, d = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    mesh = _mesh(4)
    st = sp_mod.make_sp_state(mesh, axis='sp', mode='zigzag')
    # non-causal: falls back to the plain ring and stays correct
    out = sp_mod.sp_attention(q, q, q, causal=False, scale=0.35, state=st)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, q) * 0.35
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum('bhqk,bkhd->bqhd', p, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # causal but N not divisible by 2P (24 % 8 != 0): same downgrade,
    # must still match the quadratic causal reference
    n2 = 24
    q2 = jnp.asarray(rng.randn(b, n2, h, d), jnp.float32)
    out2 = sp_mod.sp_attention(q2, q2, q2, causal=True, scale=0.35,
                               state=st)
    ref2 = _ref_causal(q2, q2, q2, 0.35)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ulysses_long_causal_uses_blockwise_skip():
    """Ulysses' local full-sequence attention routes through the causal
    block-skip path at long N: parity with the quadratic reference AND
    fewer matmul flops than the compute-then-mask program."""
    import functools
    sp, n, b, h, d = 4, 2048, 1, 4, 8
    mesh = _mesh(sp)
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    spec = P(None, 'sp', None, None)

    wrapped = shard_map(
        functools.partial(ra.ulysses_attention, axis_name='sp',
                          causal=True, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = wrapped(q, q, q)
    ref = _ref_causal(q, q, q, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=3e-5)

    flops_causal = _weighted_dot_flops(
        jax.make_jaxpr(wrapped)(q, q, q).jaxpr)
    wrapped_full = shard_map(
        functools.partial(ra.ulysses_attention, axis_name='sp',
                          causal=False, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    flops_full = _weighted_dot_flops(
        jax.make_jaxpr(wrapped_full)(q, q, q).jaxpr)
    assert flops_causal < 0.7 * flops_full, (flops_causal, flops_full)


@pytest.mark.slow
def test_ulysses_long_causal_grads_match():
    """The blockwise-skip route swaps the BACKWARD program too — grad
    parity vs the quadratic reference through the composed
    all_to_all + causal-skip path."""
    import functools
    sp, n, b, h, d = 4, 1024, 1, 4, 8
    mesh = _mesh(sp)
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    spec = P(None, 'sp', None, None)
    wrapped = shard_map(
        functools.partial(ra.ulysses_attention, axis_name='sp',
                          causal=True, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def loss_u(q, k, v):
        return jnp.sum(wrapped(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_causal(q, k, v, scale) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_zigzag_dropout_unbiased():
    """Zigzag's quadrant-level dropout keys must preserve the dropout-
    after-softmax identity: averaging many masked draws recovers the
    undropped attention (the same unbiasedness bar the plain ring
    holds)."""
    mesh = _mesh(2)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    k = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    v = jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32)
    st = sp_mod.make_sp_state(mesh, axis='sp', mode='zigzag')

    ref = np.asarray(sp_mod.sp_attention(q, k, v, causal=True, scale=0.5,
                                         state=st))

    @jax.jit
    def one(key):
        return sp_mod.sp_attention(q, k, v, causal=True, scale=0.5,
                                   state=st, dropout_p=0.3,
                                   dropout_key=key)

    n = 400
    acc = np.zeros(np.asarray(ref).shape, np.float32)
    base = jax.random.PRNGKey(11)
    for i in range(n):
        acc += np.asarray(one(jax.random.fold_in(base, i)))
    mean = acc / n
    np.testing.assert_allclose(mean, ref, atol=0.35)
