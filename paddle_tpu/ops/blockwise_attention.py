"""Blockwise (chunked) attention in pure XLA — flash's O(N) memory shape
without Mosaic.

Online-softmax over KV blocks (Dao et al. / Liu et al. "Blockwise Parallel
Transformer"), written as a `lax.scan` whose body is `jax.checkpoint`ed:
the scan's saved residuals are only the per-block running (m, l, acc)
carries, so neither forward nor backward ever materializes the [N, M]
score matrix. This is where shapes the Pallas flash kernels
(ops/flash_attention.py) cannot take are routed — cross-length causal
among them — and the long-sequence path off-TPU, when quadratic +
jax.checkpoint would exceed memory.

Reference counterpart: the fused attention family
/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu (spec
only — that is a cuBLAS/cuDNN kernel; this is an XLA-native algorithm).
"""
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def env_block_size():
    """PADDLE_TPU_BLOCKWISE_BLOCK: the blockwise attention chunk size
    (default 512) - the one home for the default, shared by the SDPA
    routing and the Ulysses causal-skip route."""
    return int(os.environ.get('PADDLE_TPU_BLOCKWISE_BLOCK', 512))


def _pick_block(n, target):
    """Largest power-of-two-ish divisor of n that is <= target."""
    b = min(target, n)
    while b > 1 and n % b:
        b //= 2
    return max(b, 1)


def blockwise_attention_bnhd(q, k, v, causal=False, scale=None,
                             block_q=512, block_k=512):
    """Attention over [batch, heads, seq, head_dim] arrays.

    Numerically matches softmax(q k^T * scale) v with f32 accumulation;
    memory is O(seq * head_dim) instead of O(seq^2).

    Causal self-attention (n == m, equal blocks, modest block count) skips
    future KV blocks outright: the q-block count is static, so a Python
    unroll gives q-block i a STATIC kv slice [0..i] — only the lower
    triangle is ever computed (the diagonal block alone carries a mask),
    halving causal attention flops vs compute-then-mask. Cross-attention
    and very deep block counts (compile-size guard) fall back to the
    vmapped compute-then-mask path, which still has the O(N) memory win.
    """
    b, h, n, d = q.shape
    m = k.shape[2]
    if causal and n > m:
        raise ValueError(
            'causal attention with more queries (%d) than keys (%d)'
            % (n, m))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = _pick_block(n, block_q)
    bk = _pick_block(m, block_k)
    tq, tk = n // bq, m // bk

    qb = q.reshape(b, h, tq, bq, d)
    kb = jnp.moveaxis(k.reshape(b, h, tk, bk, d), 2, 0)  # [tk, b, h, bk, d]
    vb = jnp.moveaxis(v.reshape(b, h, tk, bk, d), 2, 0)

    if causal and n == m and bq == bk and tq <= 64:
        return _causal_skip(qb, kb, vb, scale, q.dtype)

    def one_qblock(qi, i):
        # qi: [b, h, bq, d]; i: scalar q-block index

        def body(carry, xs):
            kj, vj, j = xs
            keep = None
            if causal:
                # bottom-right aligned: query row i*bq+row sits at
                # absolute key position (m - n) + i*bq + row, so causal
                # cross-attention (KV-cache decode, chunked prefill)
                # sees the full prefix
                qpos = (m - n) + i * bq + jnp.arange(bq)
                kpos = j * bk + jnp.arange(bk)
                keep = qpos[:, None] >= kpos[None, :]
            return _online_step(carry, qi, kj, vj, scale, keep), None

        init = _online_init(b, h, bq, d)
        (m_f, l_f, acc), _ = lax.scan(jax.checkpoint(body), init,
                                      (kb, vb, jnp.arange(tk)))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return out.astype(q.dtype)

    out = jax.vmap(one_qblock, in_axes=(2, 0), out_axes=2)(
        qb, jnp.arange(tq))
    return out.reshape(b, h, n, d)


def _online_init(b, h, bq, d):
    return (jnp.full((b, h, bq), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, bq), jnp.float32),
            jnp.zeros((b, h, bq, d), jnp.float32))


def _online_step(carry, qn, kj, vj, scale, keep=None):
    """One online-softmax accumulation step over a single KV block.

    carry = (running max, running denom, running weighted-V accum), all
    f32. qn/kj/vj stay in their NATIVE dtype: the two einsums contract
    bf16 operands with f32 MXU accumulation (preferred_element_type) —
    upcasting first would run the MXU at its f32 rate, ~8x slower on
    v5e, for no accuracy gain (softmax math is f32 either way). `keep`
    is an optional [bq, bk] visibility mask. The single copy of this
    numerically delicate update serves the masked fallback, the
    causal-skip scan body, and the causal diagonal block.
    """
    m_prev, l_prev, acc = carry
    s = jnp.einsum('bhqd,bhkd->bhqk', qn, kj,
                   preferred_element_type=jnp.float32) * scale
    if keep is not None:
        s = jnp.where(keep, s, _NEG_INF)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[..., None])
    if keep is not None:
        # -1e30 sentinel rows: exp(-1e30 - -1e30) = 1 would leak masked
        # weight; zero them explicitly
        p = jnp.where(keep, p, 0.0)
    corr = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * corr + jnp.sum(p, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        'bhqk,bhkd->bhqd', p.astype(vj.dtype), vj,
        preferred_element_type=jnp.float32)
    return m_cur, l_cur, acc


def _causal_skip(qb, kb, vb, scale, out_dtype):
    """Lower-triangle-only causal blockwise attention.

    qb: [b, h, tq, bq, d]; kb/vb: [tk, b, h, bk, d] with tq == tk,
    bq == bk. q-block i scans kv blocks 0..i-1 unmasked (all positions
    visible) via a static slice, then folds in the diagonal block with
    the in-block triangle mask — no future block is ever computed. Every
    step (diagonal included) sits under jax.checkpoint so backward only
    keeps the (m, l, acc) carries, preserving the O(seq*head_dim)
    residual contract.
    """
    b, h, tq, bq, d = qb.shape
    tri = jnp.arange(bq)[:, None] >= jnp.arange(bq)[None, :]

    def make_body(qn):
        def body(carry, xs):
            kj, vj = xs
            return _online_step(carry, qn, kj, vj, scale), None
        return body

    def diag_step(carry, qn, kj, vj):
        return _online_step(carry, qn, kj, vj, scale, tri)

    outs = []
    for i in range(tq):
        qn = qb[:, :, i]
        carry = _online_init(b, h, bq, d)
        if i > 0:
            carry, _ = lax.scan(jax.checkpoint(make_body(qn)), carry,
                                (kb[:i], vb[:i]))
        # diagonal block: the only one needing the triangle mask
        m_f, l_f, acc = jax.checkpoint(diag_step)(carry, qn, kb[i], vb[i])
        outs.append((acc / jnp.maximum(l_f, 1e-30)[..., None]
                     ).astype(out_dtype))
    return jnp.stack(outs, axis=2).reshape(b, h, tq * bq, d)


def blockwise_attention(q, k, v, causal=False, scale=None,
                        block_q=512, block_k=512):
    """Paddle-layout entry: [batch, seq, heads, head_dim]."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = blockwise_attention_bnhd(qt, kt, vt, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k)
    return jnp.swapaxes(o, 1, 2)
