"""Attention functionals.

The jnp reference path always works (and XLA fuses it well); the Pallas flash
kernel (ops/flash_attention.py) kicks in on TPU for long sequences where HBM
traffic of the naive path dominates. Which implementation runs is decided
here, before the call, and shows in the op name (`flash_attention`,
`blockwise_attention`, `sdpa`); a chosen kernel that raises is not caught.
Reference parity: paddle incubate sparse_attention / nn.MultiHeadAttention
core.
"""
import math
import os

import jax
import jax.numpy as jnp

from ...framework.core import run_op
from ...ops import flash_attention as fa
from ...tensor._helpers import ensure_tensor


def _attn_impl():
    """PADDLE_TPU_ATTN_IMPL: auto (default) | flash | blockwise | quadratic.

    'auto' prefers the Pallas flash kernel when it can run, then blockwise
    (pure-XLA online softmax, ops/blockwise_attention.py) for sequences
    long enough that the quadratic path's [B,H,N,N] recompute dominates,
    then the quadratic + jax.checkpoint reference body.
    """
    return os.environ.get('PADDLE_TPU_ATTN_IMPL', 'auto')


def _blockwise_min_seq():
    return int(os.environ.get('PADDLE_TPU_BLOCKWISE_MIN_SEQ', 1024))


def _blockwise_block(seq_len):
    """Blockwise attention chunk size. The default (see
    ops.blockwise_attention.env_block_size) flows through _pick_block's
    graceful divisor shrink; an EXPLICITLY-set PADDLE_TPU_BLOCKWISE_BLOCK
    that cannot tile the q sequence (non-divisor, <= 0) would silently
    degrade to 1-row blocks - reject that loudly instead."""
    from ...ops.blockwise_attention import env_block_size
    blk = env_block_size()
    if 'PADDLE_TPU_BLOCKWISE_BLOCK' in os.environ:
        if blk <= 0:
            raise ValueError('PADDLE_TPU_BLOCKWISE_BLOCK must be '
                             'positive, got %d' % blk)
        if seq_len % min(blk, seq_len):
            raise ValueError(
                'PADDLE_TPU_BLOCKWISE_BLOCK=%d does not tile seq len %d '
                '(pick a divisor)' % (blk, seq_len))
    return blk


def _bhnd(t):
    """Shape and dtype of a [B, N, H, D] tensor in the kernels' layout."""
    b, n, h, d = t.shape
    return jax.ShapeDtypeStruct((b, h, n, d), t._data.dtype)


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, drop_key=None):
    # q,k,v: [B, N, H, D] paddle layout
    qt = jnp.swapaxes(q, 1, 2)  # B,H,N,D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum('bhqd,bhkd->bhqk', qt, kt) * scale
    if causal:
        # bottom-right aligned (flash-attn convention): query i sits at
        # absolute position (m - n) + i, so KV-cache decode (n=1, m=T)
        # sees the whole cache. Top-left tril would mask it to key 0.
        n, m = s.shape[-2], s.shape[-1]
        if n > m:
            raise ValueError(
                'causal attention with more queries (%d) than keys (%d): '
                'the leading query rows would have no visible key' % (n, m))
        cm = jnp.tril(jnp.ones((n, m), bool), m - n)
        s = jnp.where(cm, s, -1e30)
    if mask is not None:
        s = s + mask
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and drop_key is not None:
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0).astype(q.dtype)
    o = jnp.einsum('bhqk,bhkd->bhqd', p, vt)
    return jnp.swapaxes(o, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Inputs [batch, seq, heads, head_dim] (paddle layout)."""
    q = ensure_tensor(query)
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    scale = 1.0 / math.sqrt(q.shape[-1])
    if not training:
        # eval-mode dropout is a no-op; normalizing here keeps the
        # flash/blockwise fast paths eligible during inference
        dropout_p = 0.0

    # sequence-parallel routing: when the fleet strategy activated the sp
    # context, attention is the one op that mixes tokens across the
    # sequence shards — run it as ring/Ulysses over the 'sp' mesh axis
    try:
        from ...distributed.sp import sequence_parallel_state, sp_attention
        sp_state = sequence_parallel_state()
    except ImportError:
        sp_state = None
    if sp_state is not None and q._data.ndim == 4:
        if attn_mask is not None:
            raise ValueError('sequence-parallel attention supports causal/'
                             'full masks only (attn_mask must be None)')
        sp_drop_key = None
        if dropout_p and training:
            from ...framework import random as rng
            sp_drop_key = rng.next_key()

        def fn(qq, kk, vv):
            return sp_attention(qq, kk, vv, causal=is_causal, scale=scale,
                                state=sp_state,
                                dropout_p=dropout_p if sp_drop_key is not None
                                else 0.0,
                                dropout_key=sp_drop_key)
        return run_op('sp_attention', fn, q, k, v)

    impl = _attn_impl()
    mask_arr = ensure_tensor(attn_mask)._data if attn_mask is not None else None
    plain = q._data.ndim == 4 and mask_arr is None and dropout_p == 0.0

    flash_miss = False
    if impl in ('auto', 'flash') and plain and q.shape[1] >= 512 \
            and q.shape[-1] <= 256 and fa.is_available():
        # shape eligibility is routing: an ineligible shape takes the
        # blockwise op below under its own name (strict mode raises
        # inside the flash op instead)
        flash_miss = fa.unsupported_reason(
            _bhnd(q), _bhnd(k), _bhnd(v), causal=is_causal) is not None \
            and not fa.strict_mode()
        if not flash_miss:
            def fn(qq, kk, vv):
                return fa.flash_attention_bnhd(qq, kk, vv, causal=is_causal,
                                               scale=scale)
            return run_op('flash_attention', fn, q, k, v)

    if plain and (impl == 'blockwise' or flash_miss or
                  (impl == 'auto' and q.shape[1] >= _blockwise_min_seq())):
        from ...ops import blockwise_attention as bw
        # smaller blocks widen the causal-skip window (tq = N/block must
        # be > 1 for any future block to exist); tunable for benchmarking
        blk = _blockwise_block(int(q.shape[1]))

        def fn(qq, kk, vv):
            return bw.blockwise_attention(qq, kk, vv, causal=is_causal,
                                          scale=scale, block_q=blk,
                                          block_k=blk)
        return run_op('blockwise_attention', fn, q, k, v)

    # attention-prob dropout rides the framework RNG stream (same
    # convention as F.dropout: key drawn outside the pure fn); the remat
    # recompute reuses the key, so backward sees the same mask
    drop_key = None
    if dropout_p and training:
        from ...framework import random as rng
        drop_key = rng.next_key()

    # remat the quadratic body: backward recomputes the [B,H,N,N] scores
    # and probabilities from q/k/v instead of keeping them resident —
    # the flash-attention memory shape, in pure XLA (kicks in whenever
    # the Pallas kernel doesn't; ~1/3 extra attention flops, which are a
    # small slice of a transformer step)
    @jax.checkpoint
    def fn(qq, kk, vv):
        return _sdpa_ref(qq, kk, vv, mask_arr, dropout_p, is_causal, scale,
                         drop_key)
    return run_op('sdpa', fn, q, k, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal)
    if return_softmax:
        return out, None
    return out, None
