"""Ring attention: sequence-parallel exact attention over the 'sp' mesh axis.

Beyond-reference capability (SURVEY.md §5.7): the reference's long-sequence
levers are recompute+pipeline; TPU-native long context shards the sequence
over ICI and rotates K/V blocks with ppermute while accumulating streaming
softmax (Liu et al. ring attention; blockwise from Dao et al.).

Pure jax functions designed to run INSIDE shard_map (axis_name bound).
Complexity per rank: O((N/sp)^2 * sp) flops but N/sp memory — the point.
The per-block compute maps to the MXU via jnp.einsum; the ppermute rides
ICI concurrently with compute (XLA async collectives overlap the loop body).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax


__all__ = ['ring_attention', 'ulysses_attention', 'ring_attention_sharded',
           'ulysses_attention_sharded', 'ring_flash_attention',
           'ring_flash_attention_sharded', 'zigzag_ring_attention',
           'zigzag_layout_indices']


def _block_attn(q, k, v, scale, mask, drop_p=0.0, drop_key=None):
    """One blockwise attention step in f32 accumulators.

    q: [B, Nq, H, D]; k/v: [B, Nk, H, D]; mask: [Nq, Nk] bool or None.
    Returns (scores_max [B,H,Nq], exp-sum [B,H,Nq], acc [B,Nq,H,D]).

    drop_p/drop_key: attention-prob dropout. The exp-sum `l` accumulates
    the UNdropped weights (dropout applies after softmax normalization:
    out_i = sum_j mask_ij p_ij v_j / (keep * sum_j p_ij)), so only the
    value accumulation sees the mask."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    p_v = p
    if drop_p and drop_key is not None:
        keep = jax.random.bernoulli(drop_key, 1.0 - drop_p, p.shape)
        p_v = jnp.where(keep, p / (1.0 - drop_p), 0.0)
    acc = jnp.einsum('bhqk,bkhd->bqhd', p_v.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _merge_blocks(carry, blk):
    """Online-softmax merge of two (m, l, acc) streaming-attention states.
    Safe against an empty carry (m = -inf, l = 0, acc = 0) as long as the
    incoming block's m is finite."""
    m_prev, l_prev, acc_prev = carry
    m_blk, l_blk, acc_blk = blk
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    beta = jnp.exp(m_blk - m_new)
    l_new = alpha * l_prev + beta * l_blk
    acc_new = acc_prev * jnp.moveaxis(alpha, 1, 2)[..., None] + \
        acc_blk * jnp.moveaxis(beta, 1, 2)[..., None]
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name='sp', causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None):
    """Exact attention with K/V rotating around the ring.

    All inputs are the LOCAL sequence shard [B, N_local, H, D]; output is
    the local shard of the attention result. Call inside shard_map with
    `axis_name` bound to the sequence mesh axis.

    dropout_p/dropout_key: attention-prob dropout; the caller passes a
    key already folded per q-shard rank, and each ring step folds the kv
    source rank in, so every (q-block, kv-block) pair draws an
    independent mask.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n_dev = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, n_loc, h, d = q.shape

    # positions of the local q block (global)
    q_pos = my_idx * n_loc + jnp.arange(n_loc)

    def step(carry, r):
        m_prev, l_prev, acc_prev, k_cur, v_cur = carry
        # kv block currently held came from rank (my_idx - r) mod n_dev
        src = jnp.mod(my_idx - r, n_dev)
        if causal:
            k_pos = src * n_loc + jnp.arange(n_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        blk_key = (jax.random.fold_in(dropout_key, src)
                   if dropout_p and dropout_key is not None else None)
        # q in its native dtype: _block_attn contracts with f32 MXU
        # accumulation; a pre-upcast would force an f32-rate matmul
        blk = _block_attn(q, k_cur, v_cur, scale, mask,
                          dropout_p, blk_key)
        m_new, l_new, acc_new = _merge_blocks((m_prev, l_prev, acc_prev),
                                              blk)
        # rotate kv to the next rank (ring)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    m0 = jnp.full((b, h, n_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, n_loc), jnp.float32)
    acc0 = jnp.zeros((b, n_loc, h, d), jnp.float32)
    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, k, v),
                                    jnp.arange(n_dev))
    l = jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2)[..., None]
    return (acc / l).astype(q.dtype)


def zigzag_ring_attention(q, k, v, axis_name='sp', scale=None,
                          dropout_p=0.0, dropout_key=None, causal=True):
    """Load-balanced CAUSAL ring attention (zigzag layout).

    The plain causal ring computes every (q-shard, kv-shard) pair and
    masks the future ones — and since SPMD wall-clock is gated by the
    last rank (which masks nothing), the masked flops are pure waste.
    Zigzag rebalances by layout: with P ranks the sequence is cut into
    2P chunks of size c and rank r holds rows [chunk r ; chunk 2P-1-r]
    (the caller permutes — sp.sp_attention does this outside shard_map).
    Visibility then collapses to a uniform schedule:

      - local step: lo-lo (tri), hi-lo (full), hi-hi (tri)
      - every other ring step exactly TWO full c x c quadrants:
        hi-q vs src-lo-kv always, plus lo-q vs src-lo-kv when r > src
        else hi-q vs src-hi-kv — chosen by jnp.where on the operands,
        so every rank does identical work and no masked block is ever
        computed: ~2x the causal throughput of the plain ring.

    (Brandon et al. striped attention / zigzag ring — public technique.)
    Requires causal=True (the balance argument IS causality) and an even
    local row count.
    """
    assert causal, 'zigzag_ring_attention is causal-only; use ring_attention'
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n_dev = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b, n_loc, h, d = q.shape
    assert n_loc % 2 == 0, 'zigzag needs an even local row count'
    c = n_loc // 2
    two_p = 2 * n_dev

    # native dtype: see ring_attention (f32 accumulation lives in
    # _block_attn's preferred_element_type)
    q_lo, q_hi = q[:, :c], q[:, c:]
    lo_chunk, hi_chunk = r, two_p - 1 - r
    tri = jnp.tril(jnp.ones((c, c), bool))

    def blk_key(q_chunk, kv_chunk):
        if not (dropout_p and dropout_key is not None):
            return None
        return jax.random.fold_in(
            jax.random.fold_in(dropout_key, q_chunk), kv_chunk)

    # local step (src == r): the only masked quadrants in the schedule
    k_lo, k_hi = k[:, :c], k[:, c:]
    v_lo, v_hi = v[:, :c], v[:, c:]
    lo_c = _block_attn(q_lo, k_lo, v_lo, scale, tri, dropout_p,
                       blk_key(lo_chunk, lo_chunk))
    hi_c = _block_attn(q_hi, k_lo, v_lo, scale, None, dropout_p,
                       blk_key(hi_chunk, lo_chunk))
    hi_c = _merge_blocks(hi_c, _block_attn(q_hi, k_hi, v_hi, scale, tri,
                                           dropout_p,
                                           blk_key(hi_chunk, hi_chunk)))

    def step(carry, t):
        lo_c, hi_c, k_cur, v_cur = carry
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        src = jnp.mod(r - t, n_dev)
        src_hi = two_p - 1 - src
        kl, kh = k_cur[:, :c], k_cur[:, c:]
        vl, vh = v_cur[:, :c], v_cur[:, c:]
        # quadrant A: hi-q sees every lo chunk — always full
        hi_c = _merge_blocks(hi_c, _block_attn(
            q_hi, kl, vl, scale, None, dropout_p, blk_key(hi_chunk, src)))
        # quadrant B: r > src -> lo-q vs src-lo; else hi-q vs src-hi.
        # Operand selects keep the program uniform across ranks — the
        # load-balance property — while only visible work is computed.
        pred = r > src
        qB = jnp.where(pred, q_lo, q_hi)
        kB = jnp.where(pred, kl, kh)
        vB = jnp.where(pred, vl, vh)
        keyB = blk_key(jnp.where(pred, lo_chunk, hi_chunk),
                       jnp.where(pred, src, src_hi))
        blkB = _block_attn(qB, kB, vB, scale, None, dropout_p, keyB)
        lo_new = _merge_blocks(lo_c, blkB)
        hi_new = _merge_blocks(hi_c, blkB)
        sel = lambda a, b_: jnp.where(pred, a, b_)
        lo_c = jax.tree_util.tree_map(sel, lo_new, lo_c)
        hi_c = jax.tree_util.tree_map(sel, hi_c, hi_new)
        return (lo_c, hi_c, k_cur, v_cur), None

    if n_dev > 1:
        (lo_c, hi_c, _, _), _ = lax.scan(
            step, (lo_c, hi_c, k, v), jnp.arange(1, n_dev))

    def finish(cr):
        m, l, acc = cr
        l = jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2)[..., None]
        return acc / l
    out = jnp.concatenate([finish(lo_c), finish(hi_c)], axis=1)
    return out.astype(q.dtype)


def zigzag_layout_indices(n, n_dev):
    """Global gather indices taking a contiguous sequence to the zigzag
    layout (rank r <- chunks r and 2P-1-r), and the inverse."""
    import numpy as np
    c = n // (2 * n_dev)
    idx = np.concatenate([
        np.concatenate([np.arange(r * c, (r + 1) * c),
                        np.arange((2 * n_dev - 1 - r) * c,
                                  (2 * n_dev - r) * c)])
        for r in range(n_dev)])
    inv = np.argsort(idx)
    return idx, inv


def ulysses_attention(q, k, v, axis_name='sp', causal=False, scale=None,
                      attn_fn=None, dropout_p=0.0, dropout_key=None):
    """Ulysses (DeepSpeed) sequence parallelism: all_to_all swaps the
    sequence shard for a head shard, runs full-sequence attention on H/sp
    heads locally, and swaps back. Heads must divide the axis size."""
    n_dev = lax.axis_size(axis_name)
    b, n_loc, h, d = q.shape
    assert h % n_dev == 0, 'ulysses needs heads %% sp == 0'

    # tiled all_to_all: split one dim over the axis, concatenate shards
    # along another — dev-major ordering on both sides keeps head index
    # = dev*h_loc + local consistent between the two swaps. (The untiled
    # form mislowers inside shard_map when the mesh carries extra axes.)
    def seq2head(x):
        # [B, N/sp, H, D] -> [B, N, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(x):
        # [B, N, H/sp, D] -> [B, N/sp, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = seq2head(q), seq2head(k), seq2head(v)
    if attn_fn is None:
        if scale is None:
            scale = 1.0 / (q.shape[-1] ** 0.5)
        n_full = qf.shape[1]
        from .blockwise_attention import env_block_size
        blk = env_block_size()
        if causal and not (dropout_p and dropout_key is not None) \
                and n_full >= 1024 and blk > 0 and n_full % blk == 0 \
                and n_full // blk <= 64:
            # (the divisibility/block-count guard mirrors blockwise's own
            # causal-skip precondition — without it, odd lengths would
            # degenerate to tiny-block fallbacks slower than quadratic)
            # long causal sequences: the local full-sequence attention is
            # where Ulysses burns its flops — route through the blockwise
            # causal-skip path (ops/blockwise_attention.py) so future KV
            # blocks are never computed (and memory stays O(N))
            from .blockwise_attention import blockwise_attention
            of = blockwise_attention(qf, kf, vf, causal=True, scale=scale,
                                     block_q=blk, block_k=blk)
        else:
            s = jnp.einsum('bqhd,bkhd->bhqk', qf, kf,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                cm = jnp.tril(jnp.ones((n_full, n_full), bool))
                s = jnp.where(cm[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            if dropout_p and dropout_key is not None:
                # the caller folds the rank in; local heads draw iid masks
                keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                            p.shape)
                p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            of = jnp.einsum('bhqk,bkhd->bqhd', p.astype(vf.dtype), vf)
    else:
        of = attn_fn(qf, kf, vf)
    return head2seq(of.astype(q.dtype))


def _sharded(fn, mesh, axis_name, q, k, v, **kw):
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    wrapped = jax.shard_map(
        functools.partial(fn, axis_name=axis_name, **kw), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return wrapped(q, k, v)


def ring_attention_sharded(q, k, v, mesh, axis_name='sp', causal=False):
    """Host-level entry: q/k/v are GLOBAL [B, N, H, D] arrays; shard_map
    splits the sequence over `axis_name` and runs the ring."""
    return _sharded(ring_attention, mesh, axis_name, q, k, v, causal=causal)


def ulysses_attention_sharded(q, k, v, mesh, axis_name='sp', causal=False):
    return _sharded(ulysses_attention, mesh, axis_name, q, k, v,
                    causal=causal)


# -- ring FLASH attention (SURVEY §5.7: 'ring attention as a Pallas kernel
# with ppermute over ICI') --------------------------------------------------
#
# Per ring step the LOCAL block runs the Pallas flash kernel
# (ops/flash_attention._fwd_impl) and the normalized partial outputs merge
# through their LSEs; the backward is a second ring that reuses the Pallas
# dq/dkv kernels with the GLOBAL lse/delta (blockwise-exact, Liu et al.),
# rotating the dk/dv accumulators alongside their k/v blocks so each
# block's grads arrive home after a full loop. Memory stays O(N_local);
# the quadratic [Nq, Nk] matrix never materializes.

def _lse_merge(o1, lse1, o2, lse2, w2):
    """Merge normalized flash outputs (o [B,H,N,D], lse [B,H,N,1]);
    w2 False masks block 2 out entirely."""
    neg = jnp.full_like(lse2, -jnp.inf)
    lse2w = jnp.where(w2, lse2, neg)
    m = jnp.maximum(lse1, lse2w)
    m_safe = jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))
    a1 = jnp.exp(lse1 - m_safe)
    a2 = jnp.exp(lse2w - m_safe)
    denom = jnp.maximum(a1 + a2, 1e-30)
    o = (o1 * a1 + o2 * a2) / denom
    return o, m_safe + jnp.log(denom)


def ring_flash_attention(q, k, v, axis_name='sp', causal=False, scale=None,
                         dropout_p=0.0, dropout_key=None):
    """Drop-in for ring_attention ([B, N_local, H, D] shards) running the
    Pallas flash kernels per block. Falls back to the jnp ring when the
    kernel cannot run (shape/backend), and routes attention-prob dropout
    to the jnp ring (the Pallas kernels are dropout-free)."""
    from . import flash_attention as fa
    if dropout_p and dropout_key is not None:
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              scale=scale, dropout_p=dropout_p,
                              dropout_key=dropout_key)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, N, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    reason = (None if fa.is_available() else 'flash unavailable on this '
              'backend') or fa._supported(qt, kt, vt)
    if reason is not None:
        if fa.strict_mode():
            raise RuntimeError(
                'PADDLE_TPU_FLASH_STRICT=1 but ring flash attention '
                'cannot run: %s' % reason)
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              scale=scale)

    n_dev = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    @jax.custom_vjp
    def _ring(qb, kb, vb):
        o, lse, _, _ = _ring_fwd_impl(qb, kb, vb)
        return o

    def _ring_fwd_impl(qb, kb, vb):
        my = lax.axis_index(axis_name)
        # step 0: the diagonal block (causal inside the kernel)
        o, lse = fa._fwd_impl(qb, kb, vb, causal, scale)
        o = o.astype(jnp.float32)

        def step(carry, r):
            o_c, lse_c, k_c, v_c = carry
            k_n = lax.ppermute(k_c, axis_name, perm)
            v_n = lax.ppermute(v_c, axis_name, perm)
            o_b, lse_b = fa._fwd_impl(qb, k_n, v_n, False, scale)
            src = jnp.mod(my - r, n_dev)
            w = jnp.logical_or(jnp.asarray(not causal), src < my)
            o_c, lse_c = _lse_merge(o_c, lse_c,
                                    o_b.astype(jnp.float32), lse_b, w)
            return (o_c, lse_c, k_n, v_n), None

        (o, lse, k_last, v_last), _ = lax.scan(
            step, (o, lse, kb, vb), jnp.arange(1, n_dev))
        return o.astype(qb.dtype), lse, k_last, v_last

    def _ring_vjp_fwd(qb, kb, vb):
        o, lse, _, _ = _ring_fwd_impl(qb, kb, vb)
        return o, (qb, kb, vb, o, lse)

    def _ring_vjp_bwd(res, do):
        qb, kb, vb, o, lse = res
        my = lax.axis_index(axis_name)
        do = do.astype(qb.dtype)

        # step 0: diagonal block grads
        dq, dk0, dv0 = fa._bwd_impl(qb, kb, vb, o, lse, do, causal, scale)
        dq = dq.astype(jnp.float32)

        def step(carry, r):
            dq_c, k_c, v_c, dk_c, dv_c = carry
            # rotate the kv block AND its grad accumulators together
            k_n = lax.ppermute(k_c, axis_name, perm)
            v_n = lax.ppermute(v_c, axis_name, perm)
            dk_n = lax.ppermute(dk_c, axis_name, perm)
            dv_n = lax.ppermute(dv_c, axis_name, perm)
            dq_b, dk_b, dv_b = fa._bwd_impl(qb, k_n, v_n, o, lse, do,
                                            False, scale)
            src = jnp.mod(my - r, n_dev)
            w = jnp.logical_or(jnp.asarray(not causal),
                               src < my).astype(jnp.float32)
            dq_c = dq_c + dq_b.astype(jnp.float32) * w
            dk_n = dk_n + dk_b.astype(jnp.float32) * w
            dv_n = dv_n + dv_b.astype(jnp.float32) * w
            return (dq_c, k_n, v_n, dk_n, dv_n), None

        (dq, _, _, dk_acc, dv_acc), _ = lax.scan(
            step, (dq, kb, vb, dk0.astype(jnp.float32),
                   dv0.astype(jnp.float32)), jnp.arange(1, n_dev))
        # one final rotation brings each block's accumulators home
        dk_home = lax.ppermute(dk_acc, axis_name, perm)
        dv_home = lax.ppermute(dv_acc, axis_name, perm)
        return (dq.astype(qb.dtype), dk_home.astype(kb.dtype),
                dv_home.astype(vb.dtype))

    _ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)

    return jnp.swapaxes(_ring(qt, kt, vt), 1, 2)


def ring_flash_attention_sharded(q, k, v, mesh, axis_name='sp',
                                 causal=False):
    return _sharded(ring_flash_attention, mesh, axis_name, q, k, v,
                    causal=causal)
