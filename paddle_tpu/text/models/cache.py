"""The per-layer serving caches a decoder takes, and the paged K/V write
and read every model's full attention shares.

A model names what each of its layers keeps (`cache_specs()`, one entry
per layer): a `PagedKVSpec` — rows of K and V in a page pool, addressed
through block tables — or a `RecurrentSpec` — a fixed set of arrays per
SEQUENCE that every token rewrites (a linear-attention state, the tail
of a causal convolution). `paddle_tpu/serving/kv_cache.py` builds the
device state from those specs and hands the model one cache object per
layer and dispatch: `PagedKVCache` or `RecurrentCache`.

The two kinds differ in what a padded or frozen position may do. A paged
layer lets it write: the rows land past the sequence's length or on the
scratch page, where no query looks, and the next real pass overwrites
them. A recurrent layer has no dead rows: `RecurrentCache.valid` says
how many of a row's tokens are real, and the layer must leave its arrays
bit for bit where `valid` is 0 and take nothing from the positions at or
past it; `lengths == 0` means the row starts a sequence, from zeros.
"""
import collections

import jax
import jax.numpy as jnp

from ...framework.core import Tensor

__all__ = ['PagedKVSpec', 'RecurrentSpec', 'PagedKVCache', 'RecurrentCache',
           'paged_kv_read', 'paged_attention']

_scope = jax.named_scope

# what one layer keeps while it serves. `arrays`: ((shape, dtype), ...)
# per sequence
PagedKVSpec = collections.namedtuple('PagedKVSpec',
                                     'num_heads head_dim dtype')
RecurrentSpec = collections.namedtuple('RecurrentSpec', 'arrays')


def _tensor_leaf(x):
    # flatten/unflatten must round-trip jax's internal placeholder
    # leaves (e.g. ArgInfo during lower()/AOT) untouched; only real
    # arrays and tracers get the Tensor wrapper back
    return Tensor(x) if isinstance(x, jnp.ndarray) else x


def _raw_leaf(x):
    return getattr(x, '_data', x)


class PagedKVCache:
    """Block/page-granular KV cache for the paged serving engine
    (paddle_tpu/serving/paged_engine.py): per layer, a physical pool of
    `[num_pages, page_size, H, Dh]` K/V pages plus a per-sequence
    BLOCK TABLE `[B, max_blocks]` (int32 page ids) and per-sequence
    valid lengths `[B]`. A sequence's logical row j lives in pool row
    `block_tables[s, j // page_size] * page_size + j % page_size`, so
    sequences of different lengths occupy only the pages they need and
    several sequences may map leading blocks to the SAME physical page
    (prefix sharing).

    Invariants (owned by the serving engine / PagedScheduler):
      - block-table entry 0 is the reserved SCRATCH page: never handed
        to a real block, so garbage writes from frozen/retired rows land
        there (or on the row's own dead rows past its length) and are
        unreachable — shared pages are only ever FULL, immutable blocks
        strictly below every writer's length, so no real write can touch
        them;
      - attention writes this step's K/V at each row's current length
        but does NOT advance `lengths`; the engine advances them
        host-side after the full forward;
      - pool rows at/beyond a sequence's length are garbage and never
        attended (the validity mask allows logical positions <= the
        query's absolute position only);
      - capacity/ownership is guarded host-side at admission: a traced
        block table cannot be range-checked in-program (writes are
        clipped to the pool as a memory-safety net; a clipped write is
        by construction a garbage write).
    """

    def __init__(self, k_pool, v_pool, block_tables, lengths):
        self.k = k_pool          # [num_pages, page_size, H, Dh]
        self.v = v_pool
        self.block_tables = block_tables  # [B, max_blocks] int32
        self.lengths = lengths            # [B] int32 (traced under jit)
        # set by attention on the cache it RETURNS, at trace time: which
        # read it took ('pool' | 'gather', see `paged_kv_read`). Not a
        # pytree leaf: a cache rebuilt from leaves has forgotten it.
        self.kv_read = None

    @staticmethod
    def empty(num_pages, page_size, max_blocks, batch, num_heads,
              head_dim, dtype='float32'):
        import paddle_tpu as paddle
        k = paddle.zeros([num_pages, page_size, num_heads, head_dim], dtype)
        v = paddle.zeros([num_pages, page_size, num_heads, head_dim], dtype)
        return PagedKVCache(k, v,
                            jnp.zeros((batch, max_blocks), jnp.int32),
                            jnp.zeros((batch,), jnp.int32))


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((_raw_leaf(c.k), _raw_leaf(c.v), c.block_tables, c.lengths),
               None),
    lambda _, ch: PagedKVCache(_tensor_leaf(ch[0]), _tensor_leaf(ch[1]),
                               ch[2], ch[3]))


class RecurrentCache:
    """What a recurrent layer keeps per sequence: `arrays`, each
    `[B, ...]` (raw jax arrays, in the spec's order), beside `lengths`
    `[B]` (tokens each row consumed before this call: 0 starts the row
    from zeros, whatever the arrays hold) and `valid` `[B]` (how many of
    this call's tokens are real for each row: the padded tail of a
    prefill chunk, a frozen decode lane and a lane past its budget are
    not). The layer returns a cache with the arrays after its `valid`
    tokens; a row with `valid == 0` comes back bit for bit."""

    def __init__(self, arrays, lengths, valid):
        self.arrays = tuple(arrays)
        self.lengths = lengths
        self.valid = valid


jax.tree_util.register_pytree_node(
    RecurrentCache,
    lambda c: ((c.arrays, c.lengths, c.valid), None),
    lambda _, ch: RecurrentCache(*ch))


def paged_kv_read(batch, capacity, pool_rows):
    """Which read paged attention takes, from the shapes alone: 'pool'
    attends over every pool row in place and masks what a row does not
    hold; 'gather' first materializes each row's `[capacity]` logical
    view. The pool is the smaller read once the views together (`batch *
    capacity` token rows, K and V, per layer) are at least the pool — a
    decode or verify batch; a one-row prefill chunk keeps the gather (its
    scores would span the whole pool)."""
    return 'pool' if batch * capacity >= pool_rows else 'gather'


def _pool_attention(q, kf, vf, mask):
    """q `[B, n, H, Dh]` against ALL pool rows kf / vf `[R, H, Dh]` under
    an additive mask `[B, 1, n, R]`: `_sdpa_ref`'s arithmetic (products
    in the operands' dtype, float32 softmax) with no batch axis on the
    keys, so nothing of the pool is copied per row."""
    s = jnp.einsum('bqhd,khd->bhqk', q, kf) * (1.0 / q.shape[-1] ** 0.5)
    p = jax.nn.softmax((s + mask).astype(jnp.float32), axis=-1)
    return jnp.einsum('bhqk,khd->bqhd', p.astype(q.dtype), vf)


def paged_attention(q, k, v, cache, scope, choose_read=paged_kv_read):
    """Write this call's K/V rows `[B, n, H, Dh]` into `cache`'s pools at
    each row's length and attend q over what the row then holds, causally.
    Returns (attention output `[B, n, H, Dh]` as a Tensor, the cache with
    the new pools and `kv_read` set). `scope` prefixes the named scopes
    of the device ops (`<scope>.paged_write`, `.paged_gather`, `.mask`,
    `.core`); `choose_read` is `paged_kv_read` unless the caller looks
    it up elsewhere."""
    from ...nn import functional as F
    q, k, v = _raw_leaf(q), _raw_leaf(k), _raw_leaf(v)
    b, n = q.shape[0], q.shape[1]
    num_pages, page = cache.k.shape[0], cache.k.shape[1]
    nb = cache.block_tables.shape[1]
    L = nb * page                       # logical capacity per row
    t = cache.lengths                   # [B] per-row write offsets
    bt = cache.block_tables             # [B, nb] physical page ids
    if not isinstance(t, jax.core.Tracer) and int(jnp.max(t)) + n > L:
        # (under jit lengths are traced; the serving engine guards
        # capacity at admission instead)
        raise ValueError(
            'paged cache overflow: max row length %d + %d new '
            'tokens > capacity %d' % (int(jnp.max(t)), n, L))
    # write: token i of row s sits at absolute position t[s]+i;
    # its pool row is bt[s, pos // page] * page + pos % page.
    # ONE flat scatter covers all rows; clipping keeps garbage
    # from frozen rows inside the pool (it lands on the scratch
    # page or the row's own dead rows — both unreachable, see
    # PagedKVCache invariants)
    ck, cv = _raw_leaf(cache.k), _raw_leaf(cache.v)
    with _scope(scope + '.paged_write'):
        pos = jnp.clip(t[:, None] + jnp.arange(n)[None, :], 0, L - 1)
        rows = (jnp.take_along_axis(bt, pos // page, axis=1) * page
                + pos % page)                                # [B, n]
        flat_shape = (num_pages * page,) + tuple(ck.shape[2:])
        kf = ck.reshape(flat_shape)
        vf = cv.reshape(flat_shape)
        idx = rows.reshape(-1)
        kf = kf.at[idx].set(k.astype(kf.dtype).reshape(
            (b * n,) + flat_shape[1:]))
        vf = vf.at[idx].set(v.astype(vf.dtype).reshape(
            (b * n,) + flat_shape[1:]))
    new_cache = PagedKVCache(Tensor(kf.reshape(ck.shape)),
                             Tensor(vf.reshape(cv.shape)), bt, t)
    # read, by shape at trace time (`paged_kv_read`): the pool's
    # rows where they lie when every row's logical view together
    # would be at least the pool, else the gathered view. Same
    # arithmetic either way; keys only come in another order.
    read = new_cache.kv_read = choose_read(b, L, num_pages * page)
    if read == 'pool':
        # a pool row (p, r) is logical position j*page + r of the
        # row whose FIRST block-table entry holding p is j (nb:
        # none, past every query). A shared page is visible to
        # each holder; scratch page 0 fills every unused entry,
        # so its first j lies past the row's length, and an idle
        # row (t = 0, all scratch) sees position 0 as below.
        with _scope(scope + '.mask'):
            qpos = t[:, None] + jnp.arange(n)[None, :]       # [B, n]
            holds = bt[:, :, None] == jnp.arange(num_pages)
            first = jnp.min(jnp.where(
                holds, jnp.arange(nb)[None, :, None], nb), axis=1)
            kpos = (first[:, :, None] * page
                    + jnp.arange(page)).reshape(b, num_pages * page)
            allow = qpos[:, :, None] >= kpos[:, None, :]
            mask = jnp.where(allow, 0.0, -1e9)[:, None].astype(
                jnp.float32)                   # [B, 1, n, pool rows]
        with _scope(scope + '.core'):
            out = Tensor(_pool_attention(q, kf, vf, mask))
        return out, new_cache
    # gather each row's logical [L] view through its block table
    # (this step's rows included — written above), then the same
    # masked attention as the slot path. The gather materializes
    # [B, L, H, Dh] activations; persistent memory stays
    # page-granular, which is where the density win lives.
    with _scope(scope + '.paged_gather'):
        view = (bt[:, :, None] * page
                + jnp.arange(page)[None, None, :]).reshape(b, L)
        kg = jnp.take(kf, view, axis=0)                # [B, L, H, Dh]
        vg = jnp.take(vf, view, axis=0)
    # per-row validity mask: query row i of sequence s sits at
    # absolute position t[s]+i and sees logical positions <= it
    with _scope(scope + '.mask'):
        qpos = t[:, None] + jnp.arange(n)[None, :]           # [B, n]
        allow = qpos[:, :, None] >= jnp.arange(L)[None, None, :]
        mask = Tensor(jnp.where(allow, 0.0, -1e9)[:, None].astype(
            jnp.float32))                                # [B,1,n,L]
    with _scope(scope + '.core'):
        out = F.scaled_dot_product_attention(
            Tensor(q), Tensor(kg), Tensor(vg), attn_mask=mask,
            is_causal=False, dropout_p=0.0)
    return out, new_cache
