"""The per-layer serving caches a decoder takes, and the paged K/V write
and read every model's full attention shares.

A model names what each of its layers keeps (`cache_specs()`, one entry
per layer): a `PagedKVSpec` — rows of K and V in a page pool, addressed
through block tables —, a `PagedLatentSpec` — ONE row of `width` values a
token in a pool of its own, shared by every head and read both as keys
and as values (latent attention), addressed through the same block
tables — or a `RecurrentSpec` — a fixed set of arrays per SEQUENCE that
every token rewrites (a linear-attention state, the tail of a causal
convolution). `paddle_tpu/serving/kv_cache.py` builds the device state
from those specs and hands the model one cache object per layer and
dispatch: `PagedKVCache`, `PagedLatentCache` or `RecurrentCache`.

The kinds differ in what a padded or frozen position may do. A paged
layer (of either kind) lets it write: the rows land past the sequence's
length or on the scratch page, where no query looks, and the next real
pass overwrites them. A recurrent layer has no dead rows:
`RecurrentCache.valid` says how many of a row's tokens are real, and the layer must leave its arrays
bit for bit where `valid` is 0 and take nothing from the positions at or
past it; `lengths == 0` means the row starts a sequence, from zeros.
"""
import collections
import functools

import jax
import jax.numpy as jnp

from ...framework.core import Tensor

__all__ = ['PagedKVSpec', 'PagedLatentSpec', 'RecurrentSpec', 'PagedKVCache',
           'PagedLatentCache', 'RecurrentCache', 'paged_pool_shape',
           'latent_pool_shape', 'paged_kv_read', 'paged_attention',
           'paged_latent_rows']

_scope = jax.named_scope

# what one layer keeps while it serves. `arrays`: ((shape, dtype), ...)
# per sequence
PagedKVSpec = collections.namedtuple('PagedKVSpec',
                                     'num_heads head_dim dtype')
PagedLatentSpec = collections.namedtuple('PagedLatentSpec', 'width dtype')
RecurrentSpec = collections.namedtuple('RecurrentSpec', 'arrays')

# A cache a layer RETURNS may carry `counters`: {name: device scalar} of
# what the layer counted in this call (an expert layer: the chosen pairs
# that fell on experts it holds). Not a pytree leaf. The engine adds them
# up over the layers and the steps of a burst — a name that ends in
# `_max` takes the largest instead — and fetches them with the tokens.


def _tensor_leaf(x):
    # flatten/unflatten must round-trip jax's internal placeholder
    # leaves (e.g. ArgInfo during lower()/AOT) untouched; only real
    # arrays and tracers get the Tensor wrapper back
    return Tensor(x) if isinstance(x, jnp.ndarray) else x


def _raw_leaf(x):
    return getattr(x, '_data', x)


class PagedKVCache:
    """Block/page-granular KV cache for the serving engine
    (paddle_tpu/serving/engine.py): per layer, a physical K pool
    and V pool `[G, num_pages * page_size, W]` (`paged_pool_shape`) plus
    a per-sequence BLOCK TABLE `[B, max_blocks]` (int32 page ids) and
    per-sequence valid lengths `[B]`. A sequence's logical row j lives in
    pool row `block_tables[s, j // page_size] * page_size + j %
    page_size`, so sequences of different lengths occupy only the pages
    they need and several sequences may map leading blocks to the SAME
    physical page (prefix sharing). `page_size` is static (pytree aux
    data): the pool's shape does not hold it.

    The layout is the one the write and the read both take as it lies.
    A token's row is `W` lanes wide: one head of 128 or more, or as many
    narrower heads side by side as fill 128 lanes (GPT-2's 64: two), the
    `G` groups outermost. A token then lands in `G` contiguous pieces, a
    page of 16 rows in `G` whole tiles, and a page is a window of the
    free view `[G, num_pages, page_size, W]`, so XLA:TPU takes and
    scatters pages of that view, and contracts over the lanes or over the
    rows, WITHOUT re-laying the pool: it has one layout, the default of
    its shape, from a program's parameters through its scan to its
    outputs, is updated in place and never copied. (Rows last, `[H, Dh,
    rows]`, also keeps one layout, but spreads a token over `H * Dh`
    separate words: a column write measured 7 us. Heads narrower than the
    lanes and not packed make the device pick a rows-last layout by
    itself. `page_size` should be a multiple of the dtype's sublane tile,
    16 for bfloat16, or the page view is not free.)

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
        block table cannot be range-checked in-program (writes past a
        row's last block go to the scratch page as a memory-safety net;
        such a write is by construction a garbage write).
    """

    def __init__(self, k_pool, v_pool, block_tables, lengths, page_size):
        self.k = k_pool          # [G, num_pages * page_size, W]
        self.v = v_pool
        self.block_tables = block_tables  # [B, max_blocks] int32
        self.lengths = lengths            # [B] int32 (traced under jit)
        self.page_size = int(page_size)
        # set by attention on the cache it RETURNS, at trace time: which
        # read it took ('pool' | 'gather', see `paged_kv_read`). Not a
        # pytree leaf: a cache rebuilt from leaves has forgotten it.
        self.kv_read = None

    @staticmethod
    def empty(num_pages, page_size, max_blocks, batch, num_heads,
              head_dim, dtype='float32'):
        import paddle_tpu as paddle
        shape = list(paged_pool_shape(num_heads, head_dim, num_pages,
                                      page_size))
        return PagedKVCache(paddle.zeros(shape, dtype),
                            paddle.zeros(shape, dtype),
                            jnp.zeros((batch, max_blocks), jnp.int32),
                            jnp.zeros((batch,), jnp.int32), page_size)


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((_raw_leaf(c.k), _raw_leaf(c.v), c.block_tables, c.lengths),
               c.page_size),
    lambda page_size, ch: PagedKVCache(
        _tensor_leaf(ch[0]), _tensor_leaf(ch[1]), ch[2], ch[3], page_size))


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


class PagedLatentCache:
    """One pool a layer, `[1, num_pages * page_size, W]`
    (`latent_pool_shape`: a row is the latent one token leaves, every
    head reads it, as its key and as its value; `W` is the spec's width
    rounded up to whole lanes, the rest zeros), beside the block table,
    lengths and `page_size` of `PagedKVCache` — the same pages,
    invariants and scratch page — and `valid` `[B]` as `RecurrentCache`
    has it (None where every position is real). The pool, lengths and
    valid are raw jax arrays."""

    def __init__(self, pool, block_tables, lengths, valid, page_size):
        self.pool = pool
        self.block_tables = block_tables
        self.lengths = lengths
        self.valid = valid
        self.page_size = int(page_size)
        self.kv_read = None      # as `PagedKVCache.kv_read`


jax.tree_util.register_pytree_node(
    PagedLatentCache,
    lambda c: ((c.pool, c.block_tables, c.lengths, c.valid), c.page_size),
    lambda page_size, ch: PagedLatentCache(*ch, page_size))


_LANES = 128     # the minor axis of a TPU tile


def paged_pool_shape(num_heads, head_dim, num_pages, page_size):
    """`[G, rows, W]` of one K or V pool (see `PagedKVCache`): heads
    narrower than the lanes sit side by side in one row of `W` lanes when
    a whole number of them fills it."""
    per = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return (-(-num_heads // per), num_pages * page_size, per * head_dim)


def latent_pool_shape(width, num_pages, page_size):
    """`[1, rows, W]` of a latent layer's pool: `width` rounded up to a
    multiple of the 128 lanes. A row that is not whole lanes wide (576 is
    4.5) makes the device lay the pool rows-last, and every program then
    copies it on entry and on exit; in memory its tiles are padded to
    whole lanes anyway."""
    return (1, num_pages * page_size, -(-width // _LANES) * _LANES)


def paged_kv_read(batch, capacity, pool_rows):
    """Which read paged attention takes, from the shapes alone: 'pool'
    attends over every pool row in place and masks what a row does not
    hold; 'gather' first materializes each row's `[capacity]` logical
    view. The pool is the smaller read once the views together (`batch *
    capacity` token rows, K and V, per layer) are at least the pool — a
    decode or verify batch; a one-row prefill chunk keeps the gather (its
    scores would span the whole pool)."""
    return 'pool' if batch * capacity >= pool_rows else 'gather'


def _pack(x, groups, lanes):
    """`[B, n, H, Dh]` -> `[B, G, n, W]`: heads side by side in a row,
    the last group filled with zeros."""
    b, n, h, dh = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, groups * (lanes // dh) - h), (0, 0)))
    return jnp.transpose(x.reshape(b, n, groups, lanes), (0, 2, 1, 3))


def _write(pools, news, start, bt, page):
    """Rows `[start[s], start[s] + n)` of each sequence (`news`: K and V,
    `[B, G, n, W]`) into `pools` through the pages they touch: taken from
    the pool's page view, filled where a row is the call's, scattered back
    — whatever the start, on a page boundary (every chunk the scheduler
    makes) or not; a row of a touched page outside the run keeps what it
    held. A page past the sequence's last block is the scratch page (entry
    0 of every table), never the last block, whose rows may be real. One
    form for a decode step's single rows too: a row a
    `dynamic_update_slice` keeps the layout as well, but unrolls `B`
    updates a pool a layer into the program, and the decode step measured
    a fifth slower with them."""
    b, groups, n, lanes = news[0].shape
    nb = bt.shape[1]
    m = (n + page - 2) // page + 1          # pages a run of n rows touches
    blk = (start // page)[:, None] + jnp.arange(m)                 # [B, m]
    ids = jnp.where(blk < nb, jnp.take_along_axis(
        bt, jnp.minimum(blk, nb - 1), axis=1), 0).reshape(-1)
    # row c of a sequence's m pages is token c - start % page of the call
    tok = jnp.arange(m * page)[None, :] - (start % page)[:, None]
    mine = (tok >= 0) & (tok < n)                           # [B, m*page]
    # (a page the run does not reach — the last one, from a page boundary
    # — is not put back: another sequence may be writing the scratch page)
    put_ids = jnp.where(mine.reshape(b * m, page).any(-1), ids,
                        pools[0].shape[1] // page)
    src = jnp.clip(tok, 0, n - 1)[:, None, :, None]
    out = []
    for pool, new in zip(pools, news):
        view = pool.reshape(groups, -1, page, lanes)
        old = jnp.take(view, ids, axis=1).reshape(groups, b, m * page, lanes)
        put = jnp.where(mine[:, None, :, None],
                        jnp.take_along_axis(new, src, axis=2),
                        jnp.swapaxes(old, 0, 1))         # [B, G, m*page, W]
        put = jnp.swapaxes(put, 0, 1).reshape(groups, b * m, page, lanes)
        out.append(view.at[:, put_ids].set(put, mode='drop').reshape(
            pool.shape))
    return out


def _row_views(pool, bt, page):
    """Each sequence's logical view `[B, G, capacity, W]` of `pool`: its
    block table's pages, taken from the page view."""
    groups, _, lanes = pool.shape
    view = jnp.take(pool.reshape(groups, -1, page, lanes), bt, axis=1)
    return jnp.swapaxes(view, 0, 1).reshape(
        bt.shape[0], groups, bt.shape[1] * page, lanes)


def _attention(q, kk, vv, mask, head_dim):
    """q `[B, G, n, W]` against keys and values in the pool's layout —
    every pool row `[G, R, W]` (no batch axis: nothing of the pool is
    copied per sequence) or the gathered views `[B, G, L, W]` — under an
    additive mask `[B, n, rows]`: `_sdpa_ref`'s arithmetic (products in
    the operands' dtype, float32 softmax), a head at a time. A head of a
    group sees its own lanes only: its query is zero on its neighbours'
    (exact: a product with zero adds nothing), and of the output row it
    keeps its own."""
    lanes = q.shape[-1]
    own = (jnp.arange(lanes) // head_dim
           == jnp.arange(lanes // head_dim)[:, None])[:, None, :]  # [u,1,W]
    kv = {3: 'gkl', 4: 'bgkl'}[kk.ndim]     # the pool's rows | the views
    q = jnp.where(own, q[:, :, None], 0).astype(q.dtype)     # [B,G,u,n,W]
    s = jnp.einsum('bguql,%s->bguqk' % kv, q, kk) * (1.0 / head_dim ** 0.5)
    p = jax.nn.softmax((s + mask[:, None, None]).astype(jnp.float32), axis=-1)
    o = jnp.einsum('bguqk,%s->bguql' % kv, p.astype(q.dtype), vv)
    return jnp.sum(jnp.where(own, o, 0), axis=2)                # [B,G,n,W]


@functools.partial(jax.jit, static_argnames=('page', 'scope', 'read'))
def _paged_call(q, k, v, ck, cv, bt, t, *, page, scope, read):
    """`paged_attention` on arrays. A function of its own under `jit`: a
    program traces and lowers it once however many layers call it (they
    share one function in the module; XLA inlines it), which is most of
    what a 48-layer program costs to trace and to hash for the compile
    cache on a run that compiles nothing."""
    b, n, heads, head_dim = q.shape
    groups, pool_rows, lanes = ck.shape
    nb = bt.shape[1]
    # write: token i of row s sits at absolute position t[s]+i; its
    # pool row is bt[s, pos // page] * page + pos % page. Garbage from
    # frozen rows stays inside the row's own blocks or on the scratch
    # page — both unreachable, see PagedKVCache invariants
    with _scope(scope + '.paged_write'):
        ck, cv = _write((ck, cv), [_pack(x.astype(ck.dtype), groups, lanes)
                                   for x in (k, v)], t, bt, page)
    kk, vv = ck, cv
    qpos = t[:, None] + jnp.arange(n)[None, :]               # [B, n]
    if read == 'pool':
        # a pool row (p, r) is logical position j*page + r of the
        # row whose FIRST block-table entry holding p is j (nb:
        # none, past every query). A shared page is visible to
        # each holder; scratch page 0 fills every unused entry,
        # so its first j lies past the row's length, and an idle
        # row (t = 0, all scratch) sees position 0 as below.
        with _scope(scope + '.mask'):
            holds = bt[:, :, None] == jnp.arange(pool_rows // page)
            first = jnp.min(jnp.where(
                holds, jnp.arange(nb)[None, :, None], nb), axis=1)
            kpos = (first[:, :, None] * page
                    + jnp.arange(page)).reshape(b, pool_rows)
    else:
        # each row's logical [L] view through its block table (this
        # step's rows included — written above). The views are
        # [B, G, L, W] activations; persistent memory stays
        # page-granular, which is where the density win lives.
        with _scope(scope + '.paged_gather'):
            kk, vv = _row_views(ck, bt, page), _row_views(cv, bt, page)
        kpos = jnp.arange(nb * page)[None, :]
    # per-row validity mask: query i of row s sits at absolute
    # position t[s]+i and sees logical positions <= it
    with _scope(scope + '.mask'):
        allow = qpos[:, :, None] >= kpos[:, None, :]
        mask = jnp.where(allow, 0.0, -1e9).astype(jnp.float32)
    with _scope(scope + '.core'):
        out = _attention(_pack(q, groups, lanes), kk, vv, mask, head_dim)
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(
            b, n, -1, head_dim)[:, :, :heads]
    return out, ck, cv


def paged_attention(q, k, v, cache, scope):
    """Write this call's K/V rows `[B, n, H, Dh]` into `cache`'s pools at
    each row's length and attend q over what the row then holds, causally.
    Returns (attention output `[B, n, H, Dh]` as a Tensor, the cache with
    the new pools and `kv_read` set). `scope` prefixes the named scopes
    of the device ops (`<scope>.paged_write`, `.paged_gather`, `.mask`,
    `.core`). The read is chosen by shape at trace time (`paged_kv_read`;
    same arithmetic either way, keys only come in another order)."""
    q, k, v = _raw_leaf(q), _raw_leaf(k), _raw_leaf(v)
    ck, cv = _raw_leaf(cache.k), _raw_leaf(cache.v)
    n, heads, head_dim = q.shape[1:]
    page, pool_rows = cache.page_size, ck.shape[1]
    want = paged_pool_shape(heads, head_dim, pool_rows // page, page)
    if ck.shape != want:
        raise ValueError(
            'K/V pool %s is not the pool of %d heads of %d (%s: '
            'paged_pool_shape)' % (ck.shape, heads, head_dim, want))
    t = cache.lengths                       # [B] per-row write offsets
    bt = jnp.asarray(cache.block_tables)    # [B, nb] physical page ids
    L = bt.shape[1] * page
    if not isinstance(t, jax.core.Tracer) and int(jnp.max(t)) + n > L:
        # (under jit lengths are traced; the serving engine guards
        # capacity at admission instead)
        raise ValueError(
            'paged cache overflow: max row length %d + %d new '
            'tokens > capacity %d' % (int(jnp.max(t)), n, L))
    read = paged_kv_read(q.shape[0], L, pool_rows)
    out, ck, cv = _paged_call(q, k, v, ck, cv, bt, jnp.asarray(t), page=page,
                              scope=scope, read=read)
    new_cache = PagedKVCache(Tensor(ck), Tensor(cv), bt, t, page)
    new_cache.kv_read = read
    return Tensor(out), new_cache


def paged_latent_rows(rows, cache, scope):
    """Write this call's latent rows `[B, n, width]` into `cache`'s pool
    at each row's length (`_write`: the pages and block tables K/V rows
    take; zeros fill the pool's lanes past `width`) and return (the rows
    each sequence then holds, `[B, capacity, W]` through its block table,
    as wide as the pool, the cache with the new pool). The
    read is ALWAYS of a sequence's own rows: attending over every pool
    row under a mask, as `paged_kv_read` chooses for K/V once the views
    are at least the pool, counts bytes, and a latent's scores cost every
    head `width` operations a row — a decode batch over a whole pool
    would be thousands of times the work. Rows at or past a sequence's
    length are garbage: the caller masks by position."""
    pool, page = cache.pool, cache.page_size
    bt = jnp.asarray(cache.block_tables)
    with _scope(scope + '.paged_write'):
        rows = jnp.pad(rows.astype(pool.dtype), (
            (0, 0), (0, 0), (0, pool.shape[-1] - rows.shape[-1])))
        pool, = _write((pool,), [rows[:, None]], jnp.asarray(cache.lengths),
                       bt, page)
    with _scope(scope + '.paged_gather'):
        # (not `_row_views`: with one group its swap of the group and
        # batch axes is a second copy of the views)
        held = jnp.take(pool[0].reshape(-1, page, pool.shape[-1]), bt,
                        axis=0).reshape(bt.shape[0], -1, pool.shape[-1])
    new_cache = PagedLatentCache(pool, bt, cache.lengths, cache.valid, page)
    new_cache.kv_read = 'gather'
    return held, new_cache
