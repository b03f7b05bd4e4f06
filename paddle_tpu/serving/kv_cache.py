"""Slot and page bookkeeping for the serving caches, and the per-layer
cache interface between the engine and a model.

`PagedKVCache` (text/models/cache.py): per layer, a K pool and a V pool
of `num_pages * page_size` token rows, `[G, rows, W]` — a row is `W`
lanes wide (one head of 128, or the narrower heads that fill 128 side by
side), the `G` groups outermost: the one layout the write and the read
both take as it lies, so no program copies a pool — addressed through
per-sequence block tables: a sequence only holds the pages it needs, and
sequences sharing a prompt prefix map their leading block-table entries
to the SAME physical page. `PageAllocator` (refcounted free list) and
`PrefixCache` (block-hash -> page, LRU) own the host side;
`SlotAllocator` owns which sequence rows (slots) are free and who holds
them.

Beside pages, the engine keeps a second kind of state for a layer that
names it (`RecurrentSpec`): `[num_seqs, ...]` arrays that belong to a
SLOT, not to pages — see "the per-layer cache interface" below.

The K/V pools need no clearing on reuse: a new occupant's prefill writes
from its own offset and the validity mask never lets a query see rows
at/beyond the owning sequence's current length, so a previous occupant's
rows are unreachable the moment the length resets (the engine's first
prefill chunk writes back the new occupant's own length).
"""
import heapq
import time
from collections import OrderedDict

__all__ = ['SlotAllocator', 'PageAllocator',
           'PrefixCache', 'build_paged_pools', 'SCRATCH_PAGE',
           'cache_specs', 'kv_row_bytes', 'latent_row_bytes',
           'state_bytes_per_seq',
           'layer_caches', 'layer_counters', 'layer_state']


class SlotAllocator:
    """Free-list over a fixed number of sequence slots (the rows of the
    engine's block tables and per-sequence state).

    Lowest-index-first allocation (a heap, not a LIFO stack) keeps slot
    assignment deterministic for a given arrival order — parity tests
    replay the same workload and must see the same slot layout.
    """

    def __init__(self, num_slots, clock=None):
        if num_slots < 1:
            raise ValueError('num_slots must be >= 1, got %d' % num_slots)
        self.num_slots = num_slots
        self.clock = clock or time.monotonic
        self._free = list(range(num_slots))
        heapq.heapify(self._free)
        self._owner = {}  # slot -> opaque owner (request id)
        self._held_since = {}  # slot -> advance timestamp at alloc
        self._integral = 0.0   # integral of in_use over time (slot*s)
        self._last_t = self.clock()

    def _advance(self):
        """Accrue the occupancy integral up to now; returns now. Every
        state change routes through here, so per-request holding times
        measured from the SAME timestamps sum exactly to the pool
        integral (the billing cross-check in bench/request_report)."""
        now = self.clock()
        self._integral += len(self._owner) * (now - self._last_t)
        self._last_t = now
        return now

    def touch(self):
        """Public advance: accrue the integral and return the shared
        timestamp (schedulers stamp request holding windows with it)."""
        return self._advance()

    def page_seconds(self):
        """The pool-occupancy integral: sum over time of slots held, in
        slot·seconds (one slot == the allocation granule == one 'page'
        for attribution purposes)."""
        self._advance()
        return self._integral

    def alloc(self, owner):
        """Claim the lowest free slot for `owner`; None when full."""
        if not self._free:
            return None
        now = self._advance()
        slot = heapq.heappop(self._free)
        self._owner[slot] = owner
        self._held_since[slot] = now
        return slot

    def free(self, slot):
        """Release `slot` back to the free list; returns the seconds it
        was held (measured on the integral's own timestamps).

        Freeing a slot that is not currently allocated — including a
        second free of the same slot — raises: a silent double-free here
        would put one slot on the free list twice and hand the SAME KV
        rows to two requests, which corrupts outputs rather than
        crashing. The page allocator below enforces the same rule.
        """
        if slot not in self._owner:
            raise ValueError(
                'slot %r is not allocated (double-free, or never '
                'allocated)' % (slot,))
        now = self._advance()
        del self._owner[slot]
        heapq.heappush(self._free, slot)
        return now - self._held_since.pop(slot)

    def owner_of(self, slot):
        return self._owner.get(slot)

    @property
    def in_use(self):
        return len(self._owner)

    @property
    def available(self):
        return len(self._free)

    @property
    def occupancy(self):
        """Fraction of slots occupied, the per-step utilization metric."""
        return len(self._owner) / float(self.num_slots)


# physical page 0 is never handed out: frozen/retired sequence rows keep
# their block-table entries pointed here so in-program garbage writes
# (padded prefill tails, masked decode lanes) land on rows nobody reads
SCRATCH_PAGE = 0


class PageAllocator:
    """Refcounted free list over the physical pages of a paged KV pool.

    Lowest-index-first allocation (heap) keeps page layout deterministic
    for a given workload, like SlotAllocator. Refcounts exist because a
    page can be held by several owners at once — every sequence whose
    block table maps to it, plus the prefix cache itself. `alloc` hands
    out a page at refcount 1; `incref`/`decref` move it up and down;
    the page returns to the free list only at refcount 0.
    """

    def __init__(self, num_pages, clock=None):
        if num_pages < 2:
            raise ValueError('num_pages must be >= 2 (page 0 is the '
                             'reserved scratch page), got %d' % num_pages)
        self.num_pages = num_pages
        self.clock = clock or time.monotonic
        self._free = list(range(1, num_pages))
        heapq.heapify(self._free)
        self._refs = {}  # page -> refcount (> 0)
        self._integral = 0.0  # integral of in_use over time (page*s)
        self._last_t = self.clock()

    def _advance(self):
        """Accrue the occupancy integral (distinct pages referenced x
        elapsed time) up to now; returns now. Shared pages count ONCE
        here no matter how many sequences map them — per-request
        attribution can therefore exceed the pool integral exactly when
        prefix sharing saves pool space."""
        now = self.clock()
        self._integral += len(self._refs) * (now - self._last_t)
        self._last_t = now
        return now

    def touch(self):
        """Public advance: accrue the integral and return the shared
        timestamp (schedulers stamp request holding windows with it)."""
        return self._advance()

    def page_seconds(self):
        """The pool-occupancy integral in page·seconds."""
        self._advance()
        return self._integral

    def alloc(self):
        """Claim the lowest free page at refcount 1; None when empty."""
        if not self._free:
            return None
        self._advance()
        page = heapq.heappop(self._free)
        self._refs[page] = 1
        return page

    def incref(self, page):
        if page not in self._refs:
            raise ValueError('page %r is not allocated' % (page,))
        self._refs[page] += 1

    def decref(self, page):
        """Drop one reference; frees the page at zero. Mirrors
        SlotAllocator.free's strictness: decref of an unallocated page
        (double-free included) raises instead of silently re-listing a
        page two owners would then share."""
        if page == SCRATCH_PAGE:
            raise ValueError('page 0 is the reserved scratch page')
        if page not in self._refs:
            raise ValueError(
                'page %r is not allocated (double-free, or never '
                'allocated)' % (page,))
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._advance()
            del self._refs[page]
            heapq.heappush(self._free, page)
            return True
        return False

    # free == "I was the only owner and I'm done" — intent-revealing
    # alias used by non-sharing call sites
    free = decref

    def refcount(self, page):
        return self._refs.get(page, 0)

    @property
    def in_use(self):
        return len(self._refs)

    @property
    def available(self):
        return len(self._free)

    @property
    def occupancy(self):
        """Fraction of allocatable pages currently referenced."""
        return len(self._refs) / float(self.num_pages - 1)


class PrefixCache:
    """Block-aligned prompt-prefix cache: chain-hash of token blocks ->
    the physical page already holding that block's K/V.

    Hashing is a CHAIN (each block's key folds in the previous block's
    key), so a hit on block b proves the entire prefix [0, (b+1)*P)
    matches — not just block b's own tokens. Only FULL blocks are ever
    cached, and `match` never covers a whole prompt (at least one token
    must remain to prefill, because the final chunk's logits seed the
    first generated token). Divergence inside a block therefore needs no
    page copy: the shared pages are immutable full blocks, and the
    divergent tail is prefilled into the requester's own private pages —
    copy-on-write degenerates to fill-on-write.

    The cache holds one allocator reference per entry, so published
    pages survive their publisher's retirement. `evict` drops
    least-recently-matched entries whose page nobody else references.
    """

    def __init__(self, page_size, allocator):
        if page_size < 1:
            raise ValueError('page_size must be >= 1')
        self.page_size = int(page_size)
        self.allocator = allocator
        self._pages = OrderedDict()   # chain hash -> page (LRU order)
        self.hits = 0                 # full blocks served from cache
        self.misses = 0               # full blocks that had to prefill

    @staticmethod
    def _chain(prev, block_tokens):
        return hash((prev, tuple(block_tokens)))

    def match(self, prompt):
        """Longest cached chain of full blocks covering at most
        len(prompt)-1 tokens: returns the page list (no refs taken —
        the caller increfs what it keeps)."""
        P = self.page_size
        nfull = (len(prompt) - 1) // P
        pages, h = [], None
        for b in range(nfull):
            h = self._chain(h, prompt[b * P:(b + 1) * P])
            page = self._pages.get(h)
            if page is None:
                self.misses += nfull - b
                break
            self._pages.move_to_end(h)
            pages.append(page)
            self.hits += 1
        return pages

    def publish(self, prompt, block_idx, page):
        """Register `page` as holding prompt block `block_idx` (all of
        whose tokens must already be prefilled into it). Takes one
        allocator reference. No-op (False) when the chain is already
        cached — the existing entry wins and the duplicate page stays
        private to its sequence."""
        P = self.page_size
        h = None
        for b in range(block_idx + 1):
            h = self._chain(h, prompt[b * P:(b + 1) * P])
        if h in self._pages:
            return False
        self.allocator.incref(page)
        self._pages[h] = page
        return True

    def evict(self, need):
        """Drop least-recently-matched entries whose page only the
        cache still references, until `need` pages were freed (or the
        candidates run out). Returns pages freed. Entries whose page a
        resident sequence still maps are skipped — eviction must never
        pull a page out from under a live block table."""
        freed = 0
        for h in list(self._pages):
            if freed >= need:
                break
            page = self._pages[h]
            if self.allocator.refcount(page) == 1:
                del self._pages[h]
                self.allocator.decref(page)
                freed += 1
        return freed

    def clear(self):
        """Drop every entry (each releases its cache reference)."""
        for h, page in list(self._pages.items()):
            del self._pages[h]
            self.allocator.decref(page)

    def __len__(self):
        return len(self._pages)


# ---- the per-layer cache interface --------------------------------------
#
# A model says what each of its layers keeps (`model.cache_specs()`,
# text/models/cache.py): rows of K and V in the page pool, or a fixed
# set of arrays per sequence that every token rewrites. Everything the
# engine holds on the device is built from those specs here, and so are
# the cache objects a dispatch hands the model; the engine reads none of
# a model's attributes.

def cache_specs(model):
    """The model's per-layer specs, a list with one entry per layer."""
    specs = getattr(model, 'cache_specs', None)
    if specs is None:
        raise TypeError(
            '%s names no per-layer caches: a served model defines '
            'cache_specs() (text/models/cache.py)' % type(model).__name__)
    return list(specs())


def _is_paged(spec):
    from ..text.models.cache import PagedKVSpec
    return isinstance(spec, PagedKVSpec)


def _is_latent(spec):
    from ..text.models.cache import PagedLatentSpec
    return isinstance(spec, PagedLatentSpec)


def _nbytes(shape, dtype):
    import jax.numpy as jnp
    n = jnp.dtype(dtype).itemsize
    for d in shape:
        n *= int(d)
    return n


def kv_row_bytes(specs):
    """Bytes one held token costs over the layers that keep rows in
    pages (K and V of every head, or one latent row) — the conversion
    factor between page·seconds and byte·seconds for per-tenant
    billing."""
    return sum(2 * _nbytes((s.num_heads, s.head_dim), s.dtype)
               for s in specs if _is_paged(s)) + latent_row_bytes(specs)


def latent_row_bytes(specs):
    """The latent layers' part of `kv_row_bytes`."""
    return sum(_nbytes((s.width,), s.dtype) for s in specs if _is_latent(s))


def state_bytes_per_seq(specs):
    """Bytes one resident sequence keeps in the recurrent layers,
    whatever its length."""
    return sum(_nbytes(shape, dtype) for s in specs
               if not (_is_paged(s) or _is_latent(s))
               for shape, dtype in s.arrays)


def build_paged_pools(model, num_pages, page_size, num_seqs=0):
    """The engine's persistent device state, one entry per layer:
    a (k_pool, v_pool) pair `[G, num_pages * page_size, W]` (pool row
    `page * page_size + r`; `cache.paged_pool_shape`) for a layer that
    keeps K/V rows, one pool (`cache.latent_pool_shape`) for a latent
    layer, a tuple of `[num_seqs, ...]` arrays for a recurrent
    one. Block tables / lengths stay host-side (the engine passes them
    per dispatch)."""
    import jax.numpy as jnp
    from ..text.models.cache import latent_pool_shape, paged_pool_shape
    state = []
    for spec in cache_specs(model):
        if _is_paged(spec):
            shape = paged_pool_shape(spec.num_heads, spec.head_dim,
                                     num_pages, page_size)
            state.append((jnp.zeros(shape, spec.dtype),
                          jnp.zeros(shape, spec.dtype)))
        elif _is_latent(spec):
            state.append((jnp.zeros(latent_pool_shape(
                spec.width, num_pages, page_size), spec.dtype),))
        else:
            state.append(tuple(jnp.zeros((num_seqs,) + tuple(shape), dtype)
                               for shape, dtype in spec.arrays))
    return state


def layer_caches(specs, state, block_tables, lengths, valid, page_size,
                 slot=None):
    """One cache object per layer for one dispatch over `state`.
    `lengths` / `valid` `[B]`: tokens each row holds before the call and
    how many of the call's tokens are real for it; `page_size`: rows a
    page of the K/V pools holds (their shape does not say). With `slot`
    (a traced scalar: the one-row prefill program) a recurrent layer's
    arrays are that sequence's row alone."""
    import jax
    from ..framework.core import Tensor
    from ..text.models.cache import (PagedKVCache, PagedLatentCache,
                                     RecurrentCache)
    caches = []
    for spec, arrays in zip(specs, state):
        if _is_paged(spec):
            k, v = arrays
            caches.append(PagedKVCache(Tensor(k), Tensor(v), block_tables,
                                       lengths, page_size))
            continue
        if _is_latent(spec):
            caches.append(PagedLatentCache(arrays[0], block_tables, lengths,
                                           valid, page_size))
            continue
        if slot is not None:
            arrays = [jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
                      for a in arrays]
        caches.append(RecurrentCache(arrays, lengths, valid))
    return caches


def layer_counters(caches):
    """What the layers counted in the forward that returned `caches`
    (`counters` on a cache, text/models/cache.py): {name: device scalar},
    added up over the layers — the largest for a name ending `_max`.
    Empty for a model that counts nothing."""
    import jax.numpy as jnp
    out = {}
    for c in caches:
        for name, value in (getattr(c, 'counters', None) or {}).items():
            if name not in out:
                out[name] = value
            elif name.endswith('_max'):
                out[name] = jnp.maximum(out[name], value)
            else:
                out[name] = out[name] + value
    return out


def layer_state(state, caches, slot=None):
    """The device state after a forward returned `caches`: the new pools,
    and the recurrent arrays (with `slot`, written back into that row of
    `state`'s)."""
    import jax
    from ..text.models.cache import PagedKVCache, PagedLatentCache
    out = []
    for old, c in zip(state, caches):
        if isinstance(c, PagedKVCache):
            out.append((c.k._data, c.v._data))
        elif isinstance(c, PagedLatentCache):
            out.append((c.pool,))
        elif slot is None:
            out.append(tuple(c.arrays))
        else:
            out.append(tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    a, new.astype(a.dtype), slot, axis=0)
                for a, new in zip(old, c.arrays)))
    return out
