"""The feed-forward layers the expert families share: a SwiGLU and the
expert layer that holds a share of its experts (DeepSeek-V3's gate).

Expert layer: s = sigmoid(W_r x) in float32 over all `num_experts`; the
`top_k` largest of s + bias (`e_score_correction_bias`: it chooses, it
does not weigh) are chosen; their weights are s at the chosen, divided
by their sum, times `scale`. y = shared(x) + sum_chosen w_e E_e(x), E
and shared SwiGLU of width f. `experts_held` = (lo, hi) says which expert
ids this program holds: the router keeps its width, the sum runs over
the chosen experts that are held, for every token, with no capacity and
no drop; a token none of whose experts is held gets the shared expert
alone, and nothing stands in for the experts held elsewhere.

The product over the held experts is one of two, chosen by shape when
the program is traced (`expert_path`): `dense` sends every token through
every held expert with its own weight of it, 0 where it did not choose
it; `grouped` multiplies only the chosen pairs that fell on held
experts, sorted by expert, in grouped matrix products (`_grouped`): a
held expert nobody chose is not read. The same sum either way.

A family names the layer's device ops by `scope` (`kimi.moe`,
`trinity.moe`: `<scope>.route`, `.experts`, `.shared`), from the closed
set in docs/observability.md.
"""
import jax
import jax.numpy as jnp

from ... import nn
from ...framework.core import Tensor, run_op
from ...nn import functional as F
from ...ops import flash_attention as fa

__all__ = ['SwiGLU', 'SparseMoe', 'route', 'expert_path']

_scope = jax.named_scope
_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
I32 = jnp.int32

# The shape rule's two thresholds. The dense product does `rows`
# operations a byte of the held experts' bfloat16 weights (2 a parameter
# and row, 2 bytes a parameter): under the v5e's ridge, 197 TFLOP/s over
# 819 GB/s, it is bound by the weights' read, above it by the MXU.
RIDGE_ROWS = 240
# ... and where it is bound by the read, the grouped product gains only
# the experts nobody chose: it keeps the dense one where this share or
# more of the held experts is expected to be touched.
TOUCHED_SHARE = 0.9


class SwiGLU(nn.Layer):
    def __init__(self, d, f):
        super().__init__()
        self.gate_proj = nn.Linear(d, f, bias_attr=False)
        self.up_proj = nn.Linear(d, f, bias_attr=False)
        self.down_proj = nn.Linear(f, d, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def route(x, w_r, bias, top_k, scale):
    """The router over ALL the experts, in float32. x `[T, d]`, w_r `[d,
    E]`, bias `[E]` (it chooses, it does not weigh). Returns (chosen
    expert ids `[T, top_k]`, their weights `[T, top_k]`: the sigmoid
    scores at the chosen, divided by their sum, times `scale`)."""
    s = jax.nn.sigmoid(jnp.einsum('td,de->te', x.astype(F32),
                                  w_r.astype(F32), precision=_HI))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def expert_path(rows, top_k, num_experts):
    """'dense' or 'grouped', from the shapes alone: the grouped product
    unless the dense one is bound by its weights' read (`rows` under
    `RIDGE_ROWS`) AND is expected to touch nearly every held expert
    anyway (`1 - (1 - top_k / num_experts) ** rows`, the share a held
    expert is chosen by some of `rows` independent tokens, at least
    `TOUCHED_SHARE`)."""
    touched = 1.0 - (1.0 - top_k / num_experts) ** rows
    return 'dense' if rows < RIDGE_ROWS and touched >= TOUCHED_SHARE \
        else 'grouped'


def _row_tile(rows, top_k, num_experts):
    """Rows the grouped product takes a tile: twice a held expert's
    expected pairs (`rows * top_k / num_experts`), a power of two from 8
    to 128, so that most experts' rows fit one tile and each touched
    expert's weights are read once (`_grouped` starts every expert's
    rows on a tile of their own), while the padding stays small. Timed
    on a v5e at the served shapes, twice was the fastest of 8 to 128
    rows in each (docs/serving.md)."""
    want = 2.0 * rows * top_k / num_experts
    return next(t for t in (8, 16, 32, 64, 128) if t >= want or t == 128)


def _lane_tile(n):
    """A tile of `n` (a weight's width): the largest multiple of 128 up
    to 1 152 that divides it (a bfloat16 tile of at most 2.3 MB, twice
    over in VMEM), or `n` whole where no such multiple does."""
    return next((t for t in range(1152, 0, -128) if n % t == 0), n)


def _gmm(lhs, rhs, sizes, dtype, tm, transpose_rhs=False):
    """`lhs` `[m, k]` rows in groups of `sizes` (`[G]`, in order from row
    0; rows past their sum are not computed: garbage) times group g's
    `rhs[g]`, `[k, n]` or with `transpose_rhs` `[n, k]` as the weights
    lie. The megablox kernel (`jax.experimental.pallas.ops.tpu`) where
    it runs (`flash_attention.is_available`: a TPU, or the Pallas
    interpreter); elsewhere XLA's ragged dot, the same products."""
    if fa.is_available():
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        n = rhs.shape[1 if transpose_rhs else 2]
        return gmm(lhs, rhs, sizes, dtype,
                   (tm, _lane_tile(lhs.shape[1]), _lane_tile(n)), None, None,
                   transpose_rhs, fa.interpret_mode())
    dims = jax.lax.RaggedDotDimensionNumbers(
        (((1,), (2 if transpose_rhs else 1,)), ((), ())), [0], [0])
    return jax.lax.ragged_dot_general(lhs, rhs, sizes, dims,
                                      preferred_element_type=dtype)


def _grouped(x, chosen, w, lo, w_gate, w_up, w_down, tm):
    """The held experts' part of the sum over the chosen pairs alone. x
    `[T, d]`, chosen and w `[T, k]`. The `T * k` pairs are laid out by
    expert, each with its token's row of x: a held expert's pairs from
    the start of a tile of `tm` rows, up to whole tiles (the padding
    rows weigh 0), so that no tile holds two experts and each touched
    expert's weights are read once a tile; the pairs on experts held
    elsewhere after them, not computed. The three products run over the
    groups (`_gmm`), the activated intermediate times the pair's weight
    in float32, the down product in float32, and each token sums its
    own pairs' rows. Returns (`[T, d]` in x's dtype, the rows the
    products multiply)."""
    t, k = chosen.shape
    n_held = w_gate.shape[0]
    pairs = t * k
    # every pair, and a tile's padding for each expert that can be touched
    m = -(-(pairs + min(n_held, pairs) * (tm - 1)) // tm) * tm
    local = chosen.reshape(-1) - lo
    held = (local >= 0) & (local < n_held)
    group = jnp.where(held, local, n_held)                  # [P]
    hot = (group[:, None] == jnp.arange(n_held + 1)).astype(I32)
    sizes = -(-jnp.sum(hot, axis=0)[:n_held] // tm) * tm    # whole tiles
    starts = jnp.cumsum(sizes) - sizes
    starts = jnp.append(starts, jnp.sum(sizes))             # then elsewhere
    # a pair's row: its group's start and the pairs of its group before it
    earlier = jnp.cumsum(hot, axis=0) - hot
    row = starts[group] + jnp.take_along_axis(earlier, group[:, None],
                                              axis=1)[:, 0]
    token = jnp.zeros((m,), I32).at[row].set(jnp.arange(pairs) // k)
    weight = jnp.zeros((m,), F32).at[row].set(w.reshape(-1))
    xs = x[token]                                           # [m, d]
    gate = _gmm(xs, w_gate, sizes, x.dtype, tm, transpose_rhs=True)
    up = _gmm(xs, w_up, sizes, x.dtype, tm, transpose_rhs=True)
    a = jax.nn.silu(gate.astype(F32)) * up.astype(F32) * weight[:, None]
    y = _gmm(a.astype(x.dtype), w_down, sizes, F32, tm)       # [m, d]
    y = jnp.where(held[:, None], y[row], 0.0).reshape(t, k, -1)
    return jnp.sum(y, axis=1).astype(x.dtype), jnp.sum(sizes)


def _held_share(chosen, w, lo, n_held):
    """`[T, n_held]`: each token's weight on each held expert (ids lo ..
    lo + n_held - 1), 0 where it did not choose it."""
    hit = chosen[..., None] == lo + jnp.arange(n_held)       # [T, k, E']
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)


class SparseMoe(nn.Layer):
    """The expert layer that holds experts [lo, hi) of `num_experts`,
    each and the shared one a SwiGLU of width `f`."""

    def __init__(self, d, f, num_experts, top_k, scale, experts_held,
                 num_shared=1, init_std=0.02, scope='moe', router=route):
        super().__init__()
        self.top_k, self.scale, self.scope = top_k, scale, scope
        self.num_experts = num_experts
        # the product the last trace took ('dense' | 'grouped'): the
        # block puts it on the cache it returns as `experts`
        self.path = None
        self._route = router         # `route`, or a family's stand-in
        self.lo, hi = experts_held
        self.n_held = n = hi - self.lo
        init = nn.initializer.Normal(0.0, init_std)
        par = lambda shape, dtype=None: self.create_parameter(
            shape, dtype=dtype, default_initializer=init)
        # the router and its correction stay float32 whatever the rest is
        self.router = par([d, num_experts], 'float32')
        self.e_score_correction_bias = par([num_experts], 'float32')
        # all three `[held, f, d]`: the width d innermost is how a decode
        # step's products take them as they lie (`[held, d, f]` for the
        # first two had the compiler keep a transposed copy of every
        # layer's across the burst, 4.6 GB at Kimi-Linear's sizes)
        self.gate_proj, self.up_proj = par([n, f, d]), par([n, f, d])
        self.down_proj = par([n, f, d])
        self.shared = SwiGLU(d, f * num_shared)

    def _routed(self, x, real, w_r, bias, w_gate, w_up, w_down):
        """x `[B, n, d]` -> (the held experts' part of the sum `[B, n,
        d]`, counters of the `real` `[B, n]` tokens). Whatever the
        routing nothing is dropped: the dense product or the grouped
        one (`expert_path`), the same sum."""
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        rows = x.shape[0]
        path = expert_path(rows, self.top_k, self.num_experts)
        with _scope(self.scope + '.route'):
            chosen, w = self._route(x, w_r, bias, self.top_k, self.scale)
            share = _held_share(chosen, w, self.lo, self.n_held)
            load = jnp.sum((share > 0) & real.reshape(-1, 1), axis=0)
            counters = {
                'moe_pairs': jnp.sum(real).astype(jnp.int32) * self.top_k,
                'moe_pairs_held': jnp.sum(load).astype(jnp.int32),
                'moe_experts_touched': jnp.sum(load > 0).astype(jnp.int32),
                'moe_load_max': jnp.max(load).astype(jnp.int32)}
        with _scope(self.scope + '.experts'):
            if path == 'grouped':
                y, counters['moe_expert_rows'] = _grouped(
                    x, chosen, w, self.lo, w_gate, w_up, w_down,
                    _row_tile(rows, self.top_k, self.num_experts))
            else:
                a = jax.nn.silu(
                    jnp.einsum('td,efd->etf', x, w_gate).astype(F32))
                a = a * jnp.einsum('td,efd->etf', x, w_up).astype(F32) \
                    * share.T[..., None]
                y = jnp.einsum('etf,efd->td', a.astype(x.dtype), w_down)
                counters['moe_expert_rows'] = jnp.int32(self.n_held * rows)
        self.path = path
        return y.reshape(shape), counters

    def forward(self, x, real=None):
        """y on the normal path. With `real` `[B, n]` bool (serving:
        which tokens are real) it works on raw arrays, as the mixers do
        under a cache, and returns (y, counters of the routing over the
        real tokens: device scalars)."""
        weights = (self.router, self.e_score_correction_bias,
                   self.gate_proj, self.up_proj, self.down_proj)
        if real is None:
            everyone = jnp.ones(tuple(x.shape[:2]), bool)
            y = run_op('sparse_experts', lambda x, *ws: self._routed(
                x, everyone, *ws)[0], x, *weights)
            counters = None
        else:
            y, counters = self._routed(x._data, real,
                                       *(w._data for w in weights))
            y = Tensor(y)
        with _scope(self.scope + '.shared'):
            y = y + self.shared(x)
        return y if real is None else (y, counters)
