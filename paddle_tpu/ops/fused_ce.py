"""Fused LM-head + softmax cross-entropy, chunked over rows.

The dominant non-matmul cost of LM training at realistic vocab sizes is
the logits tensor: a [batch*seq, vocab] bf16 matmul output that the
straight path (head matmul -> cross_entropy) materializes in HBM, copies
to f32 for the stable logsumexp, and materializes AGAIN as softmax probs
in the backward. On the BERT-base bench config that is ~2 GB of f32
logits + ~1 GB of probs per step — measured at ~13 ms/step of pure HBM
traffic on v5e (builder profile, 2026-08-01: docs/profile_summary_r5.txt).

This op computes mean softmax-CE of `x @ w (+bias)` against integer
labels WITHOUT ever materializing the full [rows, vocab] logits:

- forward: python-unrolled loop over row chunks; each chunk computes its
  logits tile, reduces it to (logsumexp, picked-label logit) in f32, and
  discards it. Residuals are O(rows), not O(rows*vocab).
- backward (custom_vjp): re-computes each chunk's logits tile, forms
  softmax(logits) - onehot(label) on the fly (an elementwise epilogue
  XLA fuses into the consuming matmuls), and emits dx per chunk and a
  f32-accumulated dw. MXU matmuls use f32 accumulation
  (preferred_element_type) so the chunked dw matches the one-shot matmul.

Cost: one extra logits-tile matmul (the backward recompute) — ~25% more
head flops — traded for removing every [rows, vocab] HBM round-trip.

Reference counterpart: the reference reaches the same end by op fusion
on GPU (paddle/fluid/operators/fused/ family; c_softmax_with_cross_entropy
fuses the vocab-PARALLEL variant, operators/collective/
c_softmax_with_cross_entropy_op.cu) — this is the XLA/TPU-native design:
chunk at the algorithm level, let the compiler fuse the epilogues.
"""
import functools
import os

import jax
import jax.numpy as jnp

__all__ = ['linear_cross_entropy_arrays', 'env_chunk_rows',
           'logits_sharding']

_MAX_CHUNKS = 64

# Vocab-parallel hint (reference: the c_softmax_with_cross_entropy
# vocab-PARALLEL collective op). Under tensor parallelism GSPMD's cost
# model prefers gathering the vocab axis for the CE region over
# vocab-parallel local reductions + a small all-reduce
# (test_hlo_collectives documents the r4 behavior). When a strategy
# enters `logits_sharding(s)` around the step trace, every transient
# logits tile is constrained to `s` ([rows-axes, 'mp']), which forces
# the partitioner onto the vocab-parallel plan. A ContextVar, not a
# module global: concurrent traces (a hinted train step and an
# unhinted eval step on another thread) must not see each other's
# sharding — a wrong-mesh constraint is a trace error at best.
import contextvars

_LOGITS_SHARDING = contextvars.ContextVar('fused_ce_logits_sharding',
                                          default=None)


class logits_sharding:
    """Context manager: constrain fused-CE logits tiles to `sharding`."""

    def __init__(self, sharding):
        self.sharding = sharding

    def __enter__(self):
        self._token = _LOGITS_SHARDING.set(self.sharding)
        return self

    def __exit__(self, *exc):
        _LOGITS_SHARDING.reset(self._token)
        return False


def _maybe_constrain(af):
    s = _LOGITS_SHARDING.get()
    if s is None:
        return af
    return jax.lax.with_sharding_constraint(af, s)


def env_chunk_rows():
    """PADDLE_TPU_FUSED_CE_CHUNK: rows per logits tile (default 4096).

    Bigger tiles = fewer dw accumulation passes (each one is a
    read-modify-write of the full f32 [d, vocab] accumulator) but a
    larger transient logits tile. 4096 rows x 30k vocab bf16 = 250 MB —
    comfortably HBM-resident on any TPU generation.
    """
    raw = os.environ.get('PADDLE_TPU_FUSED_CE_CHUNK')
    if raw is None:
        return 4096
    try:
        val = int(raw)
    except ValueError:
        import warnings
        warnings.warn('PADDLE_TPU_FUSED_CE_CHUNK=%r is not an integer; '
                      'using the default 4096' % (raw,))
        return 4096
    if val < 1:
        raise ValueError(
            'PADDLE_TPU_FUSED_CE_CHUNK must be >= 1, got %d' % val)
    return val


def _chunk_plan(rows, chunk):
    """(chunk, n_chunks, padded_rows) with the unroll bounded."""
    # never a chunk larger than the input: padding rounds rows up to a
    # chunk multiple, and padded rows cost real (masked) matmul flops
    chunk = max(1, min(int(chunk), rows))
    n = -(-rows // chunk)
    if n > _MAX_CHUNKS:  # keep the unrolled program a sane size
        chunk = -(-rows // _MAX_CHUNKS)
        n = -(-rows // chunk)
    return chunk, n, n * chunk


def _pad_rows(x, labels, rows_p, ignore_index):
    rows = x.shape[0]
    if rows_p == rows:
        return x, labels
    pad = rows_p - rows
    x = jnp.pad(x, ((0, pad), (0, 0)))
    labels = jnp.pad(labels, (0, pad), constant_values=ignore_index)
    return x, labels


def _tile_logits(xc, w, bias):
    logits = jnp.matmul(xc, w)
    if bias is not None:
        logits = logits + bias
    return _maybe_constrain(logits.astype(jnp.float32))


def _label_onehot(safe, shape):
    """[rows, vocab] bool mask selecting each row's label column, built
    by iota-compare rather than gather/one_hot: elementwise over the
    vocab axis, so GSPMD keeps it sharded with the logits tile (a
    vocab-axis gather would make the partitioner all-gather the tile).
    Shared by fwd (label-logit pick) and bwd (softmax - onehot)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) == safe[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def linear_cross_entropy_arrays(x, w, labels, bias, ignore_index, chunk):
    """Mean softmax-CE of (x @ w + bias) vs labels over valid rows.

    x: [rows, d] float; w: [d, vocab]; labels: [rows] int;
    bias: [vocab] or None. Rows whose label == ignore_index contribute
    nothing; the mean divides by the valid count (matching
    F.cross_entropy(reduction='mean', ignore_index=...)).
    Returns a scalar in x.dtype.
    """
    loss, _ = _lce_fwd(x, w, labels, bias, ignore_index, chunk)
    return loss


def _lce_fwd(x, w, labels, bias, ignore_index, chunk):
    rows = x.shape[0]
    v = w.shape[1]
    chunk, n, rows_p = _chunk_plan(rows, chunk)
    xp, lp = _pad_rows(x, labels, rows_p, ignore_index)
    # STRIDED chunking (chunk i = rows i, i+n, i+2n, ...): under data
    # parallelism the flattened row axis is dp-sharded contiguously, so
    # contiguous chunks would each live on ONE dp group — every chunk
    # would either run on a fraction of the devices or force a per-chunk
    # redistribution. Strided chunks hit every dp shard evenly. Rows are
    # independent in CE, so order only matters for the final stitch
    # (the [chunk, n] stack below mirrors the reshape here).
    x3 = xp.reshape(chunk, n, -1)
    l2 = lp.reshape(chunk, n)
    lse_parts, picked_parts = [], []
    for i in range(n):
        xc = x3[:, i, :]
        lc = l2[:, i]
        af = _tile_logits(xc, w, bias)
        m = af.max(axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(af - m[:, None]), axis=-1))
        safe = jnp.clip(lc, 0, v - 1).astype(jnp.int32)
        # pick the label logit as a masked SUM, not take_along_axis: a
        # gather along the vocab axis defeats GSPMD when the head weight
        # is mp-sharded, while iota-compare + sum partitions into a
        # local reduce + a tiny all-reduce — the vocab-parallel CE
        # pattern (reference: c_softmax_with_cross_entropy). The
        # elementwise cost fuses into the pass that reads af anyway.
        picked = jnp.sum(jnp.where(_label_onehot(safe, af.shape),
                                   af, 0.0), axis=-1)
        lse_parts.append(lse)
        picked_parts.append(picked)
    lse = jnp.stack(lse_parts, axis=1).reshape(rows_p)
    picked = jnp.stack(picked_parts, axis=1).reshape(rows_p)
    valid = lp != ignore_index
    per_row = jnp.where(valid, lse - picked, 0.0)
    denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    loss = (jnp.sum(per_row) / denom).astype(x.dtype)
    # residuals are O(rows): the logits tiles are recomputed in _lce_bwd
    return loss, (x, w, labels, bias, lse, denom)


def _lce_bwd(ignore_index, chunk, res, g):
    x, w, labels, bias, lse, denom = res
    rows, d = x.shape
    v = w.shape[1]
    chunk, n, rows_p = _chunk_plan(rows, chunk)
    xp, lp = _pad_rows(x, labels, rows_p, ignore_index)
    gg = g.astype(jnp.float32) / denom
    dx_parts = []
    dw = jnp.zeros((d, v), jnp.float32)
    db = jnp.zeros((v,), jnp.float32) if bias is not None else None
    # same strided chunk layout as the forward (see _lce_fwd)
    x3 = xp.reshape(chunk, n, d)
    l2 = lp.reshape(chunk, n)
    lse2 = lse.reshape(chunk, n)
    for i in range(n):
        xc = x3[:, i, :]
        lc = l2[:, i]
        lse_c = lse2[:, i]
        af = _tile_logits(xc, w, bias)
        p = jnp.exp(af - lse_c[:, None])
        valid = lc != ignore_index
        safe = jnp.clip(lc, 0, v - 1).astype(jnp.int32)
        onehot = _label_onehot(safe, p.shape)
        # d(CE)/d(logits) = softmax - onehot, zeroed on ignored rows; the
        # whole epilogue is elementwise so XLA fuses it into both
        # consuming matmuls — p never round-trips HBM at full precision
        p = (p - onehot) * (gg * valid.astype(jnp.float32))[:, None]
        pc = p.astype(w.dtype)
        dx_parts.append(
            jnp.matmul(pc, w.T,
                       preferred_element_type=jnp.float32).astype(x.dtype))
        dw = dw + jnp.matmul(xc.T, pc, preferred_element_type=jnp.float32)
        if db is not None:
            db = db + p.sum(axis=0)
    dx = jnp.stack(dx_parts, axis=1).reshape(rows_p, d)[:rows]
    dlabels = jnp.zeros(labels.shape, jax.dtypes.float0)
    return (dx, dw.astype(w.dtype), dlabels,
            None if bias is None else db.astype(bias.dtype))


linear_cross_entropy_arrays.defvjp(_lce_fwd, _lce_bwd)
