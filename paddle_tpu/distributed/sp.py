"""Sequence-parallel execution context (beyond-reference, SURVEY.md §5.7).

The strategy compiler (fleet_train_step) builds an sp state dict when
`strategy.sequence_parallel` and the mesh's 'sp' degree > 1, and the
TrainStep activates it ONLY around its own trace/execution (so a plain
eval/generation call outside the step keeps ordinary attention); while
active, every `F.scaled_dot_product_attention` call routes through ring
attention (K/V rotating over ICI via ppermute, ops/ring_attention.py) or
Ulysses all-to-all — the model code does not change between sp=1 and sp>1.

The reference has no sequence parallelism; its long-sequence levers are
recompute + pipeline (SURVEY §5.7). Here the 'sp' mesh axis shards the
sequence dimension of activations end-to-end: embeddings/MLP/layernorm are
token-local (XLA SPMD handles them), attention is the one op that mixes
tokens — and it goes through the ring.
"""
import functools

from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ['enable_sequence_parallel', 'disable_sequence_parallel',
           'sequence_parallel_state', 'sp_attention', 'make_sp_state',
           'sp_scope']

_STATE = {'active': None}


def make_sp_state(mesh, axis='sp', mode='ring', batch_axes=(),
                  head_axis=None):
    """Build (without activating) an sp routing state. batch_axes/head_axis
    describe how the OTHER q/k/v dims are sharded so shard_map's specs keep
    dp/mp layouts intact."""
    assert mode in ('ring', 'ulysses', 'zigzag'), mode
    return {'mesh': mesh, 'axis': axis, 'mode': mode,
            'batch_axes': tuple(batch_axes), 'head_axis': head_axis}


def enable_sequence_parallel(mesh, axis='sp', mode='ring', batch_axes=(),
                             head_axis=None):
    _STATE['active'] = make_sp_state(mesh, axis, mode, batch_axes, head_axis)


def disable_sequence_parallel():
    _STATE['active'] = None


class sp_scope:
    """Context manager activating an sp state only around a step's
    trace/execution — prevents the global context from hijacking eval or
    generation calls made between training steps."""

    def __init__(self, state):
        self._state = state

    def __enter__(self):
        self._saved = _STATE['active']
        if self._state is not None:
            _STATE['active'] = self._state
        return self

    def __exit__(self, *exc):
        _STATE['active'] = self._saved
        return False


def sequence_parallel_state():
    return _STATE['active']


def sp_attention(q, k, v, causal, scale, state=None, dropout_p=0.0,
                 dropout_key=None):
    """Attention over [B, N, H, D] with N sharded on the sp axis.

    Called with GLOBAL (traced) arrays inside jit; shard_map splits the
    sequence and runs the ring/Ulysses kernel per device.

    dropout_p/dropout_key: attention-prob dropout; the replicated key
    crosses the shard_map boundary and is folded with the sp rank inside,
    so every sequence shard draws independent masks (sp-aware RNG — the
    mp RNGStatesTracker pattern applied to the sequence axis).
    """
    import jax
    from jax import lax
    from ..ops import ring_attention as ra

    st = state or _STATE['active']
    mesh, axis, mode = st['mesh'], st['axis'], st['mode']
    b_ax = st['batch_axes'] or None
    if b_ax is not None and len(b_ax) == 1:
        b_ax = b_ax[0]
    spec = P(b_ax, axis, st['head_axis'], None)
    if mode == 'zigzag':
        n_dev = mesh.shape[axis]
        n = q.shape[1]
        if causal and n % (2 * n_dev) == 0:
            return _zigzag_sp(q, k, v, scale, mesh, axis, spec, n_dev,
                              dropout_p, dropout_key)
        # zigzag's balance argument IS causality; non-causal (or
        # non-chunkable N) falls back to the plain ring
        mode = 'ring'
    # ring mode prefers the Pallas-block ring (falls back to the jnp ring
    # internally when the kernel cannot run on this backend/shape; dropout
    # routes to the jnp ring)
    fn = ra.ring_flash_attention if mode == 'ring' else ra.ulysses_attention
    if dropout_p and dropout_key is not None:
        def body(qq, kk, vv, key):
            rank_key = jax.random.fold_in(key, lax.axis_index(axis))
            return fn(qq, kk, vv, axis_name=axis, causal=causal,
                      scale=scale, dropout_p=dropout_p,
                      dropout_key=rank_key)
        wrapped = shard_map(body, mesh=mesh,
                            in_specs=(spec, spec, spec, P()),
                            out_specs=spec, check_vma=False)
        return wrapped(q, k, v, dropout_key)
    wrapped = shard_map(
        functools.partial(fn, axis_name=axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return wrapped(q, k, v)


def _zigzag_sp(q, k, v, scale, mesh, axis, spec, n_dev, dropout_p,
               dropout_key):
    """Zigzag-balanced causal ring: permute the sequence so rank r holds
    chunks (r, 2P-1-r), run the balanced kernel, permute back. The gather
    costs one HBM copy each way; the kernel saves ~half the attention
    flops AND equalizes them across ranks (the plain causal ring's wall
    clock is gated by the all-visible last rank)."""
    import jax.numpy as jnp
    from ..ops import ring_attention as ra

    idx, inv = ra.zigzag_layout_indices(q.shape[1], n_dev)
    qz = jnp.take(q, idx, axis=1)
    kz = jnp.take(k, idx, axis=1)
    vz = jnp.take(v, idx, axis=1)
    if dropout_p and dropout_key is not None:
        def body(qq, kk, vv, key):
            return ra.zigzag_ring_attention(
                qq, kk, vv, axis_name=axis, scale=scale,
                dropout_p=dropout_p, dropout_key=key)
        out = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, P()),
                        out_specs=spec, check_vma=False)(qz, kz, vz,
                                                         dropout_key)
    else:
        out = shard_map(
            functools.partial(ra.zigzag_ring_attention, axis_name=axis,
                              scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(qz, kz, vz)
    return jnp.take(out, inv, axis=1)
