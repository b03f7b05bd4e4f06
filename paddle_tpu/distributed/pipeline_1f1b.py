"""1F1B pipeline schedule (reference: framework/section_worker.cc:104-143
micro-batch loop RunForward/RunBackward/RunUpdate;
fleet/meta_parallel/pipeline_parallel.py:109 train_batch).

TPU-native 1F1B: the schedule is ONE lax.scan inside a shard_map over the
'pp' mesh axis, where every tick each stage runs (a) the forward of the
incoming microbatch and (b) the backward of the microbatch whose cotangent
just arrived — forwards and backwards interleave exactly as in the
reference's steady state, so the stash of saved stage inputs is a circular
buffer of size O(pp), NOT O(n_micro) (the GPipe scan in pipeline.py keeps
O(n_micro + pp)). Backward recomputes the stage from its stashed input
(recompute is inherent to the schedule, as in SectionWorker).

Because micro-level loss must live INSIDE the pipelined region (a backward
can only start once ITS loss exists — with loss outside, reverse-mode AD
degenerates to GPipe), the model provides a 3-way decomposition via
`pp_decompose()`: pre (embedding...), blocks (homogeneous stack), post
(head + loss). Tied weights (e.g. wte reused by the head) are ONE param
entry used by both pre and post; their per-rank grads sum in the vjp and
the psum over pp adds the rank-0 (embedding) and last-rank (head)
contributions — the SharedLayerDesc tied-grad rule for free.

The whole schedule runs in the PRIMAL computation and emits grads; a
custom_vjp hands those precomputed grads to the outer jax.grad, scaled by
the incoming loss cotangent. Timeline (rank r, microbatch i):
  forward  at tick r + i
  backward at tick 2(pp-1) - r + i
  => in-flight stash span = 2(pp-1-r), total ticks = n_micro + 2(pp-1).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..framework import functional as func_mod
from ..framework import random as rng_mod
from ..framework.core import Tensor
from .pipeline import _cpu_mesh
from .auto_parallel import tuner as ap_tuner

__all__ = ['one_f_one_b_loss', 'supports_1f1b']


def supports_1f1b(model):
    return hasattr(model, 'pp_decompose')


def one_f_one_b_loss(model, params, inputs, labels, state, loss_fn=None):
    """Scalar loss array; d(loss)/d(params) flows through a custom_vjp
    whose backward returns the grads the interleaved schedule computed.

    params: {name: array} covering every model parameter (the arrays may
    be outer-jit tracers). inputs/labels: int arrays [B, ...]. loss_fn
    (logits Tensor, labels Tensor) -> scalar Tensor is forwarded to
    pp_decompose so the user's objective is honored inside the last stage.
    """
    mesh = state['mesh']
    axis = state['axis']
    pp = state['n_stages']
    n_micro = state['n_micro']
    # dropout under 1F1B: a per-step base key crosses the shard_map
    # boundary and every mask key is a pure function of (base key,
    # microbatch index, stage, layer) — so masks differ per microbatch
    # and per step, and the backward's stage RECOMPUTE (jax.vjp of
    # tick_fn at the backward tick) rederives bit-identical masks from
    # the same indices. Reference capability: parallel_layers/random.py.
    # Always threaded: a "does the model draw RNG?" heuristic would
    # silently bake one mask per trace for any dropout form it missed.
    base_key = rng_mod.next_key()
    import inspect
    takes_loss = True
    try:
        sig = inspect.signature(model.pp_decompose)
        takes_loss = bool(sig.parameters)
    except (TypeError, ValueError):
        pass
    if takes_loss:
        pre_fn, blocks, post_fn = model.pp_decompose(loss_fn)
    else:
        if loss_fn is not None:
            import warnings
            warnings.warn(
                '%s.pp_decompose() takes no loss_fn — the 1F1B schedule '
                'uses the loss baked into its post stage, NOT the loss_fn '
                'passed to the train step' % type(model).__name__)
        pre_fn, blocks, post_fn = model.pp_decompose()
    blocks = list(blocks)
    n_layers = len(blocks)
    # uneven layer counts pad to pp*ceil(n/pp) with zero ghost layers
    # masked to identity (see pipeline.pipeline_blocks; grads to ghosts
    # are discarded — unstack_grads reads only the real entries)
    per = -(-n_layers // pp)
    n_pad = pp * per - n_layers
    template = blocks[0]
    block_pnames = {}  # stacked name -> [per-layer full names]
    tmpl_names = [n for n, _ in template.named_parameters()]
    blk_maps = [dict(b.named_parameters()) for b in blocks]
    full_names = {n: [None] * len(blocks) for n in tmpl_names}
    pmap_all = dict(model.named_parameters())
    rev = {id(p): n for n, p in pmap_all.items()}
    for li, bm in enumerate(blk_maps):
        for n in tmpl_names:
            full_names[n][li] = rev[id(bm[n])]
    block_param_names = {fn2 for ns in full_names.values() for fn2 in ns}
    outer_names = [n for n in params if n not in block_param_names]

    cpu = _cpu_mesh(mesh)

    b = inputs.shape[0]
    if b % n_micro:
        raise ValueError('batch %d %% n_micro %d != 0' % (b, n_micro))
    mb = b // n_micro
    micro_ids = inputs.reshape((n_micro, mb) + inputs.shape[1:])
    micro_lbl = labels.reshape((n_micro, mb) + labels.shape[1:])
    # auto_parallel planner: pin the Auto-axis shardings at the region
    # boundaries (microbatch stream + stacked stage params) so GSPMD has
    # nothing to guess inside the while body — see planner.py for the
    # root cause of the MULTICHIP r05 cfg5 involuntary-reshard warnings.
    # Resolved through the tuner so a PADDLE_TPU_PLAN_DIR artifact
    # (tuned, content-addressed) overrides the analytic specs.
    plan = ap_tuner.resolve_plan(mesh, axis)
    if plan is not None:
        micro_ids = plan.constrain_micro(micro_ids)
        micro_lbl = plan.constrain_micro(micro_lbl)

    # probe shapes eagerly (abstract eval only) to size the rotating bufs;
    # the key scope keeps any dropout draw inside the probe from leaking
    # an abstract tracer into the live generator
    def _probe(ids):
        with rng_mod.key_scope(jax.random.PRNGKey(0)):
            return _call_pre(model, pre_fn, params, ids)
    x_shape_dtype = jax.eval_shape(_probe, micro_ids[0])

    def stacked_of(pdict):
        out = {}
        for n in tmpl_names:
            arrs = [pdict[fn2] for fn2 in full_names[n]]
            a = jnp.stack(arrs)
            if n_pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((n_pad,) + a.shape[1:], a.dtype)])
            out[n] = a.reshape((pp, per) + a.shape[1:])
        return out

    def unstack_grads(stacked_grads):
        out = {}
        for n, a in stacked_grads.items():
            flat = a.reshape((pp * per,) + a.shape[2:])
            for li, fn2 in enumerate(full_names[n]):
                out[fn2] = flat[li]
        return out

    # the base key rides as an EXPLICIT custom_vjp argument (a closed-over
    # tracer inside a custom_vjp body raises UnexpectedTracerError); its
    # cotangent is float0 (integer-typed input)
    @jax.custom_vjp
    def pp_loss(p, key_in):
        loss, _ = _run(p, key_in)
        return loss

    def _fwd(p, key_in):
        return _run(p, key_in)

    def _bwd(grads, g):
        key_ct = np.zeros((2,), jax.dtypes.float0)
        return (jax.tree_util.tree_map(lambda a: a * g, grads), key_ct)

    pp_loss.defvjp(_fwd, lambda res, g: _bwd(res, g))

    def _run(p, key_in):
        stacked = stacked_of(p)
        if plan is not None:
            stacked = plan.constrain_stacked(stacked)
        outer = {n: p[n] for n in outer_names}
        pdtypes = {n: a.dtype for n, a in outer.items()}
        if cpu:
            # f32 across the boundary: replicated operands' grad psums over
            # pp abort XLA:CPU's AllReducePromotion in bf16 (see pipeline.py)
            outer_in = {n: a.astype(jnp.float32) for n, a in outer.items()}
        else:
            outer_in = outer

        wire = jnp.float32 if cpu else jnp.dtype(x_shape_dtype.dtype)

        def body(stacked_local, outer_p, ids_all, lbl_all, key_b):
            if cpu:
                outer_p = {n: a.astype(pdtypes[n])
                           for n, a in outer_p.items()}
            local = {n: a[0] for n, a in stacked_local.items()}
            r = lax.axis_index(axis)
            last = pp - 1
            T = n_micro + 2 * (pp - 1)
            S = 2 * pp
            x_shape = (mb,) + tuple(x_shape_dtype.shape[1:])
            x_dtype = jnp.dtype(x_shape_dtype.dtype)

            def tick_fn(x_in, outer_params, local_blocks, i_mb):
                """One stage application: (y, mb_loss). pre and post run
                under lax.cond on the pp rank: only stage 0 pays the
                embedding lookup and only the last stage pays the
                vocab-size head matmul + loss (branching on the rank is
                SPMD-safe here — all devices sharing a pp coordinate take
                the same branch, so any auto-axis collectives inside a
                branch stay consistent)."""
                ids_mb = ids_all[i_mb]
                lbl_mb = lbl_all[i_mb]
                key_mb = jax.random.fold_in(key_b, i_mb)
                with rng_mod.key_scope(jax.random.fold_in(key_mb, 0)):
                    x0 = lax.cond(
                        r == 0,
                        lambda xi: _call_pre(model, pre_fn, outer_params,
                                             ids_mb).astype(x_dtype),
                        lambda xi: xi,
                        x_in.astype(x_dtype))

                def layer(c, xs):
                    lp, lk, j = xs
                    with rng_mod.key_scope(lk):
                        out, _ = func_mod.functional_call(
                            template, lp, {},
                            args=(Tensor(c, stop_gradient=False),))
                    if n_pad:
                        # ghost (padding) layers act as identity
                        out = jnp.where(r * per + j < n_layers, out, c)
                    return out, None
                # decorrelate by GLOBAL layer index r*per + j
                lkeys = jax.vmap(lambda j: jax.random.fold_in(
                    key_mb, 1 + r * per + j))(jnp.arange(per))
                y, _ = lax.scan(layer, x0,
                                (local_blocks, lkeys, jnp.arange(per)))
                with rng_mod.key_scope(jax.random.fold_in(key_mb,
                                                          99991)):
                    mb_loss = lax.cond(
                        r == last,
                        lambda yy: _call_post(model, post_fn, outer_params,
                                              yy,
                                              lbl_mb).astype(jnp.float32),
                        lambda yy: jnp.zeros((), jnp.float32),
                        y)
                return y, mb_loss

            zero_outer = {n: jnp.zeros(a.shape, jnp.float32)
                          for n, a in outer_p.items()}
            zero_blocks = {n: jnp.zeros(a.shape, jnp.float32)
                           for n, a in local.items()}
            carry0 = dict(
                fwd_buf=jnp.zeros(x_shape, wire),
                bwd_buf=jnp.zeros(x_shape, jnp.float32),
                stash=jnp.zeros((S,) + x_shape, wire),
                g_outer=zero_outer,
                g_blocks=zero_blocks,
                loss=jnp.zeros((), jnp.float32),
            )
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
            bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]

            def tick(carry, t):
                i_f = t - r
                f_valid = jnp.logical_and(i_f >= 0, i_f < n_micro)
                i_f_c = jnp.clip(i_f, 0, n_micro - 1)
                x_in = carry['fwd_buf'].astype(x_dtype)

                y, mb_loss = tick_fn(x_in, outer_p, local, i_f_c)
                loss = carry['loss'] + jnp.where(f_valid, mb_loss, 0.0)
                stash = carry['stash'].at[i_f_c % S].set(
                    jnp.where(f_valid, carry['fwd_buf'],
                              carry['stash'][i_f_c % S]))

                i_b = t - (2 * (pp - 1) - r)
                b_valid = jnp.logical_and(i_b >= 0, i_b < n_micro)
                i_b_c = jnp.clip(i_b, 0, n_micro - 1)
                x_st = stash[i_b_c % S].astype(x_dtype)

                _, vjp_fn = jax.vjp(
                    lambda x, op, lb: tick_fn(x, op, lb, i_b_c),
                    x_st, outer_p, local)
                cot_y = jnp.where(r == last,
                                  jnp.zeros(x_shape, x_dtype),
                                  carry['bwd_buf'].astype(x_dtype))
                cot_loss = jnp.where(r == last, 1.0 / n_micro, 0.0)
                cot_loss = jnp.where(b_valid, cot_loss, 0.0)
                cot_y = jnp.where(b_valid, cot_y,
                                  jnp.zeros(x_shape, x_dtype))
                dx, d_outer, d_blocks = vjp_fn(
                    (cot_y, cot_loss.astype(jnp.float32)))

                bmask = b_valid.astype(jnp.float32)
                g_outer = jax.tree_util.tree_map(
                    lambda acc, d2: acc + d2.astype(jnp.float32) * bmask,
                    carry['g_outer'], d_outer)
                g_blocks = jax.tree_util.tree_map(
                    lambda acc, d2: acc + d2.astype(jnp.float32) * bmask,
                    carry['g_blocks'], d_blocks)

                fwd_buf = lax.ppermute(y.astype(wire), axis, fwd_perm)
                bwd_buf = lax.ppermute(
                    (dx.astype(jnp.float32) * bmask), axis, bwd_perm)
                return dict(fwd_buf=fwd_buf, bwd_buf=bwd_buf, stash=stash,
                            g_outer=g_outer, g_blocks=g_blocks,
                            loss=loss), None

            carry, _ = lax.scan(tick, carry0, jnp.arange(T))
            loss = lax.psum(carry['loss'], axis) / n_micro
            g_outer = {n: lax.psum(a, axis)
                       for n, a in carry['g_outer'].items()}
            g_blocks = {n: a[None] for n, a in carry['g_blocks'].items()}
            return loss, g_outer, g_blocks

        in_specs = ({n: P(axis) for n in stacked},
                    {n: P() for n in outer_in}, P(), P(), P())
        out_specs = (P(), {n: P() for n in outer_in},
                     {n: P(axis) for n in stacked})
        fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names={axis},
                       check_vma=False)
        loss, g_outer, g_blocks = fn(stacked, outer_in, micro_ids,
                                     micro_lbl, key_in)
        if plan is not None:
            # grads leave pp-sharded like the stacked params entered;
            # the optimizer's ZeRO slice of a replicated-over-auto grad
            # is a plain dynamic-slice (efficient), unlike a guessed
            # tiled->tiled transition
            g_blocks = plan.constrain_stacked(g_blocks)
        grads = {}
        for n, a in g_outer.items():
            grads[n] = a.astype(params[n].dtype)
        for n, a in unstack_grads(g_blocks).items():
            grads[n] = a.astype(params[n].dtype)
        # params not touched by the pipeline (none normally) get zeros
        for n in params:
            if n not in grads:
                grads[n] = jnp.zeros_like(params[n])
        return loss, grads

    return pp_loss(params, base_key)


def _call_pre(model, pre_fn, pdict, ids_arr):
    """Run pre_fn with pdict bound into the live layers; returns array."""
    saved = _bind(model, pdict)
    try:
        out = pre_fn(Tensor(ids_arr))
        return out._data if isinstance(out, Tensor) else out
    finally:
        _restore(saved)


def _call_post(model, post_fn, pdict, x_arr, lbl_arr):
    saved = _bind(model, pdict)
    try:
        out = post_fn(Tensor(x_arr, stop_gradient=False), Tensor(lbl_arr))
        return out._data if isinstance(out, Tensor) else out
    finally:
        _restore(saved)


def _bind(model, pdict):
    pmap = dict(model.named_parameters())
    saved = []
    for n, arr in pdict.items():
        t = pmap.get(n)
        if t is None:
            continue
        saved.append((t, t._data))
        t._data = arr
    return saved


def _restore(saved):
    for t, arr in saved:
        t._data = arr
