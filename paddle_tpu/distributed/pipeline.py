"""Pipeline-parallel engine (reference: framework/section_worker.cc:104
micro-batch 1F1B loop + fleet/meta_parallel/pipeline_parallel.py:109
train_batch, pp_layers.py:76 stage partition).

TPU-native (SURVEY.md §7.4 hard-part #2): no executor schedules stages —
the schedule IS a jax program. A GPipe loop runs inside `jax.shard_map`
manual over the 'pp' mesh axis only (`axis_names={'pp'}`): each tick every
stage applies its segment and the activations rotate forward with
ppermute over ICI; dp/mp/sharding stay auto-sharded by XLA inside the
region, so pipeline composes with the other axes without manual
collectives. Two stage forms:

  pipeline_blocks     — homogeneous block lists (transformer): per-stage
                        params are STACKED [pp, layers/pp, ...] and
                        pp-sharded, so each device stores and computes
                        only its stage's layers (the memory win).
  pipeline_stage_fns  — heterogeneous declarative PipelineLayer segments:
                        a lax.switch picks this rank's segment; params are
                        closure-captured (schedule-real, memory-neutral),
                        which also makes SharedLayerDesc tied weights
                        work for free (same traced array in two stages).

Like sp (distributed/sp.py), the pp state is scoped to a TrainStep so
eval/generation calls between steps run the plain sequential forward.
Backward is jax AD through scan+ppermute (GPipe: all microbatches forward,
then reverse); combine with recompute for the activation-memory win.
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
from .auto_parallel import tuner as ap_tuner

__all__ = ['PipelineEngine', 'make_pp_state', 'pp_scope', 'pipeline_state',
           'pipeline_blocks', 'pipeline_stage_fns']

_STATE = {'active': None}


def _cpu_mesh(mesh):
    """True when the pp mesh runs on the XLA CPU backend, whose
    AllReducePromotion pass aborts on bf16 all-reduces once the SPMD
    partitioner has inserted a copy into the reduction region. All f32
    boundary casts in this module are gated on this — TPU keeps bf16
    collectives (half the ICI bytes)."""
    try:
        return mesh.devices.flat[0].platform == 'cpu'
    except Exception:
        return False


def make_pp_state(mesh, n_stages, n_micro=None, axis='pp', remat=False,
                  schedule='gpipe'):
    """Build (without activating) a pipeline routing state.

    n_micro: microbatches per step (reference PipelineConfig
    accumulate_steps); defaults to n_stages for GPipe (minimum that fills
    the pipe) and 2*n_stages for 1F1B (the regime where its O(pp) stash
    beats GPipe's O(n_micro)).
    remat: checkpoint each layer application inside the stage scan.
    schedule: 'gpipe' (this module) or '1f1b' (pipeline_1f1b.py —
    interleaved fwd/bwd, loss inside the last stage).
    """
    schedule = schedule.lower().replace('-', '')
    if schedule not in ('gpipe', '1f1b', 'fthenb'):
        raise ValueError('unknown pipeline schedule %r' % schedule)
    if schedule == 'fthenb':
        schedule = 'gpipe'
    default_micro = 2 * n_stages if schedule == '1f1b' else n_stages
    return {'mesh': mesh, 'axis': axis, 'n_stages': int(n_stages),
            'n_micro': int(n_micro or default_micro), 'remat': bool(remat),
            'schedule': schedule}


def pipeline_state():
    return _STATE['active']


class pp_scope:
    """Activate a pp state only around a step's trace/execution."""

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


def _gpipe_loop(stage_apply, micro, n_stages, n_micro, axis, dtype_like,
                wire_dtype, base_key):
    """The schedule: n_micro + n_stages - 1 ticks; stage 0 ingests
    microbatch t, every stage applies its segment, ppermute rotates
    activations forward; the last stage's outputs are psum-broadcast so
    the (replicated-over-pp) loss/head code downstream sees all of them.

    stage_apply(x_array, stage_id, tick_key) -> y_array, like-shaped
    with x. micro: [n_micro, mb, ...]; returns [n_micro, mb, ...].
    base_key: per-step PRNG key (callers always thread one); each tick
    derives fold_in(base_key, microbatch_index) so dropout masks differ
    per microbatch (and per step, the base key being per-step).
    """
    stage = lax.axis_index(axis)
    n_ticks = n_micro + n_stages - 1
    # wire_dtype: what collectives (ppermute/psum) carry. f32 on the CPU
    # backend — bf16 collectives there abort in AllReducePromotion once
    # the SPMD partitioner inserts a copy into the reduction region (see
    # _cpu_mesh); on TPU it equals the compute dtype (half the ICI bytes)
    wire = wire_dtype or dtype_like

    def tick(buf, t):
        idx = jnp.clip(t, 0, n_micro - 1)
        inject = jnp.where(stage == 0, micro[idx], buf).astype(dtype_like)
        # key by the microbatch THIS stage is processing (t - stage),
        # so a microbatch keeps one mask set as it moves down the pipe
        i_mb = jnp.clip(t - stage, 0, n_micro - 1)
        tick_key = jax.random.fold_in(base_key, i_mb)
        y = stage_apply(inject, stage, tick_key)
        nxt = lax.ppermute(y.astype(wire), axis,
                           [(i, (i + 1) % n_stages)
                            for i in range(n_stages)])
        return nxt, y

    _, outs = lax.scan(tick, jnp.zeros(micro.shape[1:], wire),
                       jnp.arange(n_ticks))
    valid = outs[n_stages - 1:]  # meaningful on the last stage only
    out = lax.psum(
        jnp.where(stage == n_stages - 1, valid.astype(wire),
                  jnp.zeros(valid.shape, wire)),
        axis)
    return out.astype(valid.dtype)


def _split_micro(x, n_micro):
    b = x.shape[0]
    if b % n_micro:
        raise ValueError('batch %d not divisible by n_micro=%d'
                         % (b, n_micro))
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def pipeline_blocks(blocks, x, state):
    """Run a homogeneous Layer list through the GPipe schedule with
    per-stage params stacked [pp, layers/pp, ...] and pp-sharded.

    blocks: structurally identical Layers (e.g. GPTBlock list); their
    activations must be like-shaped (transformer residual stream).
    x: Tensor [B, ...]. Returns Tensor [B, ...].

    Dropout: when the blocks contain active dropout, a per-step base key
    is threaded through the schedule and folded with (microbatch, global
    layer) indices, so masks differ per microbatch/layer/step (the
    reference's parallel_layers/random.py capability). Masks do NOT
    bit-match the sequential forward's stream — parity tests run in eval
    mode or dropout=0.
    """
    st = state
    n_stages, n_micro, axis = st['n_stages'], st['n_micro'], st['axis']
    blocks = list(blocks)
    n_layers = len(blocks)
    # uneven layer counts: pad the stack to pp*ceil(n/pp) with zero
    # "ghost" layers masked to identity in the stage scan (their compute
    # is wasted but their output — and gradient contribution — is
    # discarded by the select; the reference's seg_method splits layer
    # counts unevenly the same way, pp_layers.py:76)
    per = -(-n_layers // n_stages)
    n_pad = n_stages * per - n_layers
    template = blocks[0]
    if any(b is not None for _, b in template.named_buffers()):
        raise NotImplementedError(
            'pipeline_blocks requires buffer-free blocks (running-stat '
            'layers inside a pipelined stage are not supported)')
    pnames = [n for n, _ in template.named_parameters()]

    # stack per-layer params: {name: [pp, per, ...]}. The storage params
    # stay ordinary named entries (optimizer/shardings unchanged); the
    # stack happens in-graph, and its transpose un-stacks the grads
    # (ghost entries are constants — no grad flows to them).
    stacked = {}
    for n in pnames:
        arrs = [dict(b.named_parameters())[n]._data for b in blocks]
        a = jnp.stack(arrs)
        if n_pad:
            a = jnp.concatenate(
                [a, jnp.zeros((n_pad,) + a.shape[1:], a.dtype)])
        stacked[n] = a.reshape((n_stages, per) + a.shape[1:])

    remat = st['remat']

    def apply_layer(xb, layer_params, layer_key):
        with rng_mod.key_scope(layer_key):
            out, _ = func_mod.functional_call(
                template, layer_params, {},
                args=(Tensor(xb, stop_gradient=False),))
        return out

    def stage_apply(xb, stage_id, tick_key):
        # params for THIS rank's stage arrive with the pp dim localized
        def body(c, xs):
            lp, lk, j = xs
            f = jax.checkpoint(apply_layer) if remat else apply_layer
            out = f(c, lp, lk)
            if n_pad:
                out = jnp.where(stage_id * per + j < n_layers, out, c)
            return out, None
        # decorrelate by GLOBAL layer index: stage*per + local j
        lkeys = jax.vmap(lambda j: jax.random.fold_in(
            tick_key, stage_id * per + j))(jnp.arange(per))
        y, _ = lax.scan(body, xb,
                        (stage_apply.params, lkeys, jnp.arange(per)))
        return y

    x_arr = x._data if isinstance(x, Tensor) else x
    dtype_like = x_arr.dtype
    wire = jnp.float32 if _cpu_mesh(st['mesh']) else dtype_like
    # the key ALWAYS threads (a heuristic "does this model draw RNG?"
    # check would silently bake one mask per trace for any dropout form
    # it missed — e.g. a direct F.dropout call); unused keys cost a few
    # fold_ins per tick and are DCE'd by XLA
    base_key = rng_mod.next_key()

    def pp_body(stacked_local, micro, key_in):
        local = {n: a[0] for n, a in stacked_local.items()}  # strip pp dim
        stage_apply.params = local
        return _gpipe_loop(stage_apply, micro, n_stages, n_micro, axis,
                           dtype_like, wire, base_key=key_in)

    fn = shard_map(pp_body, mesh=st['mesh'],
                   in_specs=({n: P(axis) for n in stacked}, P(), P()),
                   out_specs=P(), axis_names={axis}, check_vma=False)
    # the replicated micro operand crosses the boundary in the wire dtype:
    # its transpose is a psum over pp (f32 on CPU, see _cpu_mesh; the
    # stacked params are pp-sharded so their transpose needs no psum)
    micro = _split_micro(x_arr, n_micro).astype(wire)
    # pin the Auto-axis shardings at the region boundary (auto_parallel
    # planner): the micro reshape and the stacked stage params are where
    # GSPMD otherwise guesses and falls back to involuntary replication
    # inside the while body (MULTICHIP r05 cfg5 warnings). Specs come
    # from a tuned plan artifact when PADDLE_TPU_PLAN_DIR has one for
    # this mesh, else from the analytic planner.
    plan = ap_tuner.resolve_plan_for_state(st)
    if plan is not None:
        stacked = plan.constrain_stacked(stacked)
        micro = plan.constrain_micro(micro)
    out = fn(stacked, micro, base_key)
    if plan is not None:
        out = plan.constrain_micro(out)
    out = out.reshape(x_arr.shape[:1] + out.shape[2:]).astype(dtype_like)
    if plan is not None:
        out = plan.constrain_batch(out)
    return Tensor(out, stop_gradient=False)


def pipeline_stage_fns(stage_fns, x, state, params=None, rebind=None):
    """GPipe over heterogeneous per-stage callables (PipelineLayer
    segments): lax.switch picks this rank's segment each tick. Segment
    boundaries must be like-shaped (switch/ppermute need one aval).

    params/rebind thread the stage fns' parameter arrays through the
    shard_map boundary as explicit replicated inputs instead of closure
    captures: `params` is a {name: array} dict and `rebind(params)` swaps
    the (inner-tracer) arrays into the live layers, returning a restore
    thunk. Closure-captured outer tracers would otherwise carry
    Auto-mesh avals into the Manual pp region, which the scan transpose
    rejects (zeros_like on a mismatched context mesh). Every rank holds
    all params (replicated) — the schedule and comm pattern are real,
    the per-stage memory win needs the homogeneous pipeline_blocks
    form. Tied weights (SharedLayerDesc) are one dict entry used by two
    stages: their cotangents sum, which is exactly the tied-grad rule."""
    st = state
    n_stages, n_micro, axis = st['n_stages'], st['n_micro'], st['axis']
    if len(stage_fns) != n_stages:
        raise ValueError('%d stage fns != pp degree %d'
                         % (len(stage_fns), n_stages))

    def wrap(fn):
        def g(arr):
            out = fn(Tensor(arr, stop_gradient=False))
            return out._data if isinstance(out, Tensor) else out
        return g

    branches = [wrap(f) for f in stage_fns]

    def stage_apply(xb, stage_id, tick_key):
        if tick_key is None:
            return lax.switch(stage_id, branches, xb)
        # every branch traces under the stage-folded key scope; only this
        # rank's branch runs, and each branch's trace advances the scoped
        # stream at a distinct position, decorrelating stages
        with rng_mod.key_scope(jax.random.fold_in(tick_key, stage_id)):
            return lax.switch(stage_id, branches, xb)

    x_arr = x._data if isinstance(x, Tensor) else x
    dtype_like = x_arr.dtype
    cpu = _cpu_mesh(st['mesh'])
    wire = jnp.float32 if cpu else dtype_like
    params = params or {}
    # on CPU the threaded params cross the boundary in f32 too (their
    # transpose is also a psum over pp) and are cast back to their real
    # dtype inside the region before rebinding
    pdtypes = {n: a.dtype for n, a in params.items()}
    boundary = ({n: a.astype(jnp.float32) for n, a in params.items()}
                if cpu else params)
    base_key = rng_mod.next_key()  # always threads; see pipeline_blocks

    def pp_body(params_in, micro, key_in):
        if cpu:
            params_in = {n: a.astype(pdtypes[n])
                         for n, a in params_in.items()}
        restore = rebind(params_in) if rebind is not None else None
        try:
            return _gpipe_loop(stage_apply, micro, n_stages, n_micro,
                               axis, dtype_like, wire, base_key=key_in)
        finally:
            if restore is not None:
                restore()

    fn = shard_map(pp_body, mesh=st['mesh'],
                   in_specs=({n: P() for n in params}, P(), P()),
                   out_specs=P(), axis_names={axis}, check_vma=False)
    micro = _split_micro(x_arr, n_micro).astype(wire)
    plan = ap_tuner.resolve_plan_for_state(st)
    if plan is not None:  # see pipeline_blocks: pin the micro boundary
        micro = plan.constrain_micro(micro)
    out = fn(boundary, micro, base_key)
    if plan is not None:
        out = plan.constrain_micro(out)
    out = out.reshape(x_arr.shape[:1] + out.shape[2:]).astype(dtype_like)
    if plan is not None:
        out = plan.constrain_batch(out)
    return Tensor(out, stop_gradient=False)


class PipelineEngine:
    """Executes PipelineLayer models: microbatch split + GPipe schedule +
    grads + optimizer, jitted once (reference SectionWorker TrainFiles +
    PipelineParallel.train_batch)."""

    def __init__(self, pipeline_layer, optimizer, hcg, n_micro=None):
        self.layer = pipeline_layer
        self.optimizer = optimizer
        self.hcg = hcg
        pp = max(hcg.get_pipe_parallel_world_size(), 1)
        self.n_micro = n_micro or max(pp, 1)
        self._step = None
        self._pp_state = None
        if pp > 1:
            self._pp_state = make_pp_state(hcg.mesh, n_stages=pp,
                                           n_micro=self.n_micro)

    def _build(self):
        model = self.layer
        loss_fn = model._loss_fn

        def step_loss(out, labels):
            return loss_fn(out, labels)

        self._step = func_mod.TrainStep(model, step_loss, self.optimizer,
                                        mesh=self.hcg.mesh,
                                        pp_state=self._pp_state)

    def step(self, inputs, labels):
        if self._step is None:
            self._build()
        return self._step(inputs, labels)
