"""Meta-optimizer strategies as explicit-communication step transforms.

Reference parity (SURVEY.md §2.2): fleet/meta_optimizers/
{localsgd,dgc,fp16_allreduce,gradient_merge,lars,lamb}_optimizer.py rewrite
the static program to change WHAT is communicated and WHEN. The TPU-native
analog keeps the same property — communication visible in the program — by
running the data-parallel train step inside shard_map over the 'dp' mesh
axis, where psum/pmean calls are explicit:

  - plain DDP        : grads <- pmean(grads) every step
  - fp16_allreduce   : grads cast to bf16 for the pmean, back after
  - dgc              : top-k sparsified grads (momentum correction + error
                       feedback, Lin et al.) summed instead of dense grads
  - localsgd         : NO grad sync; per-device replicas diverge and params
                       are pmean'd every k_steps
  - gradient merge   : accumulate k micro-grads locally, sync+apply on the
                       k-th (composes with the modes above)

lars/lamb strategies swap the optimizer (optimizer/optimizers.py
LarsMomentum/Lamb); amp/recompute/sharding remain pjit-level concerns
(strategy.py / TrainStep).

The engine keeps params/opt-slots STACKED with a leading 'dp' axis sharded
over the mesh (each device owns its replica — required for localsgd
divergence); batch is sharded over the same axis.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...framework import functional as func_mod
from ...framework import random as rng_mod
from ...framework.core import Tensor

__all__ = ['ShardMapDPStep', 'dgc_compress', 'select_optimizer']


def dgc_compress(g, momentum_buf, error_buf, momentum, sparsity):
    """Deep Gradient Compression (local side): momentum correction +
    error-feedback accumulation + top-k selection.

    Returns (dense_send, new_momentum, new_error): dense_send is the
    sparsified tensor (zeros off the top-k support) to be summed across
    ranks; the residual stays in error_buf.

    Reference: operators/dgc_op.cc + sparse_all_reduce_op_handle.cc.
    """
    u = momentum * momentum_buf + g          # momentum correction
    v = error_buf + u                        # error feedback accumulation
    flat = v.reshape(-1)
    k = max(int(flat.size * (1.0 - sparsity)), 1)
    thresh = lax.top_k(jnp.abs(flat), k)[0][-1]
    mask = (jnp.abs(v) >= thresh).astype(v.dtype)
    send = v * mask
    # masked-out residual carries over; masked-in entries reset
    new_error = v * (1 - mask)
    new_momentum = u * (1 - mask)
    return send, new_momentum, new_error


def select_optimizer(optimizer, strategy):
    """lars/lamb meta-optimizers: swap the inner optimizer when the
    strategy flag is set (reference lars_optimizer.py/lamb_optimizer.py
    _can_apply over Momentum/Adam)."""
    from ... import optimizer as opt_mod
    if strategy is None:
        return optimizer
    if getattr(strategy, 'lamb', False) and \
            not isinstance(optimizer, opt_mod.Lamb):
        cfg = strategy.lamb_configs
        return opt_mod.Lamb(
            learning_rate=optimizer._lr,
            lamb_weight_decay=cfg.get('lamb_weight_decay', 0.01),
            parameters=optimizer._parameter_list,
            grad_clip=optimizer._grad_clip)
    if getattr(strategy, 'lars', False) and \
            not isinstance(optimizer, opt_mod.LarsMomentum):
        cfg = strategy.lars_configs
        return opt_mod.LarsMomentum(
            learning_rate=optimizer._lr,
            momentum=getattr(optimizer, '_momentum', 0.9),
            lars_coeff=cfg.get('lars_coeff', 0.001),
            lars_weight_decay=cfg.get('lars_weight_decay', 0.0005),
            exclude_from_weight_decay=cfg.get('exclude_from_weight_decay',
                                              ()),
            epsilon=cfg.get('epsilon', 0.0),
            parameters=optimizer._parameter_list,
            grad_clip=optimizer._grad_clip)
    return optimizer


class ShardMapDPStep:
    """Explicit-collective data-parallel training step (see module doc).

    Restrictions (vs the pjit TrainStep): pure data parallelism (the mesh
    axis covers all devices used), uniform lr across params, no grad-clip
    hook inside the compressed paths (matches the reference, which clips
    before DGC only in the dense path), and buffers (e.g. BN stats) are
    frozen during stepping. In 'local' mode the live model object is only
    refreshed at param-sync steps — between syncs replicas legitimately
    diverge and have no single host-side value.
    """

    # DGC warm-up ladder (Lin et al. §3.3): dense before rampup_begin_step,
    # then increasingly sparse over rampup_step applied steps
    DGC_RAMP = (0.75, 0.9375, 0.984, 0.996)

    def __init__(self, model, loss_fn, optimizer, mesh=None, axis='dp',
                 mode='dense', k_steps=1, gm_k_steps=1, momentum=0.9,
                 sparsity=0.999, dtype_comm=jnp.bfloat16, adaptive=False,
                 rampup_begin_step=0, rampup_step=1):
        assert mode in ('dense', 'fp16', 'dgc', 'local')
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.axis = axis
        self.mode = mode
        self.k_steps = max(int(k_steps), 1)      # localsgd param-sync period
        self.gm_k = max(int(gm_k_steps), 1)      # gradient-merge period
        self.momentum = momentum
        self.sparsity = sparsity
        self.dtype_comm = dtype_comm
        # adaptive localsgd (reference adaptive_localsgd meta-optimizer):
        # host-side heuristic — widen the sync period while the synced loss
        # keeps improving, shrink it when it regresses
        self.adaptive = adaptive
        self._adapt_last_loss = None
        self.rampup_begin_step = int(rampup_begin_step)
        self.rampup_step = max(int(rampup_step), 1)
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (axis,))
        self.mesh = mesh
        self.n_dev = mesh.shape[axis]
        self._trainable = {name: not p.stop_gradient
                           for name, p in model.named_parameters()}
        # state-dict key -> Parameter.name: _apply hints (e.g. LARS
        # exclude_from_weight_decay) match on the Parameter's .name, same
        # as TrainStep's engine
        self._pname = {name: p.name
                       for name, p in model.named_parameters()}
        self._micro = 0          # host-side micro-batch counter
        self._step = 0           # host-side applied-step counter
        self._state = None       # stacked device state
        self._compiled = {}

    # -- state --------------------------------------------------------------
    def _stack(self, tree):
        """Replicate a pytree with a leading dp axis, sharded over it."""
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(
                jnp.broadcast_to(a[None], (self.n_dev,) + a.shape), sh),
            tree)

    def _init_state(self):
        params = func_mod.extract_params(self.model)
        pmap = dict(self.model.named_parameters())
        slots = {name: dict(self.optimizer._get_slots(pmap[name]))
                 for name in params if self._trainable[name]}
        state = {'params': self._stack(params),
                 'slots': self._stack(slots)}
        train = {n: params[n] for n in params if self._trainable[n]}
        if self.mode == 'dgc':
            zeros = {n: jnp.zeros_like(a) for n, a in train.items()}
            state['dgc_u'] = self._stack(zeros)
            state['dgc_v'] = self._stack(zeros)
        if self.gm_k > 1:
            zeros = {n: jnp.zeros_like(a) for n, a in train.items()}
            state['acc'] = self._stack(zeros)
        return state

    def _write_back(self):
        """Sync rank-0 replica back into the live model (replicas are
        identical right after a sync step)."""
        params0 = jax.tree_util.tree_map(lambda a: a[0],
                                         self._state['params'])
        func_mod.write_back_params(self.model, params0)
        pmap = dict(self.model.named_parameters())
        slots0 = jax.tree_util.tree_map(lambda a: a[0],
                                        self._state['slots'])
        for name, s in slots0.items():
            self.optimizer._slots[id(pmap[name])] = dict(s)

    # -- step build ---------------------------------------------------------
    def _build(self, sync_params, apply_opt, sparsity=None):
        model, opt, loss_fn = self.model, self.optimizer, self.loss_fn
        trainable = self._trainable
        axis = self.axis
        mode = self.mode
        n_dev = self.n_dev
        buffers = func_mod.extract_buffers(model)

        def per_device(state, batch, lr, t, key):
            # state leaves arrive as [1, ...] shards: this device's replica
            state = jax.tree_util.tree_map(lambda a: a[0], state)
            inputs, labels = batch
            params = state['params']

            def compute_loss(train_params):
                all_params = dict(params)
                all_params.update(train_params)
                gen = rng_mod.default_generator()
                saved = gen._key
                gen._key = key
                try:
                    loss_arr, _ = func_mod.functional_call(
                        model, all_params, buffers, args=inputs,
                        training=True,
                        post_fn=func_mod.make_loss_post(loss_fn, labels))
                    return loss_arr
                finally:
                    gen._key = saved

            train_params = {k: v for k, v in params.items() if trainable[k]}
            loss, grads = jax.value_and_grad(compute_loss)(train_params)
            loss = lax.pmean(loss, axis)

            new_state = dict(state)
            # gradient merge: accumulate locally, only the k-th applies
            if self.gm_k > 1:
                grads = {n: state['acc'][n] + g for n, g in grads.items()}
                if not apply_opt:
                    new_state['acc'] = grads
                    return jax.tree_util.tree_map(lambda a: a[None],
                                                  new_state), loss
                grads = {n: g / self.gm_k for n, g in grads.items()}
                new_state['acc'] = {n: jnp.zeros_like(g)
                                    for n, g in grads.items()}

            # --- communication (explicit, visible in the jaxpr) ---------
            if mode == 'dense':
                grads = {n: lax.pmean(g, axis) for n, g in grads.items()}
            elif mode == 'fp16':
                grads = {n: lax.pmean(g.astype(self.dtype_comm),
                                      axis).astype(g.dtype)
                         for n, g in grads.items()}
            elif mode == 'dgc':
                if sparsity is None:
                    # warm-up phase: dense allreduce, buffers untouched
                    grads = {n: lax.pmean(g, axis)
                             for n, g in grads.items()}
                else:
                    new_u, new_v, synced = {}, {}, {}
                    for n, g in grads.items():
                        send, u, v = dgc_compress(
                            g, state['dgc_u'][n], state['dgc_v'][n],
                            self.momentum, sparsity)
                        synced[n] = lax.psum(send, axis) / n_dev
                        new_u[n] = u
                        new_v[n] = v
                    grads = synced
                    new_state['dgc_u'] = new_u
                    new_state['dgc_v'] = new_v
            # mode == 'local': no grad communication at all

            if apply_opt:
                new_params = dict(params)
                new_slots = dict(state['slots'])
                for n, g in grads.items():
                    opt._apply_param_name = self._pname[n]
                    p, s = opt._apply(params[n], g.astype(params[n].dtype),
                                      state['slots'][n], lr, t)
                    new_params[n] = p
                    new_slots[n] = s
                if sync_params:
                    # localsgd periodic model averaging
                    new_params = {n: lax.pmean(p, axis)
                                  for n, p in new_params.items()}
                    new_slots = jax.tree_util.tree_map(
                        lambda a: lax.pmean(a, axis), new_slots)
                new_state['params'] = new_params
                new_state['slots'] = new_slots

            return jax.tree_util.tree_map(lambda a: a[None], new_state), \
                loss

        state_spec = jax.tree_util.tree_map(lambda _: P(axis), self._state)
        batch_spec = P(axis)

        @jax.jit
        def step(state, batch, lr, t, key):
            return shard_map(
                per_device, mesh=self.mesh,
                in_specs=(state_spec, (batch_spec, batch_spec), P(), P(),
                          P()),
                out_specs=(state_spec, P()),
                check_vma=False)(state, batch, lr, t, key)

        return step

    def __call__(self, inputs, labels):
        if self._state is None:
            self._state = self._init_state()
        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        if not isinstance(labels, (list, tuple)):
            labels = (labels,)
        ins = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in inputs)
        labs = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                     for a in labels)

        self._micro += 1
        apply_opt = (self._micro % self.gm_k) == 0
        will_step = self._step + (1 if apply_opt else 0)
        sync_params = (self.mode == 'local' and apply_opt
                       and (will_step % self.k_steps) == 0)
        sparsity = self._current_sparsity() if self.mode == 'dgc' else None
        key = (bool(sync_params), bool(apply_opt), sparsity)
        if key not in self._compiled:
            self._compiled[key] = self._build(sync_params, apply_opt,
                                              sparsity)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        t = jnp.asarray(will_step if apply_opt else self._step, jnp.int32)
        rng_key = rng_mod.next_key()
        new_state, loss = self._compiled[key](
            self._state, (ins, labs), lr, t, rng_key)
        self._state = new_state
        if apply_opt:
            self._step = will_step
            self.optimizer._step_count = self._step
        if self.mode != 'local' or sync_params:
            self._write_back()
        if self.adaptive and sync_params:
            # adaptive localsgd: longer local phases while the synced loss
            # improves, shorter when it regresses (host-side heuristic
            # analog of the reference's AdaptiveLocalSGDOptimizer)
            cur = float(jax.device_get(loss))
            if self._adapt_last_loss is not None:
                if cur < self._adapt_last_loss:
                    self.k_steps = min(self.k_steps * 2, 16)
                else:
                    self.k_steps = max(self.k_steps // 2, 1)
            self._adapt_last_loss = cur
        return Tensor(loss)

    def _current_sparsity(self):
        """DGC warm-up: None (dense) before rampup_begin_step, then climb
        the ramp ladder over rampup_step applied steps, ending at the
        target sparsity. A handful of distinct values keeps recompiles
        bounded."""
        applied = self._step
        if applied < self.rampup_begin_step:
            return None
        if self.rampup_step <= 1:
            return self.sparsity
        ladder = [s for s in self.DGC_RAMP if s < self.sparsity] + \
            [self.sparsity]
        seg = self.rampup_step / float(len(ladder))
        idx = min(int((applied - self.rampup_begin_step) / seg),
                  len(ladder) - 1)
        return ladder[idx]
