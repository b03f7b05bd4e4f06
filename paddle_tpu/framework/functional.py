"""Functionalization bridge: mutable Layers <-> pure jax functions.

This is the linchpin of the TPU design (SURVEY.md §7.4 hard-part #1): the
paddle-style API is stateful (Layers own Parameters, optimizers update
in-place, BN mutates running stats), but XLA wants pure functions over
pytrees. `functional_call` temporarily binds tracer arrays into the live
Parameter/buffer objects, runs the layer's ordinary forward, then harvests
mutated buffer values as explicit outputs — so ONE code path serves eager
and compiled execution (the reference needed two: dygraph + ProgramDesc).

`TrainStep` composes model + loss + optimizer into a single jitted
(params, opt_state, batch, rng) -> (params, opt_state, loss) function with
donated buffers — the XLA-native replacement for the reference's
executor-driven training loop, and the unit over which distributed
strategies apply shardings (distributed/strategy.py).
"""
import jax
import jax.numpy as jnp

from .core import Tensor
from . import random as rng_mod
from ..monitor import tracing as _tracing

__all__ = ['extract_params', 'extract_buffers', 'functional_call',
           'make_loss_post', 'TrainStep']


def _cast_like(tree, ref):
    """Cast each array in `tree` back to the dtype of the same-named entry
    in `ref`. Keeps stored state dtypes stable across a jitted update (the
    traced f32 lr intentionally promotes the arithmetic, but params,
    buffers, and optimizer slots must round-trip their dtypes or the
    lax.scan carry in multi_step mistypes)."""
    return {k: v.astype(ref[k].dtype)
            if hasattr(v, 'astype') and k in ref else v
            for k, v in tree.items()}


def extract_params(layer, trainable_only=False):
    """OrderedDict name -> jax array of the layer's parameters."""
    out = {}
    for name, p in layer.named_parameters():
        if trainable_only and p.stop_gradient:
            continue
        out[name] = p._data
    return out


def extract_buffers(layer):
    out = {}
    for name, b in layer.named_buffers():
        if b is not None:
            out[name] = b._data
    return out


def _bind(layer, params, buffers):
    """Swap arrays into live tensors; returns restore list."""
    saved = []
    pmap = dict(layer.named_parameters())
    bmap = dict(layer.named_buffers())
    for name, arr in params.items():
        t = pmap[name]
        saved.append((t, t._data))
        t._data = arr
    for name, arr in (buffers or {}).items():
        t = bmap.get(name)
        if t is None:
            continue
        saved.append((t, t._data))
        t._data = arr
    return saved, bmap


def functional_call(layer, params, buffers, args=(), kwargs=None,
                    training=None, post_fn=None):
    """Run layer.forward with `params`/`buffers` arrays bound in.

    Returns (outputs_as_arrays, new_buffers_dict). Safe under jit tracing:
    any buffer mutated by forward (e.g. BN running stats) comes back as a
    traced output instead of leaking a tracer into the live object.

    post_fn, when given, receives the forward's raw (Tensor) output and
    runs INSIDE the parameter binding; its result becomes the returned
    output. This is how a loss that references model parameters directly
    (e.g. a fused tied-embedding head, an L2 term over weights) sees the
    traced arrays rather than the live ones — referencing a live
    Parameter from an unbound loss would silently drop its gradient
    contribution.
    """
    kwargs = kwargs or {}
    prev_mode = layer.training
    if training is not None:
        layer.training = training
        for l in layer.sublayers(include_self=True):
            l.training = training
    saved, bmap = _bind(layer, params, buffers)
    try:
        targs = [Tensor(a, stop_gradient=False) if isinstance(
            a, (jnp.ndarray, jax.Array)) or hasattr(a, 'aval') else a
            for a in args]
        out = layer(*targs, **kwargs)
        if post_fn is not None:
            out = post_fn(out)
        new_buffers = {name: t._data for name, t in bmap.items()
                       if t is not None}

        def unwrap(o):
            if isinstance(o, Tensor):
                return o._data
            if isinstance(o, (list, tuple)):
                return type(o)(unwrap(x) for x in o)
            if isinstance(o, dict):
                return {k: unwrap(v) for k, v in o.items()}
            return o
        return unwrap(out), new_buffers
    finally:
        for t, arr in saved:
            t._data = arr
        if training is not None:
            layer.training = prev_mode
            for l in layer.sublayers(include_self=True):
                l.training = prev_mode


def make_loss_post(loss_fn, labels):
    """functional_call post_fn computing loss_fn(*outputs, *labels).

    Runs INSIDE the parameter binding (see functional_call): a loss that
    references model parameters — a fused tied-embedding head, weight
    penalties — must differentiate the traced arrays; calling it after
    the binding restores would silently drop those grad contributions.
    Shared by TrainStep and ShardMapDPStep so the unwrap/rewrap contract
    lives in one place.
    """
    def _loss_post(out):
        outs = out if isinstance(out, (list, tuple)) else (out,)
        t_outs = [Tensor(o._data if isinstance(o, Tensor) else o,
                         stop_gradient=False) for o in outs]
        t_labels = [Tensor(l) for l in labels]
        return loss_fn(*t_outs, *t_labels)
    return _loss_post


def write_back_params(layer, params):
    pmap = dict(layer.named_parameters())
    for name, arr in params.items():
        pmap[name]._data = arr


def write_back_buffers(layer, buffers):
    bmap = dict(layer.named_buffers())
    for name, arr in buffers.items():
        if name in bmap and bmap[name] is not None:
            bmap[name]._data = arr


class TrainStep:
    """Compiled training step: forward + backward + optimizer update fused
    into one XLA program.

    loss_fn(model_out..., *labels) -> scalar Tensor, built from paddle ops.
    Shardings (distributed strategies) are injected via `shard_fn`, a
    callback mapping (param_name, array) -> jax.sharding spec; see
    distributed/strategy.py.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True,
                 in_shardings=None, out_shardings=None, mesh=None,
                 batch_sharding=None, grad_sync=None, k_steps=1,
                 grad_merge_avg=True, amp_dtype=None, remat=False,
                 sp_state=None, pp_state=None, init_loss_scaling=65536.0,
                 ls_growth_interval=2000, fce_sharding=None,
                 attn_sharding=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._jitted = None
        self._step_index = 0
        self._mesh = mesh
        # vocab-parallel fused-CE constraint (ops/fused_ce.logits_sharding),
        # entered around every trace/step by _sp_scope
        self._fce_sharding = fce_sharding
        # layout of the [B, N, H, D] attention operands on the mesh
        # (ops/flash_attention.partitioned): GSPMD cannot partition the
        # Pallas flash kernels, so they run per shard under shard_map
        self._attn_sharding = attn_sharding
        self._in_shardings = in_shardings
        self._out_shardings = out_shardings
        self._batch_sharding = batch_sharding
        self._grad_sync = grad_sync
        self._donate = donate
        # AMP O2-style compute policy (reference fleet AMPOptimizer /
        # pure-fp16): params+float inputs cast to `amp_dtype` for fwd/bwd,
        # fp32 master params live in the optimizer update. fp16 engages
        # dynamic loss scaling (reference check_finite_and_unscale +
        # update_loss_scaling ops); bf16 has fp32's range and needs none.
        self._amp_dtype = (jnp.bfloat16 if amp_dtype in (True, 'bfloat16')
                           else jnp.float16 if amp_dtype == 'float16'
                           else amp_dtype)
        self._loss_scaling = self._amp_dtype == jnp.float16
        self._init_loss_scaling = float(init_loss_scaling)
        self._ls_growth_interval = int(ls_growth_interval)
        if self._loss_scaling and int(k_steps) > 1:
            raise NotImplementedError(
                'fp16 loss scaling is not composed with gradient merge '
                'yet; use bf16 amp (the TPU-native dtype) with '
                'gradient_merge')
        # global activation recompute (reference RecomputeOptimizer):
        # jax.checkpoint over the whole fwd — backward recomputes
        # activations instead of saving them
        self._remat = bool(remat)
        # sequence/pipeline-parallel routing states, active only inside
        # this step's trace/execution (distributed/sp.py sp_scope,
        # distributed/pipeline.py pp_scope)
        self._sp_state = sp_state
        self._pp_state = pp_state
        # gradient merge (reference GradientMergeOptimizer): accumulate
        # k_steps micro-batch grads, apply the optimizer on the k-th
        self._k_steps = int(k_steps)
        self._grad_merge_avg = grad_merge_avg
        self._param_names = list(extract_params(model).keys())
        self._trainable = {name: not p.stop_gradient
                           for name, p in model.named_parameters()}

    # -- optimizer state pytree --------------------------------------------
    def _opt_state(self):
        opt = self.optimizer
        pmap = dict(self.model.named_parameters())
        slots = {}
        for name in self._param_names:
            if self._trainable[name]:
                slots[name] = dict(opt._get_slots(pmap[name]))
        state = {'slots': slots,
                 'step': jnp.asarray(opt._step_count, jnp.int32)}
        if self._k_steps > 1:
            acc = getattr(self, '_gm_acc', None)
            # f32 accumulators for low-precision params (the reference's
            # fp16 gradient-merge accumulates in fp32): summing K same-
            # magnitude grads in bf16 loses ~log2(K) of its 8 mantissa bits
            from ..optimizer.optimizers import _is_low_precision
            state['acc'] = acc if acc is not None else {
                name: jnp.zeros(
                    pmap[name]._data.shape,
                    jnp.float32 if _is_low_precision(pmap[name]._data)
                    else pmap[name]._data.dtype)
                for name in slots}
            state['micro'] = getattr(
                self, '_gm_micro', jnp.zeros((), jnp.int32))
        if self._loss_scaling:
            state['loss_scale'] = getattr(
                self, '_ls_scale',
                jnp.asarray(self._init_loss_scaling, jnp.float32))
            state['growth'] = getattr(
                self, '_ls_growth', jnp.zeros((), jnp.int32))
        return state

    def _write_opt_state(self, state):
        opt = self.optimizer
        pmap = dict(self.model.named_parameters())
        for name, s in state['slots'].items():
            opt._slots[id(pmap[name])] = dict(s)
        # keep the step counter device-side: int(...) would block the host
        # on the step's completion, serializing the dispatch pipeline
        opt._step_count = state['step']
        if self._k_steps > 1:
            self._gm_acc = state['acc']
            self._gm_micro = state['micro']
        if self._loss_scaling:
            self._ls_scale = state['loss_scale']
            self._ls_growth = state['growth']

    # -- the pure step ------------------------------------------------------
    def _build(self, sample_batch):
        model, opt, loss_fn = self.model, self.optimizer, self.loss_fn
        trainable = self._trainable
        grad_sync = self._grad_sync
        pmeta = dict(model.named_parameters())  # metadata: need_clip, lr, reg

        amp_dtype = self._amp_dtype
        loss_scaling = self._loss_scaling

        def _amp_cast(tree):
            return {k: (v.astype(amp_dtype)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in tree.items()}

        opt_scope = 'optimizer.' + type(opt).__name__.lower()
        pp_state = self._pp_state
        use_1f1b = False
        if pp_state is not None and pp_state.get('schedule') == '1f1b':
            from ..distributed.pipeline_1f1b import supports_1f1b
            if supports_1f1b(model):
                use_1f1b = True
            else:
                # models without a pre/blocks/post split keep training —
                # GPipe is the schedule the generic pipeline path runs
                import warnings
                warnings.warn(
                    'pipeline schedule_mode=1F1B needs %s.pp_decompose() '
                    '(pre/blocks/post split); falling back to the GPipe '
                    'schedule' % type(model).__name__)
                self._pp_state = pp_state = dict(pp_state,
                                                 schedule='gpipe')
                if pp_state.get('n_micro_defaulted'):
                    # undo the 1F1B-only 2*pp default: GPipe's minimum
                    # n_micro is pp, and keeping 2*pp would tighten the
                    # batch divisibility constraint for no benefit
                    pp_state['n_micro'] = pp_state['n_stages']

        def pure_step(params, buffers, opt_state, batch, lr, key):
            inputs, labels = batch

            def compute_loss(train_params):
                all_params = dict(params)
                all_params.update(train_params)
                call_buffers = buffers
                call_inputs = inputs
                if amp_dtype is not None:
                    all_params = _amp_cast(all_params)
                    call_buffers = _amp_cast(buffers)
                    call_inputs = tuple(
                        a.astype(amp_dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a
                        for a in inputs)
                if use_1f1b:
                    # micro-level loss lives inside the pipelined region
                    # (pipeline_1f1b.py); loss_fn is forwarded into the
                    # model's pp_decompose post stage. The step key is
                    # seated around it so the schedule's dropout base key
                    # derives from the traced per-step key (and the split
                    # tracer cannot leak into the live generator)
                    from ..distributed.pipeline_1f1b import one_f_one_b_loss
                    with rng_mod.key_scope(key):
                        loss_val = one_f_one_b_loss(
                            model, all_params, call_inputs[0], labels[0],
                            self._pp_state,
                            loss_fn=loss_fn).astype(jnp.float32)
                    if loss_scaling:
                        return loss_val * opt_state['loss_scale'], \
                            ({}, loss_val)
                    return loss_val, {}
                with rng_mod.key_scope(key):
                    loss_arr, new_buf = functional_call(
                        model, all_params, call_buffers, args=call_inputs,
                        training=True,
                        post_fn=make_loss_post(loss_fn, labels))
                loss_val = loss_arr
                if amp_dtype is not None:
                    loss_val = loss_val.astype(jnp.float32)
                new_buf = _cast_like(new_buf, buffers)
                if loss_scaling:
                    # differentiate the SCALED loss so fp16 cotangents stay
                    # above the fp16 underflow floor; report the raw loss
                    return loss_val * opt_state['loss_scale'], \
                        (new_buf, loss_val)
                return loss_val, new_buf

            if self._remat:
                compute_loss = jax.checkpoint(compute_loss)
            train_params = {k: v for k, v in params.items() if trainable[k]}
            (loss, aux), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(train_params)
            if loss_scaling:
                new_buffers, loss = aux
                grads = {n: g / opt_state['loss_scale']
                         for n, g in grads.items()}
            else:
                new_buffers = aux
            if grad_sync is not None:
                grads = grad_sync(grads)

            # mirror Optimizer.step()'s full semantics in pure form:
            # grad clip -> (coupled) weight decay / regularizer ->
            # per-param lr -> update rule -> decoupled decay (AdamW)
            # (the scope is metadata on the update's device ops: a trace
            # reads `optimizer.adamw` where it read `fusion`)
            @jax.named_scope(opt_scope)
            def apply_updates(gdict):
                if opt._grad_clip is not None:
                    names = list(gdict.keys())
                    pg = [(pmeta[n], Tensor(gdict[n])) for n in names]
                    clipped = opt._grad_clip(pg)
                    gdict = {n: (g._data if isinstance(g, Tensor) else g)
                             for n, (_, g) in zip(names, clipped)}
                coeff = opt._decay_coeff()
                decoupled = opt._apply_decoupled_decay()
                decay_fun = getattr(opt, '_apply_decay_param_fun', None)
                t = opt_state['step'] + 1
                new_slots = {}
                new_params = dict(params)
                for name, g in gdict.items():
                    old_slots = opt_state['slots'][name]
                    master = old_slots.get('master')
                    # multi_precision: the update rule runs on the f32
                    # master; the stored param is its rounded shadow
                    p = master if master is not None else params[name]
                    g = g.astype(p.dtype)
                    meta = pmeta[name]
                    if coeff and not decoupled:
                        g = g + coeff * p
                    if meta.regularizer is not None:
                        g = meta.regularizer._append(g, p)
                    plr = lr * meta.optimize_attr.get('learning_rate', 1.0)
                    if coeff and decoupled and \
                            (decay_fun is None or decay_fun(meta.name)):
                        p = p * (1.0 - plr * coeff)
                    opt._apply_param_name = meta.name
                    new_p, slots = opt._apply(p, g, old_slots, plr, t)
                    slots = _cast_like(slots, old_slots)
                    if master is not None:
                        slots['master'] = new_p.astype(jnp.float32)
                    new_params[name] = new_p.astype(params[name].dtype)
                    new_slots[name] = slots
                return new_params, new_slots, t

            K = self._k_steps
            if K == 1:
                if not loss_scaling:
                    new_params, new_slots, t = apply_updates(grads)
                    return new_params, new_buffers, \
                        {'slots': new_slots, 'step': t}, loss

                # dynamic loss scaling (reference operators/amp/
                # check_finite_and_unscale + update_loss_scaling): skip the
                # update on overflow, halve the scale; grow it after
                # `growth_interval` consecutive finite steps
                finite = jnp.asarray(True)
                for g in grads.values():
                    finite = jnp.logical_and(finite, jnp.isfinite(g).all())

                def do_apply(_):
                    return apply_updates(grads)

                def skip_apply(_):
                    return (dict(params),
                            {n: dict(opt_state['slots'][n]) for n in grads},
                            opt_state['step'])

                new_params, new_slots, t = jax.lax.cond(
                    finite, do_apply, skip_apply, None)
                scale = opt_state['loss_scale']
                growth = opt_state['growth']
                grown = growth + 1 >= self._ls_growth_interval
                new_scale = jnp.where(
                    finite,
                    jnp.where(grown, jnp.minimum(scale * 2.0, 2.0 ** 24),
                              scale),
                    jnp.maximum(scale * 0.5, 1.0))
                new_growth = jnp.where(finite & ~grown, growth + 1, 0)
                return new_params, new_buffers, \
                    {'slots': new_slots, 'step': t,
                     'loss_scale': new_scale, 'growth': new_growth}, loss

            # gradient merge: accumulate raw grads; clip/decay/update run
            # only on the k-th micro step (lax.cond keeps one XLA program)
            micro = opt_state['micro'] + 1
            new_acc = {n: opt_state['acc'][n] + grads[n].astype(
                opt_state['acc'][n].dtype) for n in grads}

            def do_apply(_):
                scale = 1.0 / K if self._grad_merge_avg else 1.0
                # no downcast here: apply_updates casts to the update
                # operand's dtype (the f32 master when one exists)
                eff = {n: a * scale for n, a in new_acc.items()}
                np_, ns_, t_ = apply_updates(eff)
                return (np_, ns_, t_,
                        {n: jnp.zeros_like(a) for n, a in new_acc.items()},
                        jnp.zeros((), jnp.int32))

            def skip(_):
                return (dict(params),
                        {n: dict(opt_state['slots'][n])
                         for n in new_acc},
                        opt_state['step'], new_acc, micro)

            new_params, new_slots, t, acc_out, micro_out = jax.lax.cond(
                micro >= K, do_apply, skip, None)
            return new_params, new_buffers, \
                {'slots': new_slots, 'step': t, 'acc': acc_out,
                 'micro': micro_out}, loss

        jit_kwargs = {}
        if self._donate:
            jit_kwargs['donate_argnums'] = (0, 2)
        if self._in_shardings is not None:
            jit_kwargs['in_shardings'] = self._in_shardings
        if self._out_shardings is not None:
            jit_kwargs['out_shardings'] = self._out_shardings
        self._pure_step = pure_step
        return jax.jit(pure_step, **jit_kwargs)

    def _lr_array(self):
        """Device-resident lr, re-uploaded only when the python value
        changes (a scheduler step) — not once per train step."""
        lr = self.optimizer.get_lr()
        cached = getattr(self, '_lr_cache', None)
        if cached is None or cached[0] != lr:
            self._lr_cache = (lr, jnp.asarray(lr, jnp.float32))
        return self._lr_cache[1]

    def _step_args(self, inputs, labels):
        """Normalize a host batch into pure_step's argument tuple."""
        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        if not isinstance(labels, (list, tuple)):
            labels = (labels,)
        in_arrays = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                          for a in inputs)
        lab_arrays = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                           for a in labels)
        return in_arrays, lab_arrays

    def _sp_scope(self):
        import contextlib
        stack = contextlib.ExitStack()
        if self._sp_state is not None:
            from ..distributed.sp import sp_scope
            stack.enter_context(sp_scope(self._sp_state))
        if self._pp_state is not None:
            from ..distributed.pipeline import pp_scope
            stack.enter_context(pp_scope(self._pp_state))
        fce = self._fce_sharding
        if fce is not None:
            # vocab-parallel fused CE under tensor parallelism: constrain
            # the transient logits tiles (set by fleet_train_step)
            from ..ops.fused_ce import logits_sharding
            stack.enter_context(logits_sharding(fce))
        if self._attn_sharding is not None:
            from ..ops.flash_attention import partitioned
            stack.enter_context(partitioned(self._attn_sharding))
        return stack

    def trace_jaxpr(self, inputs, labels):
        """str(jaxpr) of the pure step on this batch — lets tests assert a
        strategy flag actually transformed the program (the reference's
        program-transform assertions, test_fleet_*_meta_optimizer.py)."""
        in_arrays, lab_arrays = self._step_args(inputs, labels)
        with self._sp_scope():
            if self._jitted is None:
                self._jitted = self._build((in_arrays, lab_arrays))
            params = extract_params(self.model)
            buffers = extract_buffers(self.model)
            opt_state = self._opt_state()
            lr = self._lr_array()
            # make_jaxpr never executes the program: a peek at the current
            # key suffices (advancing the stream here would desync a
            # parity run that traces between steps)
            key = rng_mod.default_generator()._key
            jaxpr = jax.make_jaxpr(self._pure_step)(
                params, buffers, opt_state, (in_arrays, lab_arrays), lr, key)
        return str(jaxpr)

    def _build_multi(self):
        pure_step = self._pure_step
        jit_kwargs = {}
        if self._donate:
            jit_kwargs['donate_argnums'] = (0, 2)
        if self._out_shardings is not None:
            # same pytree as the single step: (params, buffers, opt_state,
            # loss) — the strategy's layout contract holds across the scan
            # (the loss entry's replicated spec covers the [K] losses too)
            jit_kwargs['out_shardings'] = self._out_shardings

        def multi(params, buffers, opt_state, batches, lr, keys):
            def body(carry, xs):
                p, b, o = carry
                batch, key = xs
                np_, nb, no, loss = pure_step(p, b, o, batch, lr, key)
                return (np_, nb, no), loss
            (p, b, o), losses = jax.lax.scan(
                body, (params, buffers, opt_state), (batches, keys))
            return p, b, o, losses
        return jax.jit(multi, **jit_kwargs)

    def multi_step(self, inputs, labels):
        """K training steps in ONE dispatch: `lax.scan` over the step body.

        Every input/label array carries a leading K axis. The device runs
        all K fwd+bwd+update iterations without returning to the host —
        the XLA-native analog of the reference's executor-driven
        multi-iteration `Run` (fluid Executor runs a whole program once
        per call), and the lever that amortizes per-dispatch host time.
        Returns the K losses as a Tensor.
        """
        in_arrays, lab_arrays = self._step_args(inputs, labels)
        if self._batch_sharding is not None:
            # the per-step batch sharding shards dim 0 = batch; here dim 0
            # is the K scan axis, so prepend None to keep the batch dim
            # (now dim 1) on the dp axis
            bs = self._batch_sharding
            try:
                from jax.sharding import NamedSharding, PartitionSpec as P
                ks = NamedSharding(bs.mesh, P(None, *tuple(bs.spec)))
            except (AttributeError, TypeError):
                ks = bs
            in_arrays = tuple(jax.device_put(a, ks) for a in in_arrays)
            lab_arrays = tuple(jax.device_put(a, ks) for a in lab_arrays)
        k = in_arrays[0].shape[0]
        with self._sp_scope():
            if self._jitted is None:
                sample = (tuple(a[0] for a in in_arrays),
                          tuple(a[0] for a in lab_arrays))
                self._jitted = self._build(sample)
            if getattr(self, '_jitted_multi', None) is None:
                self._jitted_multi = self._build_multi()
            params = extract_params(self.model)
            buffers = extract_buffers(self.model)
            opt_state = self._opt_state()
            lr = self._lr_array()
            keys = jax.random.split(rng_mod.next_key(), k)
            new_params, new_buffers, new_opt_state, losses = \
                self._jitted_multi(params, buffers, opt_state,
                                   (in_arrays, lab_arrays), lr, keys)
        write_back_params(self.model, new_params)
        write_back_buffers(self.model, new_buffers)
        self._write_opt_state(new_opt_state)
        return Tensor(losses)

    def compiled_executable(self, inputs, labels):
        """Compile the step for this batch and return the jax Compiled
        object (without executing) — tests read its HLO text, input
        shardings, and memory_analysis() (peak temp bytes is the honest
        metric for 'does this transformation actually save memory';
        HLO-text tensor counts are only a proxy)."""
        in_arrays, lab_arrays = self._step_args(inputs, labels)
        if self._batch_sharding is not None:
            in_arrays = tuple(jax.device_put(a, self._batch_sharding)
                              for a in in_arrays)
            lab_arrays = tuple(jax.device_put(a, self._batch_sharding)
                               for a in lab_arrays)
        with self._sp_scope():
            if self._jitted is None:
                self._jitted = self._build((in_arrays, lab_arrays))
            params = extract_params(self.model)
            buffers = extract_buffers(self.model)
            opt_state = self._opt_state()
            lr = self._lr_array()
            key = rng_mod.default_generator()._key
            return self._jitted.lower(
                params, buffers, opt_state, (in_arrays, lab_arrays), lr,
                key).compile()

    def compiled_hlo(self, inputs, labels):
        """Optimized (post-SPMD-partitioning) HLO of the step, plus the
        compiled executable's input shardings for the params pytree.

        Returns (hlo_text, param_shardings dict). Tests assert the
        partitioner REALLY inserted the expected collectives and sharded
        the parameters at realistic dims — the TPU analog of the
        reference's program-transform assertions
        (test_fleet_*_meta_optimizer.py, SURVEY §4.2)."""
        compiled = self.compiled_executable(inputs, labels)
        hlo = compiled.as_text()
        try:
            pshard = compiled.input_shardings[0][0]
        except Exception:
            pshard = None
        return hlo, pshard

    def __call__(self, inputs, labels):
        """One step; returns the loss as a Tensor. The `train.step` span
        (host tracer + device-trace annotation) covers what the host
        does for a step — batch placement, trace-cache lookup, dispatch
        — and ends when the dispatch returns, not when the device is
        done: against the step's wall it reads the host's head-room."""
        self._step_index += 1
        with _tracing.default_tracer().start_span(
                'train.step', annotate=True) as span:
            if span:
                span.set_tag('step', self._step_index)
            return self._step(inputs, labels)

    def _step(self, inputs, labels):
        in_arrays, lab_arrays = self._step_args(inputs, labels)
        if self._batch_sharding is not None:
            in_arrays = tuple(jax.device_put(a, self._batch_sharding)
                              for a in in_arrays)
            lab_arrays = tuple(jax.device_put(a, self._batch_sharding)
                               for a in lab_arrays)
        with self._sp_scope():
            if self._jitted is None:
                self._jitted = self._build((in_arrays, lab_arrays))
            params = extract_params(self.model)
            buffers = extract_buffers(self.model)
            opt_state = self._opt_state()
            lr = self._lr_array()
            key = rng_mod.next_key()
            new_params, new_buffers, new_opt_state, loss = self._jitted(
                params, buffers, opt_state, (in_arrays, lab_arrays), lr, key)
        write_back_params(self.model, new_params)
        write_back_buffers(self.model, new_buffers)
        self._write_opt_state(new_opt_state)
        if isinstance(self.optimizer._lr, object) and hasattr(
                self.optimizer._lr, 'step') and not isinstance(
                self.optimizer._lr, (int, float)):
            pass  # LR scheduler stepping left to the user loop (paddle parity)
        return Tensor(loss)
