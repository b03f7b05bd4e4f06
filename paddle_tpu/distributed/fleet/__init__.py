"""Fleet API (reference: fleet/base/fleet_base.py:139 init, :783
distributed_optimizer, :836 distributed_model, :1288 minimize).

TPU-native: fleet composes a Mesh (HybridCommunicateGroup), per-strategy
sharding specs (strategy.py), and a jitted TrainStep — the meta-optimizer
program-rewrite pipeline collapses into spec composition (SURVEY.md §7.1).
"""
import os

from .distributed_strategy import DistributedStrategy
from ..topology import (HybridCommunicateGroup, set_hybrid_communicate_group,
                        get_hybrid_communicate_group)
from ..env import get_rank, get_world_size, init_parallel_env
from .. import strategy as strategy_mod
from ...framework import functional as func_mod

__all__ = ['init', 'DistributedStrategy', 'distributed_optimizer',
           'distributed_model', 'get_hybrid_communicate_group',
           'worker_index', 'worker_num', 'is_worker', 'is_server', 'barrier_worker',
           'init_worker', 'init_server', 'run_server', 'stop_worker',
           'UserDefinedRoleMaker', 'PaddleCloudRoleMaker', 'minimize',
           'distributed_scaler', 'fleet_train_step', 'meta_parallel',
           'utils']

from .. import meta_parallel  # noqa: E402,F401
from . import utils  # noqa: E402,F401

_FLEET = {'initialized': False, 'strategy': None, 'hcg': None,
          'is_collective': True, 'model': None, 'optimizer': None,
          'train_step': None, 'role_maker': None}


class PaddleCloudRoleMaker:
    """reference: fleet/base/role_maker.py:946 — reads PADDLE_* env."""

    def __init__(self, is_collective=False, **kwargs):
        self._is_collective = is_collective

    def _worker_index(self):
        return get_rank()

    def _worker_num(self):
        return get_world_size()

    def _is_worker(self):
        return os.environ.get('TRAINING_ROLE', 'TRAINER') == 'TRAINER'

    def _is_server(self):
        return os.environ.get('TRAINING_ROLE', '') == 'PSERVER'


class UserDefinedRoleMaker(PaddleCloudRoleMaker):
    def __init__(self, current_id=0, role='TRAINER', worker_num=1,
                 server_endpoints=None, **kwargs):
        super().__init__()
        self._cur = current_id
        self._n = worker_num

    def _worker_index(self):
        return self._cur

    def _worker_num(self):
        return self._n


def init(role_maker=None, is_collective=False, strategy=None, log_level='INFO'):
    strategy = strategy or DistributedStrategy()
    _FLEET['strategy'] = strategy
    _FLEET['is_collective'] = is_collective or role_maker is None
    _FLEET['role_maker'] = role_maker or PaddleCloudRoleMaker(is_collective)
    init_parallel_env()

    hc = strategy.hybrid_configs
    try:
        hcg = HybridCommunicateGroup(
            dp_degree=hc.get('dp_degree', -1),
            mp_degree=hc.get('mp_degree', 1),
            pp_degree=hc.get('pp_degree', 1),
            sharding_degree=hc.get('sharding_degree', 1),
            sp_degree=hc.get('sp_degree', 1),
            ep_degree=hc.get('ep_degree', 1))
    except ValueError:
        # degrees don't match the device count: fall back to pure DP
        hcg = HybridCommunicateGroup(dp_degree=-1)
    _FLEET['hcg'] = hcg
    set_hybrid_communicate_group(hcg)
    _FLEET['initialized'] = True


def _strategy_dict(s=None):
    s = s or _FLEET['strategy'] or DistributedStrategy()
    return {
        'zero_stage': s._zero_stage(),
        'tensor_parallel': s.tensor_parallel,
        'sequence_parallel': s.sequence_parallel,
        'amp': s.amp,
        'recompute': s.recompute,
        'gradient_merge_k': (s.gradient_merge_configs.get('k_steps', 1)
                             if s.gradient_merge else 1),
    }


def distributed_model(model):
    """reference fleet_base.py:836: wraps per hybrid config. Here: record the
    model and place its params onto the mesh per strategy."""
    _FLEET['model'] = model
    hcg = _FLEET['hcg']
    if hcg is not None and _FLEET['optimizer'] is not None:
        _prepare_train_step()
    return model


class _FleetOptimizer:
    """Wrapper returned by distributed_optimizer: step() runs the jitted
    sharded TrainStep when a model is registered, else plain step."""

    def __init__(self, inner, strategy):
        self._inner = inner
        self._strategy = strategy

    def __getattr__(self, name):
        return getattr(self.__dict__['_inner'], name)

    def step(self):
        self._inner.step()

    def clear_grad(self):
        self._inner.clear_grad()

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        return self._inner.minimize(loss, startup_program, parameters,
                                    no_grad_set)


def distributed_optimizer(optimizer, strategy=None):
    if strategy is not None:
        _FLEET['strategy'] = strategy
    _FLEET['optimizer'] = optimizer
    return _FleetOptimizer(optimizer, _FLEET['strategy'])


def _prepare_train_step():
    """distributed_model's placement step (reference fleet_base.py:836
    broadcasts/places initial params when the model is wrapped): put every
    parameter onto the fleet mesh under the strategy's shardings NOW, so
    the first fleet_train_step compiles against pre-placed arrays and
    large models never materialize fully replicated. Optimizer slots are
    NOT touched here — they must be created after placement (zeros_like
    of the sharded param; see place_opt_slots), which fleet_train_step
    does."""
    model = _FLEET['model']
    hcg = _FLEET['hcg']
    if model is None or hcg is None:
        return
    cfg = strategy_mod.build_shardings(model, strategy_mod._NullOpt(),
                                       hcg.mesh, _strategy_dict())
    strategy_mod.place_params(model, cfg['param_shardings'])


def fleet_train_step(model, loss_fn, optimizer, strategy=None, hcg=None):
    """Build the sharded jitted TrainStep for (model, loss, optimizer) under
    the fleet strategy — the executable artifact of fleet.minimize.

    Strategy routing (reference meta-optimizer selection,
    base/strategy_compiler.py): localsgd/dgc/fp16_allreduce need explicit
    collectives, so they build the shard_map engine
    (meta_optimizers.ShardMapDPStep) over the dp axis; everything else
    (dp/mp/sharding/amp/recompute/gradient_merge) composes in the pjit
    TrainStep. lars/lamb swap the optimizer first.
    """
    from . import meta_optimizers as mo

    hcg = hcg or _FLEET['hcg']
    if hcg is None:
        init(is_collective=True, strategy=strategy)
        hcg = _FLEET['hcg']
    s = strategy if isinstance(strategy, DistributedStrategy) \
        else _FLEET['strategy'] or DistributedStrategy()
    optimizer = mo.select_optimizer(optimizer, s)

    # one strategy object governs BOTH the step build and the shardings —
    # deriving them from different objects caused pytree mismatches
    sdict = _strategy_dict(s)
    gm_k = sdict['gradient_merge_k']
    wants_explicit = s.localsgd or s.adaptive_localsgd or s.dgc or \
        s.fp16_allreduce
    if wants_explicit:
        pure_dp = hcg.mesh.size == hcg.get_data_parallel_world_size()
        if not pure_dp:
            raise ValueError(
                'localsgd/dgc/fp16_allreduce run on a pure data-parallel '
                'mesh (mp/pp/sharding degree 1); got %s' % (hcg.mesh,))
        adaptive = False
        if s.localsgd or s.adaptive_localsgd:
            mode = 'local'
            if s.adaptive_localsgd:
                adaptive = True
                k = s.adaptive_localsgd_configs.get('init_k_steps', 1)
            else:
                k = s.localsgd_configs.get('k_steps', 1)
        elif s.dgc:
            mode = 'dgc'
            k = 1
        else:
            mode = 'fp16'
            k = 1
        from jax.sharding import Mesh as _Mesh
        import numpy as _np
        dp_mesh = _Mesh(_np.asarray(hcg.mesh.devices).reshape(-1), ('dp',))
        return mo.ShardMapDPStep(
            model, loss_fn, optimizer, mesh=dp_mesh, axis='dp', mode=mode,
            k_steps=k, gm_k_steps=gm_k, adaptive=adaptive,
            momentum=s.dgc_configs.get('momentum', 0.9),
            sparsity=s.dgc_configs.get('sparsity', 0.999),
            rampup_begin_step=s.dgc_configs.get('rampup_begin_step', 0),
            rampup_step=s.dgc_configs.get('rampup_step', 1))

    # sequence parallel -> sp attention routing over the 'sp' mesh axis
    # (ring by default; SURVEY §5.7 beyond-reference capability). The state
    # is scoped to the TrainStep (sp_scope) so eval/generation calls
    # between steps keep ordinary attention.
    sp_state = None
    sp_deg = hcg.get_sequence_parallel_world_size()
    if sdict['sequence_parallel'] and sp_deg > 1:
        from .. import sp as sp_mod
        shape = dict(hcg.mesh.shape)
        batch_axes = tuple(a for a in ('dp', 'sharding')
                           if shape.get(a, 1) > 1)
        sp_state = sp_mod.make_sp_state(
            hcg.mesh, axis='sp',
            mode=s.sequence_parallel_configs.get('mode', 'ring'),
            batch_axes=batch_axes,
            head_axis='mp' if shape.get('mp', 1) > 1 else None)

    # pipeline parallel -> GPipe schedule over the 'pp' mesh axis
    # (distributed/pipeline.py), scoped to the step like sp
    pp_state = None
    pp_deg = hcg.get_pipe_parallel_world_size()
    if pp_deg > 1:
        from .. import pipeline as pp_mod
        # strategy.pipeline=True engages pipeline_configs: accumulate_steps
        # and schedule_mode ('1F1B' -> interleaved schedule with loss in
        # the last stage, 'F-then-B' -> GPipe). Without the flag the
        # default GPipe schedule with n_micro=pp runs (hybrid_configs only).
        schedule = 'gpipe'
        acc = 1
        if s.pipeline:
            acc = s.pipeline_configs.get('accumulate_steps', 1)
            mode = s.pipeline_configs.get('schedule_mode', '1F1B')
            schedule = '1f1b' if str(mode).upper() == '1F1B' else 'gpipe'
        # an explicit accumulate_steps is honored as-is (>= pp); the
        # 2*pp floor applies only as the 1F1B DEFAULT (the regime where
        # its O(pp) stash wins)
        if acc > 1:
            n_micro = max(pp_deg, acc)
        else:
            n_micro = 2 * pp_deg if schedule == '1f1b' else pp_deg
        pp_state = pp_mod.make_pp_state(hcg.mesh, n_stages=pp_deg,
                                        n_micro=n_micro,
                                        remat=bool(sdict['recompute']),
                                        schedule=schedule)
        # lets the GPipe fallback (TrainStep) undo the 1F1B default
        pp_state['n_micro_defaulted'] = acc <= 1

    # amp -> O2 compute-dtype policy inside the step (reference fleet
    # AMPOptimizer); bf16 is TPU-native, fp16 only on explicit request
    amp_dtype = None
    if sdict['amp']:
        pure_fp16 = s.amp_configs.get('use_pure_fp16', False) and \
            not s.amp_configs.get('use_bf16', True)
        amp_dtype = 'float16' if pure_fp16 else 'bfloat16'
        if s.amp_configs.get('custom_white_list') or \
                s.amp_configs.get('custom_black_list'):
            import warnings
            warnings.warn(
                'fleet amp runs the O2 pure-%s policy inside the jitted '
                'step; custom_white_list/custom_black_list apply only to '
                'the eager paddle.amp.auto_cast path and are ignored here'
                % amp_dtype)
    sdict['amp_dtype'] = amp_dtype

    # (dropout composes with sp since r4: non-attention dropout partitions
    # under GSPMD, attention-prob dropout rides sp-aware folded keys in
    # distributed/sp.py sp_attention)

    # recompute -> per-block remat when the model declares segments
    # (enable_recompute), else whole-forward remat in the step. Always set
    # two-way: a True left by an earlier fleet_train_step on the same
    # model must not leak into a recompute=False build.
    remat = False
    if hasattr(model, 'enable_recompute'):
        model.enable_recompute(bool(sdict['recompute']))
    elif sdict['recompute']:
        remat = True

    # vocab-parallel fused CE (reference: c_softmax_with_cross_entropy,
    # operators/collective/): under plain tensor parallelism constrain
    # the fused-loss logits tiles to [rows over dp/sharding, vocab over
    # mp] so GSPMD computes the CE vocab-parallel (local max/sum + small
    # all-reduce) instead of gathering the vocab axis per device — the
    # r4 HLO evidence showed gathered f32[rows, vocab] tiles dominating
    # CE-region memory (769 -> 435 MB peak temp at BERT dims dp2 x mp4).
    # Restricted to sp/pp == 1: under sp the flattened rows mix
    # sp-sharded sequence, under pp the loss runs inside the pipeline
    # engine — both have their own layouts.
    fce_sharding = None
    attn_sharding = None
    mshape = dict(hcg.mesh.shape)
    if mshape.get('sp', 1) <= 1 and mshape.get('pp', 1) <= 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rows = tuple(a for a in ('dp', 'sharding') if mshape.get(a, 1) > 1)
        heads = 'mp' if mshape.get('mp', 1) > 1 else None
        if heads:
            fce_sharding = NamedSharding(
                hcg.mesh, P(rows if rows else None, 'mp'))
        # the Pallas flash kernels are not GSPMD-partitionable: name the
        # [B, N, H, D] operands' layout so each device runs them on its
        # shard (ops/flash_attention.partitioned). Same restriction as
        # above: sp attention has its own shard_map, and under pp the
        # blocks already run inside the pipeline's.
        if hcg.mesh.size > 1:
            attn_sharding = NamedSharding(
                hcg.mesh, P(rows if rows else None, None, heads, None))

    cfg = strategy_mod.build_shardings(model, optimizer, hcg.mesh, sdict)
    strategy_mod.place_params(model, cfg['param_shardings'])
    strategy_mod.place_opt_slots(model, optimizer, cfg['out_shardings'][2])
    step = func_mod.TrainStep(
        model, loss_fn, optimizer,
        out_shardings=cfg['out_shardings'],
        mesh=hcg.mesh,
        batch_sharding=cfg['batch_sharding'],
        k_steps=gm_k,
        grad_merge_avg=s.gradient_merge_configs.get('avg', True)
        if s.gradient_merge else True,
        amp_dtype=amp_dtype,
        remat=remat,
        sp_state=sp_state,
        pp_state=pp_state,
        init_loss_scaling=s.amp_configs.get('init_loss_scaling', 65536.0),
        ls_growth_interval=s.amp_configs.get('incr_every_n_steps', 2000),
        fce_sharding=fce_sharding,
        attn_sharding=attn_sharding)
    return step


def minimize(loss, startup_program=None, parameter_list=None,
             no_grad_set=None):
    opt = _FLEET['optimizer']
    return opt.minimize(loss)


def distributed_scaler(scaler):
    return scaler


def worker_index():
    return get_rank()


def worker_num():
    return get_world_size()


def is_worker():
    return _FLEET['role_maker']._is_worker() if _FLEET['role_maker'] else True


def is_server():
    return _FLEET['role_maker']._is_server() if _FLEET['role_maker'] else False


def is_first_worker():
    return get_rank() == 0


def worker_endpoints(to_string=False):
    eps = os.environ.get('PADDLE_TRAINER_ENDPOINTS', '127.0.0.1:6170').split(',')
    return ','.join(eps) if to_string else eps


def server_endpoints(to_string=False):
    eps = os.environ.get('PADDLE_PSERVERS_IP_PORT_LIST', '').split(',')
    return ','.join(eps) if to_string else eps


def barrier_worker():
    """reference fleet_base.py barrier_worker: in PS mode, rendezvous all
    workers through the service's BarrierTable (the reference the_one_ps
    reserves a table id for this — configure it via
    PADDLE_FLEET_BARRIER_TABLE_ID); collective mode under
    single-controller SPMD has no cross-process eager phase to order, so
    it is a no-op there by design."""
    from ..ps import runtime as ps_runtime
    client = ps_runtime.get_client()
    tid = os.environ.get('PADDLE_FLEET_BARRIER_TABLE_ID')
    if client is not None and tid is not None:
        client.barrier(int(tid), worker_id=worker_index())


def init_worker():
    """PS-mode worker init (reference the_one_ps.py:486): starts the
    embedding-service client when a PS strategy is active."""
    from ..ps import runtime as ps_runtime
    ps_runtime.init_worker(_FLEET)


def init_server(*args, **kwargs):
    from ..ps import runtime as ps_runtime
    ps_runtime.init_server(_FLEET, *args, **kwargs)


def run_server():
    from ..ps import runtime as ps_runtime
    ps_runtime.run_server(_FLEET)


def stop_worker():
    from ..ps import runtime as ps_runtime
    ps_runtime.stop_worker(_FLEET)


def save_inference_model(*args, **kwargs):
    from ...static import save_inference_model as _s
    return _s(*args, **kwargs)


def save_persistables(executor, dirname, main_program=None, mode=0):
    """reference fleet save_persistables: PS mode saves the server-side
    tables through the service; otherwise the registered fleet model's
    state_dict is written under `dirname` (the persistables of the
    single-controller job)."""
    from ..ps import runtime as ps_runtime
    client = ps_runtime.get_client()
    if client is not None:
        # sparse side: every service table listed for this job
        tids = os.environ.get('PADDLE_FLEET_PS_TABLE_IDS', '0')
        for tid in tids.split(','):
            client.save(int(tid), os.path.join(dirname,
                                               'table_%s' % tid.strip()))
        return
    model = _FLEET['model']
    if model is None:
        raise RuntimeError('save_persistables: no fleet model registered '
                           '(call fleet.distributed_model first) and no '
                           'PS service is running')
    from ... import save as paddle_save
    os.makedirs(dirname, exist_ok=True)
    paddle_save(model.state_dict(),
                os.path.join(dirname, 'persistables.pdparams'))
