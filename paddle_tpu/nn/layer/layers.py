"""Layer base class (reference: python/paddle/fluid/dygraph/layers.py).

Holds Parameters/buffers/sublayers as instance state (mutable, paddle-style)
while staying functionalizable: framework/functional.py can pull the param
pytree out, run forward under a jit trace with tracer-backed params bound in,
and push updated arrays back — that is how the fast path compiles.
"""
import collections
import contextlib

import numpy as np
import jax
import jax.numpy as jnp

from ...framework.core import Tensor, Parameter, no_grad_guard
from ...framework import dtype as dtype_mod
from .. import initializer as init_mod

__all__ = ['Layer', 'ParamAttr', 'skip_init']

_SKIP_INIT = [False]


@contextlib.contextmanager
def skip_init():
    """Build layers without initialising them: inside, `create_parameter`
    gives a Parameter of the right shape and dtype with NO array behind
    it (`_data` is a `jax.ShapeDtypeStruct`), for a caller that puts
    every value in itself before the first use — a served model whose
    weights come from elsewhere and would not fit the device beside a
    random initialisation of the same size. Reading such a parameter's
    values before they are put raises."""
    before, _SKIP_INIT[0] = _SKIP_INIT[0], True
    try:
        yield
    finally:
        _SKIP_INIT[0] = before


class ParamAttr:
    """paddle.ParamAttr parity (python/paddle/fluid/param_attr.py)."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.do_model_average = do_model_average
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, init_mod.Initializer):
            return ParamAttr(initializer=attr)
        if attr is False:
            return False
        return ParamAttr()


class _HookRemoveHelper:
    def __init__(self, hooks, hid):
        self._hooks, self._hid = hooks, hid

    def remove(self):
        self._hooks.pop(self._hid, None)


_name_counters = collections.defaultdict(int)


class Layer:
    def __init__(self, name_scope=None, dtype='float32'):
        cls = self.__class__.__name__.lower()
        _name_counters[cls] += 1
        self._full_name = "%s_%d" % (name_scope or cls, _name_counters[cls])
        self._dtype = dtype_mod.convert_dtype(dtype) if dtype else 'float32'
        self.training = True
        self._parameters = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers = collections.OrderedDict()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()

    # -- construction -------------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        initializer = attr.initializer or default_initializer
        if initializer is None:
            initializer = (init_mod.Constant(0.0) if is_bias
                           else init_mod.XavierNormal())
        if _SKIP_INIT[0]:
            jdtype = dtype_mod.to_jax_dtype(dtype)
            p = Parameter(jnp.zeros((), jdtype), name=attr.name,
                          trainable=attr.trainable)
            p._data = jax.ShapeDtypeStruct(tuple(shape), jdtype)
        else:
            p = Parameter(initializer(shape, dtype), name=attr.name,
                          trainable=attr.trainable)
        p.optimize_attr['learning_rate'] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def create_variable(self, name=None, persistable=False, dtype=None):
        t = Tensor(jnp.zeros((), dtype_mod.to_jax_dtype(dtype or self._dtype)))
        t.persistable = persistable
        return t

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- attribute magic ----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get('_parameters')
        layers = self.__dict__.get('_sub_layers')
        buffers = self.__dict__.get('_buffers')
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() first")
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            buffers[name] = value if (value is None or isinstance(value, Tensor)) \
                else Tensor(value)
        else:
            if params is not None:
                params.pop(name, None)
            if layers is not None:
                layers.pop(name, None)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ('_parameters', '_buffers', '_sub_layers'):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError("'%s' object has no attribute '%s'"
                             % (type(self).__name__, name))

    def __delattr__(self, name):
        for store in ('_parameters', '_buffers', '_sub_layers'):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = list(self._parameters) + list(self._buffers) + list(self._sub_layers)
        return sorted(set(super().__dir__() + extra))

    # -- traversal ----------------------------------------------------------
    def named_parameters(self, prefix='', include_sublayers=True):
        seen = set()
        for lname, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (lname + '.' + pname if lname else pname), p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix='', include_sublayers=True):
        seen = set()
        for lname, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (lname + '.' + bname if lname else bname), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix='', include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + ('.' if prefix else '') + name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return [l for l in self._sub_layers.values() if l is not None]

    def named_children(self):
        return [(n, l) for n, l in self._sub_layers.items() if l is not None]

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- mode ---------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix='', use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for lname, layer in self.named_sublayers(include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                key = structured_name_prefix + \
                    (lname + '.' + bname if lname else bname)
                dest[key] = b
        return dest

    to_static_state_dict = state_dict

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = self.state_dict()
        if not use_structured_name:
            # reference semantics: checkpoint keys are the parameters'
            # own .name attributes instead of structured paths
            remapped = collections.OrderedDict()
            for key, t in own.items():
                nm = getattr(t, 'name', None) or key
                if nm in remapped:
                    raise ValueError(
                        'set_state_dict(use_structured_name=False): '
                        'duplicate parameter name %r — names must be '
                        'unique to load by name' % nm)
                remapped[nm] = t
            own = remapped
        for key, target in own.items():
            if key in state_dict:
                v = state_dict[key]
                arr = v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
                target.set_value(arr.reshape(tuple(target.shape)))
            else:
                missing.append(key)
        for key in state_dict:
            if key not in own:
                unexpected.append(key)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device motion ---------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._to_dtype(dtype)
        if device is not None:
            self._to_device(device)
        return self

    def _to_device(self, device):
        """Move params/buffers to a device spec ('cpu', 'tpu:3', a Place,
        or a jax.Device — one resolver, shared with set_device). blocking
        is irrelevant: device_put is async and ordered for us by XLA."""
        import jax
        from ...framework.device import resolve_device
        dev = resolve_device(device)
        for _, p in self.named_parameters():
            p._data = jax.device_put(p._data, dev)
        for _, b in self.named_buffers():
            b._data = jax.device_put(b._data, dev)

    def _to_dtype(self, dtype):
        jd = dtype_mod.to_jax_dtype(dtype)
        for _, p in self.named_parameters():
            if jnp.issubdtype(p._data.dtype, jnp.floating):
                p._data = p._data.astype(jd)
        for _, b in self.named_buffers():
            if b is not None and jnp.issubdtype(b._data.dtype, jnp.floating):
                b._data = b._data.astype(jd)
        for l in self.sublayers(include_self=True):
            l._dtype = dtype_mod.convert_dtype(dtype)
        return self

    def astype(self, dtype):
        return self._to_dtype(dtype)

    def float(self):
        return self._to_dtype('float32')

    def half(self):
        return self._to_dtype('float16')

    def bfloat16(self):
        return self._to_dtype('bfloat16')

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        hid = len(self._forward_pre_hooks)
        self._forward_pre_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = len(self._forward_post_hooks)
        self._forward_post_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_post_hooks, hid)

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def full_name(self):
        return self._full_name

    def extra_repr(self):
        return ''

    def __repr__(self):
        extra = self.extra_repr()
        lines = [extra] if extra else []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split('\n')
            rep = [rep[0]] + ['  ' + r for r in rep[1:]]
            lines.append('(%s): %s' % (name, '\n'.join(rep)))
        main = self.__class__.__name__
        if not lines:
            return main + ('(%s)' % extra if extra else '()')
        return main + '(\n  ' + '\n  '.join(lines) + '\n)'

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
