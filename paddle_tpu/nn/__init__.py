"""paddle.nn parity surface (reference: python/paddle/nn/__init__.py)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer import *  # noqa: F401,F403
from .layer.layers import Layer, ParamAttr, skip_init  # noqa: F401
from .clip import (ClipGradByValue, ClipGradByNorm,  # noqa: F401
                   ClipGradByGlobalNorm)
from .utils_weight_norm import (weight_norm, remove_weight_norm,  # noqa: F401
                               spectral_norm, remove_spectral_norm)
from .decode import BeamSearchDecoder, dynamic_decode  # noqa: F401
from . import quant  # noqa: F401


class utils:  # namespace shim: paddle.nn.utils.*
    from .utils_weight_norm import (weight_norm, remove_weight_norm,
                                    spectral_norm, remove_spectral_norm)
    from .clip import clip_grad_norm_, clip_grad_value_

    @staticmethod
    def parameters_to_vector(parameters, name=None):
        import jax.numpy as jnp
        from ..framework.core import Tensor
        return Tensor(jnp.concatenate([p._data.reshape(-1) for p in parameters]))

    @staticmethod
    def vector_to_parameters(vec, parameters, name=None):
        import numpy as np
        offset = 0
        for p in parameters:
            n = int(np.prod(p.shape)) if p.shape else 1
            p.set_value(vec._data[offset:offset + n].reshape(tuple(p.shape)))
            offset += n

from .layer.loss import HSigmoidLoss  # noqa: F401
