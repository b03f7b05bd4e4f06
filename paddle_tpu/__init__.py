"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit.

Import as `import paddle_tpu as paddle` — the public surface mirrors
python/paddle/__init__.py of the reference (~v2.1).
"""

__version__ = '0.1.0'

# framework core
from .framework.core import Tensor, Parameter, to_tensor  # noqa: F401
from .framework.core import no_grad_guard as no_grad  # noqa: F401
from .framework.core import enable_grad_guard as enable_grad  # noqa: F401
from .framework.core import is_grad_enabled, set_grad_enabled  # noqa: F401
from .framework.dtype import set_default_dtype, get_default_dtype  # noqa: F401
from .framework.device import (set_device, get_device, device_count,  # noqa: F401
                               is_compiled_with_cuda, is_compiled_with_xpu,
                               is_compiled_with_npu, is_compiled_with_rocm,
                               get_cudnn_version, CPUPlace, CUDAPlace,
                               CUDAPinnedPlace, XPUPlace, NPUPlace)
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .framework.random import (get_rng_state as get_cuda_rng_state,  # noqa: F401
                               set_rng_state as set_cuda_rng_state)

# dtype singletons (paddle.float32 etc.)
float16 = 'float16'
bfloat16 = 'bfloat16'
float32 = 'float32'
float64 = 'float64'
int8 = 'int8'
int16 = 'int16'
int32 = 'int32'
int64 = 'int64'
uint8 = 'uint8'
bool = 'bool'
complex64 = 'complex64'
complex128 = 'complex128'

# the wide tensor function surface
from .tensor import *  # noqa: F401,F403
from .tensor import einsum  # noqa: F401
from .tensor.logic import is_tensor  # noqa: F401

# subpackages
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import autograd  # noqa: F401
from . import metric  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import vision  # noqa: F401
from . import text  # noqa: F401
from . import distribution  # noqa: F401
from . import regularizer  # noqa: F401
from . import distributed  # noqa: F401
from . import incubate  # noqa: F401
from . import hub  # noqa: F401
from . import onnx  # noqa: F401
from . import profiler  # noqa: F401
from . import slim  # noqa: F401
from . import utils  # noqa: F401
from . import dataset  # noqa: F401
from . import sysconfig  # noqa: F401
from . import monitor  # noqa: F401
# the process's one compile listener (monitor/perf/watchdog.py), before
# anything builds a program
monitor.perf.watchdog.install()
from . import data  # noqa: F401

from .nn.layer.layers import ParamAttr  # noqa: F401
from .framework.io_save import save, load  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi import summary, flops  # noqa: F401
from .hapi import callbacks  # noqa: F401  (paddle.callbacks namespace)
from .framework import device  # noqa: F401  (paddle.device module)
# make `import paddle_tpu.callbacks` / `.device` statement forms work too
import sys as _sys
_sys.modules[__name__ + '.callbacks'] = callbacks
_sys.modules[__name__ + '.device'] = device
from .batch import batch  # noqa: F401
from .autograd import grad  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401

# paddle.disable_static / enable_static shims: we are always "dygraph" at the
# API level; static mode is jit-compilation under the hood (see jit/static).
_static_mode = [False]


def enable_static():
    _static_mode[0] = True


def disable_static(place=None):
    _static_mode[0] = False


def in_dynamic_mode():
    return not _static_mode[0]


def get_flags(flags):
    from .framework import flags as F
    return F.get_flags(flags)


def set_flags(flags):
    from .framework import flags as F
    F.set_flags(flags)


def set_printoptions(**kwargs):
    import numpy as np
    np.set_printoptions(**{k: v for k, v in kwargs.items()
                           if k in ('precision', 'threshold', 'edgeitems',
                                    'linewidth')})


class version:
    full_version = __version__
    major, minor, patch = 0, 1, 0
    rc = 0
    istaged = True
    commit = 'tpu-native'

    @staticmethod
    def show():
        print('paddle_tpu', version.full_version)

# eager/dygraph mode facades: this framework is always-eager with jit
# compilation (SURVEY §7.1) — the reference's mode switch is a constant
VarBase = Tensor


def in_dygraph_mode():
    return True


def enable_dygraph(place=None):
    pass


def disable_dygraph():
    pass


enable_imperative = enable_dygraph
disable_imperative = disable_dygraph


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from .static import create_parameter as _cp
    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


def crop_tensor(x, shape=None, offsets=None, name=None):
    from .tensor.manipulation import crop
    return crop(x, shape=shape, offsets=offsets)


def monkey_patch_variable():  # no-op: Tensor methods are always patched
    pass


def monkey_patch_math_varbase():
    pass


from .framework.dtype import DTypeStr as dtype  # noqa: F401,E402
