"""Device management: paddle.set_device / get_device parity over jax devices.

Reference parity: paddle/fluid/platform/place.h (Place variants) and
python/paddle/device/__init__.py. TPU-native: a "place" is a jax.Device; the
default device is jax's default; 'tpu:3' selects jax.devices('tpu')[3].
"""
import jax

_STATE = {'device': None}  # None means jax default


def _backend_of(name):
    name = name.lower()
    if name in ('gpu', 'cuda'):
        return 'gpu'
    if name in ('cpu',):
        return 'cpu'
    if name in ('tpu', 'xpu', 'npu', 'xla'):
        # reference XPU/NPU places map to the accelerator backend here
        return 'tpu'
    raise ValueError("unknown device %r" % name)


def resolve_device(device):
    """Any paddle device spec -> a jax.Device: 'tpu:3'/'cpu'/'cuda',
    a Place object, or a jax.Device passthrough.

    A backend asked for BY NAME ('tpu', 'tpu:0', 'gpu') that this host
    does not have raises — it never resolves to another backend's device.
    The compute Place facades (CUDAPlace/XPUPlace/NPUPlace) are what
    reference scripts write to mean "the accelerator, whatever it is",
    so they resolve to the default backend's devices."""
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, _Place):
        host = isinstance(device, (CPUPlace, CUDAPinnedPlace))
        devs = jax.devices('cpu') if host else jax.devices()
        return devs[device.device_id]
    name, _, idx_s = str(device).partition(':')
    backend = _backend_of(name)
    try:
        devs = jax.devices(backend)
    except RuntimeError as e:
        raise RuntimeError(
            'device %r was asked for by name but this process has no %r '
            'backend (default backend: %s)'
            % (device, backend, jax.default_backend())) from e
    return devs[int(idx_s) if idx_s else 0]  # out-of-range index raises


def process_holds_accelerator():
    """True once THIS process has initialised a non-CPU jax backend.

    A chip belongs to one process at a time: a process that has touched
    jax on a TPU host holds the chip, and a child that needs it then
    fails or hangs. Spawners (distributed.spawn, serving.fabric) ask this
    before starting a child that is not pinned to the CPU backend. Never
    initialises a backend itself (which is why it reads jax's private
    xla_bridge: every public query initialises one)."""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() and \
        jax.default_backend() != 'cpu'


def set_device(device):
    """Select the current device, e.g. 'tpu', 'cpu', 'tpu:0'."""
    dev = resolve_device(device)
    _STATE['device'] = dev
    return dev


def get_device():
    dev = _STATE['device']
    if dev is None:
        dev = jax.devices()[0]
    plat = dev.platform
    if plat == 'TPU':
        plat = 'tpu'
    return "%s:%d" % (plat, dev.id)


def current_jax_device():
    return _STATE['device']


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_tpu():
    return True


def device_count(backend=None):
    try:
        return len(jax.devices(backend) if backend else jax.devices())
    except RuntimeError:
        return 0


class _Place:
    """Place facades (reference platform/place.h variants): compute
    places resolve to the default backend's devices (the accelerator
    where there is one — see resolve_device); identities kept for API
    parity and isinstance checks."""

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return '%s(%d)' % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and \
            self.device_id == other.device_id


class CPUPlace(_Place):
    pass


class CUDAPlace(_Place):
    pass


class CUDAPinnedPlace(_Place):
    pass


class XPUPlace(_Place):
    pass


class NPUPlace(_Place):
    pass


def get_cudnn_version():
    return None  # no cuDNN on TPU (reference returns None when absent)


def is_compiled_with_rocm():
    return False
