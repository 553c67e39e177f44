"""Device places and low-level shims.

Parity: reference paddle/fluid/platform/place.h (CPUPlace/CUDAPlace) and the
pybind `core` module (python/paddle/fluid/__init__.py imports `core`).
TPU-first: `TPUPlace` replaces CUDAPlace as the accelerator place; both map to
a jax.Device. A Place only selects which jax device backs Scope arrays and
where jitted programs run — kernels themselves are XLA-compiled, not per-op.
"""
import numpy as np

import jax


class DeviceUnavailableError(RuntimeError):
    """An explicitly requested Place names a device this process does not
    have. Raised instead of substituting another device: a TPUPlace that
    quietly ran on the CPU (or on chip 0 for chip 3) would let every
    platform-keyed decision below it — kernel choice, interpret mode,
    benchmark labels — disagree with where the arrays actually live."""


class Place(object):
    _platform = None

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self._platform]
        if not 0 <= self.device_id < len(devs):
            raise DeviceUnavailableError(
                '%r asks for %s device %d but this process has %d; '
                'jax.devices() returned %r'
                % (self, self._platform, self.device_id, len(devs),
                   jax.devices()))
        return devs[self.device_id]


class CPUPlace(Place):
    _platform = 'cpu'

    def __init__(self):
        super(CPUPlace, self).__init__(0)

    def jax_device(self):
        # the host backend exists beside any accelerator backend, which
        # jax.devices() (default backend only) would not list
        return jax.devices('cpu')[0]


class TPUPlace(Place):
    """The accelerator place (reference: platform::CUDAPlace)."""
    _platform = 'tpu'


# Alias so code written against the reference's GPU API keeps working.
CUDAPlace = TPUPlace
CUDAPinnedPlace = CPUPlace


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return get_tpu_device_count() > 0


def get_tpu_device_count():
    return len([d for d in jax.devices() if d.platform == 'tpu'])


def default_place():
    """The place an entry point gets when the user passes none: the first
    TPU when this process has one, else the host. Only this default looks
    at what is present; an explicit Place is a demand (jax_device)."""
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace()


# Fluid VarDesc dtype enum compatibility (reference: framework.proto VarType).
class VarDesc(object):
    class VarType(object):
        BOOL = 0
        INT16 = 1
        INT32 = 2
        INT64 = 3
        FP16 = 4
        FP32 = 5
        FP64 = 6
        LOD_TENSOR = 7
        SELECTED_ROWS = 8
        FEED_MINIBATCH = 9
        FETCH_LIST = 10
        STEP_SCOPES = 11
        LOD_RANK_TABLE = 12
        LOD_TENSOR_ARRAY = 13
        PLACE_LIST = 14
        READER = 15
        UINT8 = 20
        BF16 = 22
        RAW = 17


_DTYPE_ENUM_TO_NP = {
    VarDesc.VarType.BOOL: np.bool_,
    VarDesc.VarType.INT16: np.int16,
    VarDesc.VarType.INT32: np.int32,
    VarDesc.VarType.INT64: np.int64,
    VarDesc.VarType.FP16: np.float16,
    VarDesc.VarType.FP32: np.float32,
    VarDesc.VarType.FP64: np.float64,
    VarDesc.VarType.UINT8: np.uint8,
}


def convert_dtype(dtype):
    """Normalize str / np.dtype / VarType enum to a canonical dtype string."""
    import jax.numpy as jnp
    if isinstance(dtype, int):
        dtype = _DTYPE_ENUM_TO_NP[dtype]
    if dtype == 'bfloat16' or dtype is jnp.bfloat16:
        return 'bfloat16'
    return np.dtype(dtype).name


def __getattr__(name):
    # Scope lives in executor.py (it owns the var-store design), but the
    # reference exposes it as `fluid.core.Scope` (pybind core module) and
    # reference book code instantiates it through that path — lazy alias
    # to avoid a core <-> executor import cycle.
    if name == 'Scope':
        from .executor import Scope
        return Scope
    raise AttributeError('module %r has no attribute %r'
                         % (__name__, name))
