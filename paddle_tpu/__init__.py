"""paddle_tpu: a TPU-native deep-learning framework with the capability
surface of PaddlePaddle 3.0, built on JAX/XLA/Pallas/pjit.

Layer map vs the reference (see SURVEY.md §1):
- L0-L3 (common/PHI/kernels/C++ API)  -> jax.numpy + XLA + Pallas kernel pack
- L4a eager autograd (GradNode graph) -> core.tensor dispatch + jax.vjp tape
- L4b/L6 PIR/CINN                     -> jaxpr/StableHLO + XLA (not rebuilt)
- L5 executor                          -> XLA async dispatch
- L7 distributed C++ runtime           -> jax.distributed + XLA collectives
- L8 python API                        -> this package
- L9 python distributed                -> paddle_tpu.distributed
- L10 inference                        -> paddle_tpu.inference (AOT/StableHLO)
- L11 CLI                              -> python -m paddle_tpu.distributed.launch
"""
from __future__ import annotations

__version__ = "0.1.0"

# Paddle dtype semantics: integer tensors default to int64, floats to float32
# (float64 allowed but opt-in). Requires x64 mode; weak-typed Python scalars
# keep float32 compute on the hot path, so this does not degrade TPU perf.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

# the persistent compilation cache: JAX_COMPILATION_CACHE_DIR when set,
# else a fixed .jax_cache under the checkout (core/backend.py)
from .core.backend import configure_compile_cache as _configure_cache

_configure_cache()

# -- core ---------------------------------------------------------------------
from .core.dtypes import (  # noqa: F401
    bool_ as bool, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    get_default_dtype, set_default_dtype)
from .core.tensor import (  # noqa: F401
    Tensor, no_grad, enable_grad, is_grad_enabled, set_grad_enabled)
from .core.flags import set_flags, get_flags  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401

# -- tensor ops (also patches Tensor methods) ---------------------------------
from .tensor import *  # noqa: F401,F403
from . import tensor  # noqa: F401

# -- autograd -----------------------------------------------------------------
from .autograd.backward import grad  # noqa: F401
from . import autograd  # noqa: F401

# -- device -------------------------------------------------------------------
from . import device  # noqa: F401
from .device import (  # noqa: F401
    CPUPlace, CUDAPlace, CUDAPinnedPlace, TPUPlace, XPUPlace, set_device,
    get_device, is_compiled_with_cuda, is_compiled_with_rocm,
    is_compiled_with_xpu)

# -- subsystems ---------------------------------------------------------------
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import utils  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from . import metric  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401
from . import inference  # noqa: F401
from . import quantization  # noqa: F401
from . import sparse  # noqa: F401
from . import geometric  # noqa: F401
from . import vision  # noqa: F401
from . import incubate  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import regularizer  # noqa: F401
from . import distribution  # noqa: F401
from .batch import batch  # noqa: F401

from .framework.io import save, load  # noqa: F401
from .framework import ParamAttr  # noqa: F401
from .jit.api import to_static  # noqa: F401

from .tensor.creation import to_tensor  # noqa: F401
from .tensor.logic import is_tensor  # noqa: F401


def is_compiled_with_tpu():
    from .device import is_compiled_with_tpu as _f
    return _f()


def disable_static():
    """Eager is the only authoring mode; kept for API parity."""
    return None


def enable_static():
    """Static graphs are expressed via jit.to_static; this flips a marker
    consulted by paddle_tpu.static helpers."""
    from . import static as _s
    _s._static_mode[0] = True


def in_dynamic_mode():
    from . import static as _s
    return not _s._static_mode[0]


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes=dtypes, input=input)


def __getattr__(name):
    # lazy top-level surfaces (reference: paddle.Model, paddle.callbacks,
    # paddle.DataParallel) without importing them at package import time
    if name == "Model":
        from .hapi import Model as _m
        return _m
    if name == "callbacks":
        from .hapi import callbacks as _c
        return _c
    if name == "hub":
        from .hapi import hub as _h
        return _h
    if name == "DataParallel":
        from .distributed.parallel import DataParallel as _dp
        return _dp
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


# reference: paddle.dtype is the datatype class usable in isinstance /
# constructor position; jax dtypes ARE numpy dtypes here
import numpy as _np_dtype_mod  # noqa: E402
dtype = _np_dtype_mod.dtype

from .framework import LazyGuard  # noqa: F401, E402


def shape(x):
    """reference: paddle.shape — runtime shape as an int32 tensor."""
    from .core.tensor import Tensor, to_value
    import numpy as np
    return Tensor(np.asarray(np.shape(to_value(x)), np.int32))


def tolist(x):
    """reference: paddle.tolist."""
    from .core.tensor import to_value
    import numpy as np
    return np.asarray(to_value(x)).tolist()


# -- round-3 long-tail parity -------------------------------------------------
from .framework.extras import (finfo, iinfo, set_printoptions,  # noqa: F401
                               to_dlpack, from_dlpack,
                               get_cuda_rng_state, set_cuda_rng_state,
                               disable_signal_handler, check_shape,
                               flops, create_tensor, create_parameter,
                               reverse)
from .tensor.math import reduce_as, broadcast_shape  # noqa: F401
from .tensor.search import top_p_sampling  # noqa: F401
from .nn.functional.common import pdist  # noqa: F401
from .signal import stft, istft  # noqa: F401

# math constants (reference: paddle exposes numpy's scalars + newaxis)
import numpy as _np  # noqa: E402
pi = _np.pi
e = _np.e
inf = _np.inf
nan = _np.nan
newaxis = None
# dtype sentinels with no dense-kernel backing (reference
# framework/dtype.py:67 maps them to VarDesc.VarType entries)
pstring = "pstring"
raw = "raw"


def _patch_round3_methods():
    # only functions living OUTSIDE the tensor/ package need explicit
    # method attachment (tensor/__init__._patch auto-installs the rest);
    # is_tensor is in that patcher's _SKIP but the reference DOES expose
    # it as a method (tensor_method_func), so attach it here on purpose.
    from .core.tensor import Tensor as _T
    from .framework import extras as _ex
    from . import signal as _sig
    from .tensor.logic import is_tensor as _is_tensor
    for name, fn in (("resize_", _ex.resize_), ("reverse", _ex.reverse),
                     ("stft", _sig.stft), ("istft", _sig.istft),
                     ("is_tensor", _is_tensor)):
        if not hasattr(_T, name):
            setattr(_T, name, fn)


_patch_round3_methods()
del _patch_round3_methods
