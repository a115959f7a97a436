"""Custom C++ operator extension.

TPU-native redesign of the reference's custom-op machinery
(paddle/fluid/framework/custom_operator.cc + paddle/phi/api/ext/op_meta_info.h
and python/paddle/utils/cpp_extension/): users write a C++ kernel, `load()`
compiles it with the host toolchain and registers it as a paddle_tpu op.

Execution model on TPU: the compiled C++ function runs on the HOST, bridged
into XLA programs via ``jax.pure_callback`` (the analog of the reference's
CPU-kernel fallback for custom ops — custom_device_op_list.cc). Inside jit
the callback is staged as a host call; eagerly it is called directly. An
optional ``vjp`` C++ (or Python) function makes the op differentiable.

C ABI contract (simpler than the reference's 736-line device_ext.h — one
function per op):

    // all buffers are dense contiguous float32/int32...; shapes passed
    // explicitly; out buffers preallocated by the caller
    extern "C" void <name>(const void** ins, const int64_t* in_shapes,
                           const int32_t* in_ranks, int n_in,
                           void** outs);

Example::

    src = '''
    extern "C" void my_relu(const void** ins, const long long* shp,
                            const int* rk, int n_in, void** outs) {
        const float* x = (const float*) ins[0];
        float* y = (float*) outs[0];
        long long n = 1;
        for (int d = 0; d < rk[0]; ++d) n *= shp[d];
        for (long long i = 0; i < n; ++i) y[i] = x[i] > 0 ? x[i] : 0;
    }
    '''
    op = load(name="my_relu", sources=[src_file],
              out_shape_fn=lambda x: x)          # shape inference
    y = op(paddle.to_tensor(arr))
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Callable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core.backend import cache_dir
from ..core.tensor import Tensor, dispatch, to_value

__all__ = ["load", "load_inline", "CustomOp", "get_build_directory"]

_build_dir = [os.path.join(cache_dir(), "extensions")]


def get_build_directory() -> str:
    os.makedirs(_build_dir[0], exist_ok=True)
    return _build_dir[0]


def _compile(sources: Sequence[str], name: str,
             extra_cflags: Sequence[str] = ()) -> str:
    """g++ -shared the sources; content-hashed cache in the build dir."""
    h = hashlib.sha1()
    srcs = []
    for s in sources:
        if os.path.exists(s):
            code = open(s).read()
            srcs.append(s)
        else:
            code = s  # inline source string
            f = os.path.join(get_build_directory(),
                             f"{name}_{len(srcs)}.cc")
            with open(f, "w") as fh:
                fh.write(code)
            srcs.append(f)
        h.update(code.encode())
    so = os.path.join(get_build_directory(),
                      f"{name}_{h.hexdigest()[:12]}.so")
    if not os.path.exists(so):
        cmd = ["g++", "-shared", "-fPIC", "-O2", "-o", so,
               *extra_cflags, *srcs]
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"custom op build failed:\n{' '.join(cmd)}\n{r.stderr}")
    return so


class CustomOp:
    """A loaded custom operator; callable on Tensors, jit-safe."""

    def __init__(self, name: str, so_path: str,
                 out_shape_fn: Callable, out_dtype_fn: Optional[Callable],
                 num_outputs: int, vjp: Optional[Callable]):
        self.name = name
        self.so_path = so_path
        self._lib = ctypes.CDLL(so_path)
        self._fn = getattr(self._lib, name)
        self._fn.restype = None
        self._out_shape_fn = out_shape_fn
        self._out_dtype_fn = out_dtype_fn
        self._num_outputs = num_outputs
        self._vjp = vjp

    # -- host execution ------------------------------------------------------
    def _host_call(self, *arrays):
        arrays = [np.ascontiguousarray(a) for a in arrays]
        shapes = np.concatenate([np.asarray(a.shape, np.int64) if a.ndim
                                 else np.zeros(0, np.int64)
                                 for a in arrays]) if arrays else \
            np.zeros(0, np.int64)
        ranks = np.asarray([a.ndim for a in arrays], np.int32)
        out_shapes = self._resolve_out_shapes(arrays)
        out_dtypes = self._resolve_out_dtypes(arrays)
        outs = [np.empty(s, d) for s, d in zip(out_shapes, out_dtypes)]
        in_ptrs = (ctypes.c_void_p * len(arrays))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in arrays])
        out_ptrs = (ctypes.c_void_p * len(outs))(
            *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
        self._fn(in_ptrs,
                 shapes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                 ranks.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                 ctypes.c_int(len(arrays)), out_ptrs)
        return outs[0] if self._num_outputs == 1 else tuple(outs)

    def _resolve_out_shapes(self, arrays):
        s = self._out_shape_fn(*[tuple(a.shape) for a in arrays])
        if self._num_outputs == 1 and not (
                s and isinstance(s[0], (tuple, list))):
            return [tuple(s)]
        return [tuple(x) for x in s]

    def _resolve_out_dtypes(self, arrays):
        if self._out_dtype_fn is None:
            return [arrays[0].dtype] * self._num_outputs
        d = self._out_dtype_fn(*[a.dtype for a in arrays])
        if self._num_outputs == 1 and not isinstance(d, (tuple, list)):
            return [d]
        return list(d)

    # -- jax bridge ----------------------------------------------------------
    def _jax_fn(self, *vals):
        out_shapes = self._resolve_out_shapes(vals)
        out_dtypes = self._resolve_out_dtypes(
            [np.empty(0, v.dtype) for v in vals])
        result_shape = [jax.ShapeDtypeStruct(s, d)
                        for s, d in zip(out_shapes, out_dtypes)]
        if self._num_outputs == 1:
            result_shape = result_shape[0]
        out = jax.pure_callback(self._host_call, result_shape, *vals,
                                vmap_method="sequential")
        return out

    def __call__(self, *tensors):
        args = tuple(t if isinstance(t, Tensor) else Tensor(t)
                     for t in tensors)
        fn = self._jax_fn
        if self._vjp is not None:
            fn = self._diff_fn()
        return dispatch(fn, args, name=self.name,
                        multi_output=self._num_outputs > 1)

    def _diff_fn(self):
        if getattr(self, "_diff_cached", None) is None:
            op = self

            @jax.custom_vjp
            def f(*vals):
                return op._jax_fn(*vals)

            def fwd(*vals):
                return op._jax_fn(*vals), vals

            def bwd(res, g):
                grads = op._vjp(res, g)
                return tuple(grads)

            f.defvjp(fwd, bwd)
            self._diff_cached = f
        return self._diff_cached


def load(name: str, sources: Sequence[str], out_shape_fn: Callable,
         out_dtype_fn: Optional[Callable] = None, num_outputs: int = 1,
         vjp: Optional[Callable] = None,
         extra_cflags: Sequence[str] = ()) -> CustomOp:
    """Compile + load a custom C++ op (reference:
    python/paddle/utils/cpp_extension/extension_utils.py load)."""
    so = _compile(sources, name, extra_cflags)
    return CustomOp(name, so, out_shape_fn, out_dtype_fn, num_outputs, vjp)


def load_inline(name: str, cpp_source: str, out_shape_fn: Callable,
                **kwargs) -> CustomOp:
    """Compile a C++ source string directly."""
    return load(name, [cpp_source], out_shape_fn, **kwargs)
