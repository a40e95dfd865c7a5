"""User-defined runtime kernels via Pallas.

Reference: `python/mxnet/rtc.py` / `include/mxnet/rtc.h:39` — `CudaModule`
compiles CUDA source with NVRTC at runtime and hands back launchable
kernels.  The TPU-native equivalent of "write your own kernel" is Pallas:
a `PallasModule` wraps one or more Python kernel functions (written against
`jax.experimental.pallas`), and `get_kernel(...).launch(args, grid)` mirrors
the reference's launch API.  On non-TPU backends kernels run in Pallas
interpret mode, so user kernels are testable on the CPU mesh.

Example::

    import mxnet_tpu as mx
    from jax.experimental import pallas as pl

    def axpy_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    mod = mx.rtc.PallasModule(axpy_kernel)
    k = mod.get_kernel("axpy_kernel", out_like=0)   # output shaped like arg 0
    z = k.launch((x, y))
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .context import on_tpu
from .ops.invoke import invoke

__all__ = ["PallasModule", "PallasKernel"]


def _interpret_default():
    # interpret mode everywhere but real TPU hardware
    return not on_tpu()


class PallasKernel:
    """A launchable kernel (reference analogue: `CudaKernel`,
    `python/mxnet/rtc.py`)."""

    def __init__(self, fun, name, out_like=None, out_shape=None,
                 out_dtype=None, interpret=None):
        self._fun = fun
        self.name = name
        self._out_like = out_like
        self._out_shape = out_shape
        self._out_dtype = out_dtype
        self._interpret = interpret

    def _resolve_out(self, datas):
        if self._out_like is not None:
            ref = datas[self._out_like]
            return jax.ShapeDtypeStruct(ref.shape, ref.dtype)
        shape = self._out_shape
        if shape is None:
            raise ValueError("specify out_like or out_shape for the kernel")
        dtype = self._out_dtype or jnp.float32
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def launch(self, args, grid=None, **pallas_kwargs):
        """Run the kernel over NDArray args; returns a new NDArray.

        `grid`/`in_specs`/`out_specs` etc. pass through to
        `pl.pallas_call`.  (The reference launch takes CUDA grid/block dims;
        the Pallas grid plays that role.)
        """
        from jax.experimental import pallas as pl

        interpret = self._interpret
        if interpret is None:
            interpret = _interpret_default()

        if grid is not None:
            pallas_kwargs["grid"] = grid

        def f(*datas):
            call = pl.pallas_call(
                self._fun,
                out_shape=self._resolve_out(datas),
                interpret=interpret,
                **pallas_kwargs)
            return call(*datas)
        return invoke(f, tuple(args), name=f"rtc.{self.name}")

    __call__ = launch


class PallasModule:
    """A bundle of user kernels (reference analogue: `CudaModule`)."""

    def __init__(self, *kernels, exports=None):
        self._kernels = {k.__name__: k for k in kernels}
        self.exports = list(exports or self._kernels)

    def get_kernel(self, name, out_like=None, out_shape=None, out_dtype=None,
                   interpret=None):
        if name not in self._kernels:
            raise ValueError(
                f"unknown kernel {name!r}; available: {sorted(self._kernels)}")
        return PallasKernel(self._kernels[name], name, out_like=out_like,
                            out_shape=out_shape, out_dtype=out_dtype,
                            interpret=interpret)
