"""``mx.rtc`` — runtime custom-kernel modules (Pallas).

Reference: include/mxnet/rtc.h:39-61 CudaModule + python/mxnet/rtc.py —
users hand the framework raw CUDA source at runtime (compiled via NVRTC)
and launch it on engine-managed streams when the built-in kernels or the
compiler's fusion fall short.

TPU-native re-design: the escape hatch is **Pallas** — kernels are Python
functions over VMEM refs, compiled by Mosaic for the TPU's MXU/VPU and
tiling constraints (see /opt/skills/guides/pallas_guide.md).  A
``PallasModule`` plays CudaModule's role: it wraps kernel functions,
``get_kernel`` yields a launchable with a CudaKernel-ish ``launch`` API
(grid in place of grid/block dims), and ``register_op`` drops a kernel into
THE op registry so nd/sym/gluon and jit'd graphs can call it like any
built-in.  On non-TPU backends kernels run through the Pallas interpreter,
so the same code tests on CPU and compiles to Mosaic on TPU.

Built-in kernels living on this path: ops/pallas_kernels.py (fused row
softmax, fused scale-bias-relu) — the NMS-class "XLA fuses poorly" escape
valve SURVEY §7 calls for.
"""
from __future__ import annotations

__all__ = ["PallasModule", "PallasKernel", "register_op", "interpret_mode"]


def interpret_mode():
    """True when kernels must run in the Pallas interpreter: the default
    backend is not a TPU.  A backend that fails to start is an error,
    not a reason to interpret."""
    import jax
    return jax.default_backend() != "tpu"


class PallasKernel:
    """A launchable kernel (reference CudaKernel: rtc.py get_kernel
    result)."""

    def __init__(self, kernel_fn, out_shape, grid=None, in_specs=None,
                 out_specs=None, name=None, interpret=None):
        self._kernel = kernel_fn
        self._out_shape = out_shape
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs
        self.name = name or getattr(kernel_fn, "__name__", "pallas_kernel")
        self._interpret = interpret

    def _call(self, *arrays):
        import jax
        from jax.experimental import pallas as pl

        out_shape = self._out_shape
        if callable(out_shape):
            out_shape = out_shape(*arrays)
        interp = self._interpret if self._interpret is not None \
            else interpret_mode()
        kwargs = {}
        if self._grid is not None:
            grid = self._grid(*arrays) if callable(self._grid) else \
                self._grid
            kwargs["grid"] = grid
        if self._in_specs is not None:
            specs = self._in_specs
            kwargs["in_specs"] = specs(*arrays) if callable(specs) else specs
        if self._out_specs is not None:
            os_ = self._out_specs
            kwargs["out_specs"] = os_(*arrays) if callable(os_) else os_
        return pl.pallas_call(self._kernel, out_shape=out_shape,
                              interpret=interp, **kwargs)(*arrays)

    def launch(self, args, grid=None):
        """Run on NDArray/jax inputs; returns NDArray(s) (the CudaKernel
        launch analog — grid dims come from the BlockSpec/grid instead of
        CUDA's grid/block tuple)."""
        import jax.numpy as jnp
        from .ndarray.ndarray import NDArray, _wrap
        vals = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in args]
        if grid is not None:
            prev, self._grid = self._grid, grid
            try:
                out = self._call(*vals)
            finally:
                self._grid = prev
        else:
            out = self._call(*vals)
        if isinstance(out, (list, tuple)):
            return [_wrap(o) for o in out]
        return _wrap(out)

    def __call__(self, *arrays):
        """Raw-jax entry (composes with jit/grad of the surrounding
        program)."""
        return self._call(*arrays)


class PallasModule:
    """Holds named kernels (reference CudaModule holds compiled source)."""

    def __init__(self, *kernel_fns, **named_kernels):
        self._kernels = {}
        for fn in kernel_fns:
            self._kernels[fn.__name__] = fn
        self._kernels.update(named_kernels)

    def get_kernel(self, name, out_shape, grid=None, in_specs=None,
                   out_specs=None, interpret=None):
        if name not in self._kernels:
            raise KeyError("no kernel %r in module (have %s)"
                           % (name, sorted(self._kernels)))
        return PallasKernel(self._kernels[name], out_shape, grid=grid,
                            in_specs=in_specs, out_specs=out_specs,
                            name=name, interpret=interpret)


def register_op(op_name, kernel, out_shape, grid=None, in_specs=None,
                out_specs=None, differentiable=False, interpret=None):
    """Register a Pallas kernel as a first-class registry op so it is
    callable as mx.nd.<op_name> / mx.sym.<op_name> and inside jitted
    graphs (the capability MXLoadLib + RTC give the reference)."""
    from .ops.registry import register

    pk = PallasKernel(kernel, out_shape, grid=grid, in_specs=in_specs,
                      out_specs=out_specs, name=op_name,
                      interpret=interpret)

    def op_fn(*arrays, **_):
        import jax.numpy as jnp
        return pk._call(*[jnp.asarray(a) for a in arrays])

    register(op_name, differentiable=differentiable)(op_fn)
    return pk
