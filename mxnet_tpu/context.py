"""Device contexts.

Reference design: ``include/mxnet/base.h`` Context {devtype, devid} with
``mx.cpu()/mx.gpu(i)`` constructors threaded through every NDArray and
executor.  TPU-native re-design: a Context is a *view onto a jax.Device*.
``mx.tpu(i)`` is the native accelerator context; ``mx.gpu(i)`` is kept as an
alias for accelerator i so reference training scripts (``ctx=mx.gpu(0)``) run
unmodified.  ``mx.cpu()`` maps to the host platform.

Unlike the reference there is no per-context stream/thread pool: XLA owns
scheduling on-device, and jax's async dispatch replaces the ThreadedEngine
(reference src/engine/threaded_engine_perdevice.cc:47-120).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "accelerator_is_real", "require_accelerator"]


class Context:
    """A device context. Compares by (device_type, device_id)."""

    # devtype codes kept for serialization parity (include/mxnet/base.h)
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devstr2type:
            raise ValueError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = device_id

    # -- jax bridge ---------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _platform_devices("cpu")
            if devs is None:
                # no cpu platform registered (rare) — fall back to default
                return jax.devices()[0]
            return devs[self.device_id % len(devs)]
        # 'gpu' is an accelerator alias: scripts written for mx.gpu(i) get chip i
        devs = _accelerator_devices()
        if not devs:
            raise MXNetErrorNoDevice(
                "no accelerator devices visible for ctx %r" % (self,)
            )
        if self.device_id >= len(devs):
            raise MXNetErrorNoDevice(
                "ctx %r out of range: %d accelerator device(s)" % (self, len(devs))
            )
        return devs[self.device_id]

    # -- context manager ----------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT


class MXNetErrorNoDevice(RuntimeError):
    pass


def _platform_devices(platform: str):
    try:
        return jax.devices(platform)
    except RuntimeError:
        return None


_ACCEL_CACHE: Optional[list] = None


def _accelerator_devices():
    """All non-cpu devices.  On a CPU-only backend (the virtual-device
    test mesh) the cpu devices stand in, so mx.tpu()/mx.gpu() still
    resolve — :func:`accelerator_is_real` tells the two apart."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        _ACCEL_CACHE = devs if devs else list(jax.devices())
    return _ACCEL_CACHE


def accelerator_is_real() -> bool:
    """True when ``mx.tpu(i)`` names an accelerator chip, False when cpu
    devices are standing in for one.  Answered from the device platform,
    never from the context's name."""
    return _accelerator_devices()[0].platform != "cpu"


def require_accelerator(what: str):
    """Fail unless the accelerator is real.  Anything that reports a
    time, a rate or a utilization calls this first: a number taken on
    stand-in cpu devices must never pass for a device number."""
    if not accelerator_is_real():
        raise MXNetErrorNoDevice(
            "%s needs an accelerator, but this backend has only %s "
            "device(s); mx.tpu(0) is a cpu device standing in"
            % (what, jax.default_backend()))


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context; alias of tpu() for reference-script parity."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


_DEFAULT = Context("tpu", 0)


def current_context() -> Context:
    return Context.default_ctx()


def ctx_from_device(dev: jax.Device) -> Context:
    if dev.platform == "cpu" and _accelerator_devices()[0].platform != "cpu":
        return Context("cpu", dev.id)
    # accelerator (or cpu-only world where cpu devices *are* the accelerators)
    accels = _accelerator_devices()
    try:
        return Context("tpu", accels.index(dev))
    except ValueError:
        return Context("cpu", dev.id)
