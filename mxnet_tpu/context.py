"""Device contexts.

Parity: reference ``include/mxnet/base.h:142-247`` (Context) and
``python/mxnet/context.py``. TPU-first redesign: a Context names a JAX
device. ``tpu()`` is the native accelerator context; ``gpu()`` is kept as
an alias for accelerator so reference scripts run unmodified; ``cpu()``
maps to the host platform. ``cpu_pinned()`` maps to host memory used for
staging (PJRT manages pinned transfer buffers itself, so it is an alias
of cpu for placement purposes).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus"]


class Context:
    """A device context. Comparable/hashable; usable as a ``with`` scope."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    # -- JAX mapping --------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device. Always a LOCAL
        (process-addressable) device: under multi-process SPMD,
        jax.devices() lists the whole job's devices and rank r must not
        resolve cpu(0) to rank 0's device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = jax.local_devices(backend="cpu") if _has_platform("cpu") \
                else jax.local_devices()
            # every cpu(i) is the host (reference semantics): ids wrap
            return devs[self.device_id % len(devs)]
        # tpu and the gpu alias both mean "the accelerator". An id beyond
        # the chips present is an error, never chip (id mod n) or the
        # host: a fallback here would hide a missing chip from every
        # caller above
        devs = _accelerator_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "%r: this process has %d accelerator device(s) (jax "
                "platform %r); use mx.cpu() to run on the host"
                % (self, len(devs), jax.default_backend()))
        return devs[self.device_id]

    # -- dunder -------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    def __enter__(self):
        self._old = getattr(Context._default, "value", None)
        Context._default.value = self
        return self

    def __exit__(self, *exc):
        Context._default.value = self._old
        self._old = None


def _has_platform(name):
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    """Local non-CPU devices (empty on a CPU-only host)."""
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for the accelerator so reference code using mx.gpu() runs on TPU."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_gpus():
    """Number of accelerator chips visible (parity: mx.context.num_gpus)."""
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return len(devs)


def current_context():
    ctx = getattr(Context._default, "value", None)
    if ctx is None:
        # Default to the accelerator when present, else cpu — the TPU-native
        # twist on the reference default of cpu(0).
        ctx = tpu(0) if num_gpus() > 0 else cpu(0)
    return ctx
