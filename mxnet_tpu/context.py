"""Device contexts: ``mx.cpu()``, ``mx.gpu()``, ``mx.tpu()``.

Re-design of the reference's ``python/mxnet/context.py`` (Context,
default-context thread-local) with TPU as a first-class device. A Context
maps onto a concrete ``jax.Device``; ``gpu()`` is accepted for source
compatibility and resolves to the platform accelerator (TPU here).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """Execution device. ``Context('tpu', 0)`` designates TPU chip 0.

    Mirrors the user surface of reference ``python/mxnet/context.py:Context``
    (devtype2str/devstr2type, ``with ctx:`` scoping, equality/hash) while the
    backing runtime is a jax.Device rather than an mshadow stream.
    """

    # dev_type codes kept for .params compat (reference context.py devtype2str)
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = int(device_id)
        self._old_ctx: Optional[Context] = None

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

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

    __str__ = __repr__

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        if not hasattr(self._default_ctx, "value"):
            self._default_ctx.value = Context("cpu", 0)
        self._old_ctx = self._default_ctx.value
        self._default_ctx.value = self
        return self

    def __exit__(self, *args):
        self._default_ctx.value = self._old_ctx

    # -- jax mapping -------------------------------------------------------
    def jax_device(self) -> "jax.Device":
        """Resolve this context to a concrete jax.Device."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _devices_by_platform("cpu")
        else:
            # an accelerator context names an accelerator or nothing: a
            # host fallback would let ``mx.tpu(0)`` be a CPU array on a
            # machine whose chip failed to initialise
            devs = _accelerator_devices()
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s: no such device — jax reports %d %s device(s) in this "
                "process (backend %r)"
                % (self, len(devs),
                   "cpu" if self.device_type.startswith("cpu")
                   else "accelerator", jax.default_backend()))
        return devs[self.device_id]

    def empty_cache(self):
        """Parity with reference Context.empty_cache; XLA manages HBM pools."""
        # jax manages its own HBM allocator; nothing to do, kept for API parity.
        return


def _devices_by_platform(platform: str):
    """Addressable devices of a platform. Under ``jax.distributed`` a context
    names a device of THIS process (the reference's ``mx.gpu(i)`` is likewise
    worker-local); other processes' devices are only reachable through
    collectives, so they never back an NDArray."""
    try:
        devs = jax.devices(platform)
    except RuntimeError:
        return []
    local = [d for d in devs if d.process_index == jax.process_index()]
    return local or devs


_ACCEL_CACHE = None


def _accelerator_devices():
    """Process-local non-CPU jax devices (TPU first), cached."""
    global _ACCEL_CACHE
    if _ACCEL_CACHE is None:
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        _ACCEL_CACHE = devs
    return _ACCEL_CACHE


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accepted for source compatibility with reference scripts; resolves to
    the platform accelerator (TPU on this stack)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
