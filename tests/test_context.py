"""Context -> jax.Device resolution: an accelerator context names an
accelerator or raises; a device id is never wrapped (PR 22). The suite runs
on the CPU test mesh (8 virtual devices, no accelerator), so ``mx.tpu()``
resolving to anything at all here would be the silent host fallback."""
import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import context as ctx_mod
from mxnet_tpu.base import MXNetError


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_without_accelerator_raises(make):
    assert ctx_mod.num_tpus() == 0          # the premise: CPU test mesh
    with pytest.raises(MXNetError, match="no such device"):
        make(0).jax_device()
    with pytest.raises(MXNetError, match="no such device"):
        mx.nd.ones((2,), ctx=make(0))


def test_device_id_out_of_range_raises_instead_of_wrapping():
    n = len(jax.devices("cpu"))
    assert mx.cpu(n - 1).jax_device() == jax.devices("cpu")[n - 1]
    for bad in (n, n + 3, -1):
        with pytest.raises(MXNetError, match="no such device"):
            mx.cpu(bad).jax_device()


def test_accelerator_context_resolves_when_one_exists(monkeypatch):
    # steer the test, not the program: pretend the process has two
    # accelerators by filling the module's device cache
    fake = jax.devices("cpu")[:2]
    monkeypatch.setattr(ctx_mod, "_ACCEL_CACHE", fake)
    assert mx.tpu(1).jax_device() == fake[1]
    assert mx.gpu(0).jax_device() == fake[0]
    with pytest.raises(MXNetError, match="no such device"):
        mx.tpu(3).jax_device()              # chip 3 of 2 is not chip 1


@pytest.mark.parametrize("kind", ["cpu_pinned", "cpu_shared"])
def test_host_memory_contexts_resolve_to_cpu(kind):
    assert mx.Context(kind, 0).jax_device() == jax.devices("cpu")[0]
