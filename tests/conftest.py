"""Test config: run the suite on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (the analogue of the
reference's `--launcher local` single-host distributed tests, SURVEY.md §4.2).
Must set env before jax initializes."""
import os

# MXNET_TEST_DEVICE=tpu opts OUT of the CPU forcing so the TPU-context
# rerun suite (test_operator_tpu.py) can execute on the real chip — the
# reference's test_operator_gpu.py pattern needs the accelerator visible
_WANT_TPU = os.environ.get("MXNET_TEST_DEVICE", "").lower() == "tpu"

if not _WANT_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"  # force: the suite never asks for a chip
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _flightrec_in_tmp(tmp_path):
    """The flight recorder dumps on death paths some tests deliberately
    exercise (decode worker catch-all, SIGTERM); default its path into
    the test's tmp dir so suites never litter the repo root. Tests that
    assert on the dump set MXNET_FLIGHTREC_PATH explicitly."""
    prev = os.environ.get("MXNET_FLIGHTREC_PATH")
    os.environ["MXNET_FLIGHTREC_PATH"] = str(tmp_path / "flightrec.json")
    yield
    if prev is None:
        os.environ.pop("MXNET_FLIGHTREC_PATH", None)
    else:
        os.environ["MXNET_FLIGHTREC_PATH"] = prev


@pytest.fixture(autouse=True)
def _seeded():
    """Seeded determinism per test (reference tests/python/unittest/common.py
    @with_seed): failures are reproducible."""
    import mxnet_tpu as mx

    seed = np.random.randint(0, 2**31 - 1)
    mx.random.seed(seed)
    yield
    # seed printed by pytest on failure via -l; keep quiet otherwise


def subprocess_env(**extra):
    """Env for driving a repo script in a subprocess: CPU-only jax. The
    ONE copy of this recipe — example and driver-artifact tests import it
    from here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env
