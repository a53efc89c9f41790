"""End-to-end test of the C predict API + C++ frontend.

The reference's equivalent surface is include/mxnet/c_predict_api.h consumed
by example/image-classification/predict-cpp; here the whole loop runs:
export a checkpoint from Python, build the embedded-interpreter predict
library and the C++ demo with make, run the binary, and compare its output
numbers against the Python executor bit-for-bit (1e-4).
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import model

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("make") is None,
    reason="C++ toolchain unavailable")


def _mlp():
    data = mx.symbol.var("data")
    h = mx.symbol.FullyConnected(data, num_hidden=8, name="fc1")
    a = mx.symbol.Activation(h, act_type="relu", name="relu1")
    return mx.symbol.softmax(
        mx.symbol.FullyConnected(a, num_hidden=3, name="fc2"), name="sm")


@pytest.mark.slow
def test_cpp_predict_matches_python(tmp_path):
    out = _mlp()
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = out.infer_shape(data=(2, 5))
    args = {n: mx.nd.array(rs.randn(*s).astype(np.float32) * 0.3)
            for n, s in zip(out.list_arguments(), arg_shapes) if n != "data"}
    prefix = str(tmp_path / "mlp")
    model.save_checkpoint(prefix, 0, out, args, {})

    x = np.arange(10, dtype=np.float32).reshape(2, 5) * 0.01
    ex = out.simple_bind(mx.cpu(), data=(2, 5))
    ex.copy_params_from({**args, "data": mx.nd.array(x)})
    expected = ex.forward()[0].asnumpy()

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    build = subprocess.run(["make", "-C", str(REPO / "cpp-package"),
                            "predict_demo"], capture_output=True, text=True,
                           timeout=300, env=env)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run([str(REPO / "cpp-package" / "predict_demo"),
                          prefix, "2", "5"], capture_output=True, text=True,
                         timeout=300, env=env)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    assert lines[0].strip() == "output shape: 2 3"
    got = np.array([float(v) for v in lines[1:]], np.float32).reshape(2, 3)
    np.testing.assert_allclose(got, expected, atol=1e-4)
