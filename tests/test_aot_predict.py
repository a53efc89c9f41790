"""AOT no-Python deployment (VERDICT r4 item 7).

export_model → (a) portable jax.export StableHLO artifact round-trips and
matches the live net; (b) the TF-SavedModel form runs from a pure C++
binary (cpp-package/predict_aot_demo.cc) linked against the TensorFlow C
API with **no libpython**, matching the Python forward bit-for-bit-ish.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    from mxnet_tpu import aot, gluon, nd

    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    rs = np.random.RandomState(0)
    x = rs.randn(2, 8).astype(np.float32)
    net(nd.array(x))  # materialize params
    out_dir = str(tmp_path_factory.mktemp("aot"))
    manifest = aot.export_model(net, (2, 8), out_dir)
    expect = net(nd.array(x)).asnumpy()
    return out_dir, manifest, x, expect


def test_stablehlo_roundtrip(exported):
    from mxnet_tpu import aot

    out_dir, manifest, x, expect = exported
    assert os.path.exists(os.path.join(out_dir, "model.stablehlo"))
    got = aot.predict_stablehlo(out_dir, x)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    assert manifest["output_shape"] == [2, 4]


def test_c_runner_no_python(exported, tmp_path):
    out_dir, manifest, x, expect = exported
    tf_dir = None
    for p in sys.path:
        cand = Path(p) / "tensorflow"
        if (cand / "libtensorflow_cc.so.2").exists():
            tf_dir = cand
            break
    if tf_dir is None:
        pytest.skip("tensorflow C libraries not available")

    binary = tmp_path / "predict_aot_demo"
    compile_cmd = [
        "g++", "-std=c++17", "-O1",
        str(REPO / "cpp-package" / "predict_aot_demo.cc"),
        "-I", str(tf_dir / "include"),
        str(tf_dir / "libtensorflow_cc.so.2"),
        str(tf_dir / "libtensorflow_framework.so.2"),
        "-Wl,-rpath," + str(tf_dir),
        "-o", str(binary),
    ]
    out = subprocess.run(compile_cmd, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]

    # the whole point: the runner must not link libpython
    ldd = subprocess.run(["ldd", str(binary)], capture_output=True,
                         text=True, timeout=60)
    assert "libpython" not in ldd.stdout, ldd.stdout

    run = subprocess.run(
        [str(binary), out_dir, manifest["tf_input_tensor"],
         manifest["tf_output_tensor"], str(x.size)],
        input=x.tobytes(), capture_output=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:].decode(errors="replace")
    got = np.frombuffer(run.stdout, np.float32).reshape(expect.shape)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)
