"""Tests for tools/ (im2rec, diagnose, flakiness_checker normalization).

The reference ships its dataset packer and launch utilities in tools/
(tools/im2rec.py, tools/launch.py, tools/diagnose.py); launch.py is covered
by test_dist_launch.py.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))


def _write_images(root):
    from mxnet_tpu import image

    for cls in ("cats", "dogs"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            img = (np.random.RandomState(i).rand(24, 30, 3) * 255).astype(np.uint8)
            (root / cls / ("img%d.png" % i)).write_bytes(image.imencode(img, ".png"))


def test_im2rec_list_and_pack(tmp_path):
    import im2rec

    from mxnet_tpu import recordio

    _write_images(tmp_path / "imgs")
    prefix = str(tmp_path / "data")
    assert im2rec.main(["--list", "--recursive", prefix, str(tmp_path / "imgs")]) == 0
    lst = Path(prefix + ".lst").read_text().strip().splitlines()
    assert len(lst) == 6
    labels = {line.split("\t")[1] for line in lst}
    assert labels == {"0.000000", "1.000000"}  # two classes

    assert im2rec.main(["--resize", "16", "--encoding", ".png",
                        prefix, str(tmp_path / "imgs")]) == 0
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert len(r.keys) == 6
    seen_labels = set()
    for k in r.keys:
        h, img = recordio.unpack_img(r.read_idx(k))
        assert min(img.shape[:2]) == 16
        seen_labels.add(float(h.label))
    assert seen_labels == {0.0, 1.0}
    r.close()


def test_im2rec_pass_through(tmp_path):
    import im2rec

    from mxnet_tpu import recordio

    _write_images(tmp_path / "imgs")
    prefix = str(tmp_path / "data")
    im2rec.main(["--list", "--recursive", prefix, str(tmp_path / "imgs")])
    im2rec.main(["--pass-through", prefix, str(tmp_path / "imgs")])
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    h, payload = recordio.unpack(r.read_idx(r.keys[0]))
    assert payload[:8].startswith(b"\x89PNG")  # raw bytes, not re-encoded
    r.close()


def test_flakiness_checker_target_normalization():
    import flakiness_checker

    assert flakiness_checker.normalize_target(
        "tests/test_operator.py::test_x") == "tests/test_operator.py::test_x"
    assert flakiness_checker.normalize_target(
        "test_operator.test_x") == os.path.join("tests", "test_operator.py") + "::test_x"


def test_diagnose_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "tools" / "diagnose.py")],
                         capture_output=True, text=True, timeout=180,
                         env=env)
    assert out.returncode == 0
    assert "mxnet_tpu Info" in out.stdout and "JAX Info" in out.stdout


def test_im2rec_multithread(tmp_path):
    """--num-thread packs via the host engine with serialized writes."""
    import im2rec

    from mxnet_tpu import recordio

    _write_images(tmp_path / "imgs")
    prefix = str(tmp_path / "data")
    im2rec.main(["--list", "--recursive", prefix, str(tmp_path / "imgs")])
    assert im2rec.main(["--resize", "16", "--encoding", ".png",
                        "--num-thread", "4", prefix,
                        str(tmp_path / "imgs")]) == 0
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert sorted(r.keys) == list(range(6))
    for k in r.keys:
        h, img = recordio.unpack_img(r.read_idx(k))
        assert min(img.shape[:2]) == 16
    r.close()


def test_kernel_time_rehearses_off_the_chip(tmp_path):
    """``tools/kernel_time.py --interpret``: the control flow at tiny shapes
    (this tree's kernel beside a second copy of its own source, agreeing bit
    for bit, one JSON line a run and no time in it); without ``--interpret``
    and without a TPU it refuses to measure."""
    import json

    tool = str(REPO / "tools" / "kernel_time.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out_file = tmp_path / "kernel_time.jsonl"
    out = subprocess.run(
        [sys.executable, tool, "--interpret", "--cell", "trinity", "--fill",
         "empty15", "--out", str(out_file), "--other",
         str(REPO / "mxnet_tpu" / "ops" / "pallas_kernels.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out_file.read_text().splitlines()]
    assert [ln["side"] for ln in lines] == ["other", "this", "this", "other"]
    assert all(ln["launches"] == 5 and ln["live_columns"] > 0
               and "us_per_launch" not in ln for ln in lines)
    assert [ln["bitwise_equal"] for ln in lines[1:]] == [True] * 3
    refused = subprocess.run([sys.executable, tool, "--cell", "opt"],
                             capture_output=True, text=True, timeout=300,
                             env=env)
    assert refused.returncode != 0 and "no TPU" in refused.stderr


def test_kernel_time_rehearses_the_band_kernel_off_the_chip(tmp_path):
    """``--kernel band --interpret``: a prefill's chain of launches at tiny
    shapes over one rung and three real lengths, this tree's kernel beside a
    second copy of its own source (gap 0.0) and beside itself at other block
    sizes (the same mathematics in another order of sums), no time in a
    line."""
    import json

    tool = str(REPO / "tools" / "kernel_time.py")
    out_file = tmp_path / "kernel_time.jsonl"
    out = subprocess.run(
        [sys.executable, tool, "--kernel", "band", "--interpret", "--out",
         str(out_file), "--band-blocks", "16", "--other",
         str(REPO / "mxnet_tpu" / "ops" / "pallas_kernels.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out_file.read_text().splitlines()]
    sides = ["other", "this", "this", "other", "this@16"]
    assert [ln["side"] for ln in lines] == sides * 3
    assert [ln["real"] for ln in lines[::5]] == [64, 47, 33]
    assert all(ln["kernel"] == "band" and ln["rung"] == 64
               and "ms_per_chain" not in ln for ln in lines)
    for ln in lines:
        if ln["side"] == "this":
            assert ln["max_abs_gap"] == 0.0
        elif ln["side"] != "other":
            assert 0.0 < ln["max_abs_gap"] < 1e-5
