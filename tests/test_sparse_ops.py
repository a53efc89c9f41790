"""Sparse operator family vs scipy oracles.

Counterpart of the reference's sparse op tests
(``tests/python/unittest/test_sparse_operator.py``): dot(csr, dense) both
transposes, cast_storage round-trips, _sparse_retain, _square_sum on
row_sparse, _contrib_SparseEmbedding, and gradient flow through sparse dot
(grad w.r.t. the dense operand only — the reference's sparse-dot contract).
"""
import numpy as np
import pytest

try:
    import scipy.sparse as sps
except ImportError:  # pragma: no cover
    sps = None

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ndarray import sparse as mxs
from mxnet_tpu.ndarray.ndarray import invoke

RS = np.random.RandomState(11)

needs_scipy = pytest.mark.skipif(sps is None, reason="scipy not available")


def rand_sparse(m, n, density=0.3):
    a = (RS.randn(m, n) * (RS.rand(m, n) < density)).astype(np.float32)
    return a


@needs_scipy
def test_cast_storage_csr_matches_scipy():
    a = rand_sparse(13, 7)
    csr = mxs.cast_storage(nd.array(a), "csr")
    sp = sps.csr_matrix(a)
    assert csr.stype == "csr"
    np.testing.assert_allclose(csr.data.asnumpy(), sp.data, rtol=1e-6)
    np.testing.assert_array_equal(csr.indices.asnumpy(), sp.indices)
    np.testing.assert_array_equal(csr.indptr.asnumpy(), sp.indptr)
    # round-trip back to dense through the registered op
    np.testing.assert_allclose(
        mxs.cast_storage(csr, "default").asnumpy(), a, rtol=1e-6)


def test_cast_storage_row_sparse_roundtrip():
    a = rand_sparse(9, 5)
    a[3] = 0  # guarantee an all-zero row
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    assert rsp.stype == "row_sparse"
    stored = set(rsp.indices.asnumpy().tolist())
    assert 3 not in stored
    np.testing.assert_allclose(rsp.asnumpy(), a, rtol=1e-6)
    np.testing.assert_allclose(
        mxs.cast_storage(rsp, "default").asnumpy(), a, rtol=1e-6)
    # sparse→sparse cross-cast goes through dense
    csr = mxs.cast_storage(rsp, "csr")
    assert csr.stype == "csr"
    np.testing.assert_allclose(csr.asnumpy(), a, rtol=1e-6)


@needs_scipy
@pytest.mark.parametrize("transpose_a", [False, True])
def test_dot_csr_dense(transpose_a):
    a = rand_sparse(12, 8)
    sp = sps.csr_matrix(a)
    rhs_rows = 12 if transpose_a else 8
    b = RS.randn(rhs_rows, 6).astype(np.float32)
    csr = mxs.cast_storage(nd.array(a), "csr")
    out = mxs.dot(csr, nd.array(b), transpose_a=transpose_a)
    expect = (sp.T @ b) if transpose_a else (sp @ b)
    np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-4, atol=1e-5)


@needs_scipy
def test_dot_csr_vector():
    a = rand_sparse(10, 4)
    b = RS.randn(4).astype(np.float32)
    csr = mxs.cast_storage(nd.array(a), "csr")
    out = mxs.dot(csr, nd.array(b))
    np.testing.assert_allclose(out.asnumpy(), sps.csr_matrix(a) @ b,
                               rtol=1e-5, atol=1e-6)


def test_dot_dense_fallback_unchanged():
    # dense×dense must still take the plain FCompute path
    a = RS.randn(5, 4).astype(np.float32)
    b = RS.randn(4, 3).astype(np.float32)
    out = invoke("dot", nd.array(a), nd.array(b))
    np.testing.assert_allclose(out.asnumpy(), a @ b, rtol=1e-5)


@needs_scipy
def test_dot_csr_gradient_wrt_dense():
    """vjp through sparse dot reaches the dense operand; the csr operand is
    grad_req=null (reference dot-inl.h sparse backward)."""
    a = rand_sparse(12, 8)
    sp = sps.csr_matrix(a)
    csr = mxs.cast_storage(nd.array(a), "csr")
    w = nd.array(RS.randn(8, 3).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        y = mxs.dot(csr, w)
        loss = (y * y).sum()
    loss.backward()
    expect = 2 * (sp.T @ (sp @ np.asarray(w.asnumpy())))
    np.testing.assert_allclose(w.grad.asnumpy(), expect, rtol=1e-4, atol=1e-5)


def test_sparse_retain():
    a = rand_sparse(8, 3)
    a[2] = 0
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    ret = mxs.retain(rsp, [1, 2, 5])
    assert ret.stype == "row_sparse"
    expect = np.zeros_like(a)
    for r in (1, 2, 5):
        expect[r] = a[r]
    np.testing.assert_allclose(ret.asnumpy(), expect, rtol=1e-6)
    # requested-but-absent rows (row 2 zeroed above) come back zero
    np.testing.assert_array_equal(ret.asnumpy()[2], np.zeros(3, np.float32))


def test_square_sum_row_sparse():
    a = rand_sparse(10, 6)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    ss = invoke("_square_sum", rsp, axis=(1,), keepdims=True)
    assert ss.stype == "row_sparse"
    np.testing.assert_allclose(ss.asnumpy(), (a ** 2).sum(1, keepdims=True),
                               rtol=1e-5)
    flat = invoke("_square_sum", rsp, axis=(1,))
    np.testing.assert_allclose(flat.asnumpy(), (a ** 2).sum(1), rtol=1e-5)
    col = invoke("_square_sum", rsp, axis=(0,))
    np.testing.assert_allclose(col.asnumpy(), (a ** 2).sum(0), rtol=1e-5)
    tot = invoke("_square_sum", rsp)
    np.testing.assert_allclose(float(tot.asnumpy()), (a ** 2).sum(), rtol=1e-5)


def test_square_sum_dense_path_still_works():
    a = RS.randn(4, 5).astype(np.float32)
    out = invoke("_square_sum", nd.array(a), axis=(1,))
    np.testing.assert_allclose(out.asnumpy(), (a ** 2).sum(1), rtol=1e-5)


def test_sparse_embedding():
    w = RS.randn(20, 6).astype(np.float32)
    ids = RS.randint(0, 20, (4, 3)).astype(np.int64)
    out = invoke("_contrib_SparseEmbedding", nd.array(ids), nd.array(w),
                 input_dim=20, output_dim=6)
    np.testing.assert_allclose(out.asnumpy(), w[ids], rtol=1e-6)
    # gradient w.r.t. weight touches only looked-up rows
    wnd = nd.array(w)
    wnd.attach_grad()
    with autograd.record():
        e = invoke("_contrib_SparseEmbedding", nd.array(ids), wnd,
                   input_dim=20, output_dim=6)
        loss = e.sum()
    loss.backward()
    g = wnd.grad.asnumpy()
    touched = set(ids.ravel().tolist())
    for r in range(20):
        if r not in touched:
            np.testing.assert_array_equal(g[r], np.zeros(6, np.float32))
        else:
            assert np.any(g[r] != 0)


def test_sparse_dot_rejects_unsupported_combination():
    a = rand_sparse(6, 4)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    with pytest.raises(MXNetError):
        mxs.dot(rsp, nd.array(RS.randn(4, 2).astype(np.float32)))


@needs_scipy
def test_fm_training_converges():
    """Miniature of example/sparse/fm.py (reference
    tests/python/train/test_sparse_fm.py): FM on planted-linear csr data
    must cut MSE by >5x in a few epochs."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(repo / "example" / "sparse" / "fm.py"),
         "--epochs", "12", "--num-samples", "192", "--feature-dim", "300"],
        capture_output=True, text=True, timeout=300, cwd=str(repo), env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-1000:]
    assert "IMPROVED" in out.stdout


def test_dot_csr_vector_transpose_b_noop():
    a = rand_sparse(6, 4)
    b = RS.randn(4).astype(np.float32)
    csr = mxs.cast_storage(nd.array(a), "csr")
    out = mxs.dot(csr, nd.array(b), transpose_b=True)
    np.testing.assert_allclose(out.asnumpy(), a @ b, rtol=1e-5, atol=1e-6)


def test_square_sum_unsupported_axis_raises():
    a = rand_sparse(5, 4)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    with pytest.raises(MXNetError, match="axis"):
        invoke("_square_sum", rsp, axis=(2,))


def test_out_with_sparse_storage_rejected():
    a = rand_sparse(5, 4)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    with pytest.raises(MXNetError, match="sparse"):
        invoke("cast_storage", nd.array(a), stype="row_sparse", out=rsp)


def test_sparse_elemwise_add_sub():
    """rsp +/- rsp stays row_sparse over the row union (reference
    elemwise FComputeEx); mixed storage densifies."""
    a = np.zeros((6, 3), np.float32)
    b = np.zeros((6, 3), np.float32)
    a[[0, 2]] = RS.randn(2, 3)
    b[[2, 5]] = RS.randn(2, 3)
    ra = mxs.cast_storage(nd.array(a), "row_sparse")
    rb = mxs.cast_storage(nd.array(b), "row_sparse")
    s = invoke("elemwise_add", ra, rb)
    assert s.stype == "row_sparse"
    assert sorted(s.indices.asnumpy().tolist()) == [0, 2, 5]
    np.testing.assert_allclose(s.asnumpy(), a + b, rtol=1e-6)
    d = invoke("elemwise_sub", ra, rb)
    assert d.stype == "row_sparse"
    np.testing.assert_allclose(d.asnumpy(), a - b, rtol=1e-6)
    # mixed: rsp + dense -> dense
    m = invoke("elemwise_add", ra, nd.array(b))
    assert m.stype == "default"
    np.testing.assert_allclose(m.asnumpy(), a + b, rtol=1e-6)
    # empty rsp operand
    z = mxs.cast_storage(nd.array(np.zeros((6, 3), np.float32)),
                         "row_sparse")
    s2 = invoke("elemwise_add", ra, z)
    np.testing.assert_allclose(s2.asnumpy(), a, rtol=1e-6)


def test_sparse_elemwise_add_taped_dense_grad():
    """When recording with a dense in-graph operand, the non-differentiable
    ex kernel must NOT swallow the tape: the call falls back to the dense
    FCompute path and gradients flow."""
    a = rand_sparse(5, 3)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    w = nd.array(RS.randn(5, 3).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        y = invoke("elemwise_add", w, rsp)
        loss = (y * y).sum()
    loss.backward()
    np.testing.assert_allclose(w.grad.asnumpy(),
                               2 * (w.asnumpy() + a), rtol=1e-5)


def test_row_sparse_array_unsorted_indices_canonicalized():
    """User-supplied unsorted rsp indices are canonicalized (sorted with
    values reordered), as the binary-searching ex kernels require."""
    vals = np.array([[5., 5.], [1., 1.]], np.float32)
    rsp = mx.nd.sparse.row_sparse_array((vals, [5, 1]), shape=(6, 2))
    np.testing.assert_array_equal(rsp.indices.asnumpy(), [1, 5])
    np.testing.assert_allclose(rsp.data.asnumpy(),
                               [[1., 1.], [5., 5.]], rtol=1e-6)
    other = mxs.cast_storage(nd.array(np.zeros((6, 2), np.float32)
                                      + np.eye(6, 2, dtype=np.float32)),
                             "row_sparse")
    s = invoke("elemwise_add", rsp, other)
    dense = np.zeros((6, 2), np.float32)
    dense[5] = 5; dense[1] = 1
    np.testing.assert_allclose(s.asnumpy(),
                               dense + np.eye(6, 2, dtype=np.float32),
                               rtol=1e-6)


def test_sparse_retain_works_under_record():
    """_sparse_retain has no dense equivalent: it must keep dispatching its
    ex kernel even while autograd records (no grad-fallback regression)."""
    a = rand_sparse(6, 3)
    rsp = mxs.cast_storage(nd.array(a), "row_sparse")
    w = nd.array(RS.randn(2, 2).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        _ = (w * w).sum()      # recording is genuinely active
        ret = mxs.retain(rsp, [0, 2])
    assert ret.stype == "row_sparse"


def test_sparse_elemwise_add_int_dtype_preserved():
    a = np.zeros((4, 2), np.int32); a[1] = 3
    b = np.zeros((4, 2), np.int32); b[2] = 4
    ra = mxs.cast_storage(nd.array(a, dtype="int32"), "row_sparse")
    rb = mxs.cast_storage(nd.array(b, dtype="int32"), "row_sparse")
    s = invoke("elemwise_add", ra, rb)
    assert s.asnumpy().dtype == np.int32
    d = invoke("elemwise_sub", ra, rb)
    assert d.asnumpy().dtype == np.int32
    np.testing.assert_array_equal(d.asnumpy(), a - b)
