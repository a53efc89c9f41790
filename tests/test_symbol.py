"""Symbol serialization tests, including the legacy-JSON upgrade path
(reference src/nnvm/legacy_json_util.cc; fixture
tests/python/unittest/save_000800.json is a REAL v1.0 artifact saved by
MXNet 0.8)."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx

LEGACY_JSON = "/root/reference/tests/python/unittest/save_000800.json"


@pytest.mark.skipif(not os.path.exists(LEGACY_JSON),
                    reason="reference fixture not available")
def test_legacy_v1_json_loads_and_runs():
    """The v1.0 format keeps op parameters in a per-node 'param' dict next
    to user 'attr's, and omits aux-state inputs (BatchNorm moving stats);
    loading must merge the dicts and synthesize the aux variables."""
    sym = mx.sym.load(LEGACY_JSON)
    assert sym.list_arguments() == [
        "data", "fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias",
        "fc3_weight", "fc3_bias", "batchnorm0_gamma", "batchnorm0_beta",
        "softmax_label"]
    assert sym.list_auxiliary_states() == [
        "batchnorm0_moving_mean", "batchnorm0_moving_var"]

    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(data=(4, 10))
    assert out_shapes == [(4, 10)]
    assert aux_shapes == [(10,), (10,)]

    ex = sym.simple_bind(mx.cpu(), data=(4, 10))
    ex.arg_dict["data"][:] = np.random.rand(4, 10).astype(np.float32)
    out = ex.forward(is_train=False)
    # SoftmaxOutput rows sum to one
    np.testing.assert_allclose(out[0].asnumpy().sum(axis=1), 1.0, rtol=1e-5)


@pytest.mark.skipif(not os.path.exists(LEGACY_JSON),
                    reason="reference fixture not available")
def test_legacy_json_roundtrips_to_modern_format():
    sym = mx.sym.load(LEGACY_JSON)
    js = json.loads(sym.tojson())
    # modern format: single 'attrs' dict, no 'param'
    assert all("param" not in n for n in js["nodes"])
    s2 = mx.sym.load_json(sym.tojson())
    assert s2.list_arguments() == sym.list_arguments()
    assert s2.list_auxiliary_states() == sym.list_auxiliary_states()


def test_modern_json_roundtrip(tmp_path):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc")
    out = mx.sym.SoftmaxOutput(data=fc, name="softmax")
    fname = str(tmp_path / "m-symbol.json")
    out.save(fname)
    back = mx.sym.load(fname)
    assert back.list_arguments() == out.list_arguments()
    _, shapes, _ = back.infer_shape(data=(2, 5))
    assert shapes == [(2, 8)]


def test_group2ctx_model_parallel_placement():
    """group2ctx maps ctx_group attrs to device placement constraints
    (reference graph_executor.cc:1577). The symbol is the v1.0 fixture's
    two-group MLP, built here with AttrScope so the test runs where the
    reference checkout is absent. Same numerics as unplaced execution."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    with mx.AttrScope(ctx_group="stage1"):
        data = mx.sym.var("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
        act1 = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    with mx.AttrScope(ctx_group="stage2"):
        fc2 = mx.sym.FullyConnected(act1, num_hidden=64, name="fc2")
        act2 = mx.sym.Activation(fc2, act_type="relu", name="relu2")
        fc3 = mx.sym.FullyConnected(act2, num_hidden=10, name="fc3")
        sym = mx.sym.SoftmaxOutput(fc3, name="softmax")
    assert {n: a.get("ctx_group") for n, a in sym.attr_dict().items()
            if n in ("fc1", "fc3")} == {"fc1": "stage1", "fc3": "stage2"}
    rng = np.random.RandomState(0)
    x = rng.rand(4, 10).astype(np.float32)

    ex_plain = sym.simple_bind(mx.cpu(), data=(4, 10))
    ex_mp = sym.simple_bind(mx.cpu(), data=(4, 10),
                            group2ctx={"stage1": mx.cpu(0),
                                       "stage2": mx.cpu(1)})
    for ex in (ex_plain, ex_mp):
        ex.arg_dict["data"][:] = x
        for name, arr in ex.arg_dict.items():
            if name != "data":
                arr[:] = rng.rand(*arr.shape).astype(np.float32) * 0.1
            rng = np.random.RandomState(1)  # same weights for both
    out_plain = ex_plain.forward(is_train=True)[0].asnumpy()
    out_mp = ex_mp.forward(is_train=True)[0].asnumpy()
    np.testing.assert_allclose(out_mp, out_plain, rtol=1e-5)
    ex_mp.backward()
    assert np.isfinite(ex_mp.grad_dict["fc1_weight"].asnumpy()).all()


def test_attr_scope():
    """AttrScope attaches attrs to symbols created inside it (reference
    python/mxnet/attribute.py; tests/python/unittest/test_attr.py)."""
    with mx.AttrScope(ctx_group="stage1", __lr_mult__="2"):
        a = mx.sym.var("scoped_a")
        b = mx.sym.FullyConnected(a, num_hidden=4, name="scoped_fc")
        with mx.AttrScope(ctx_group="stage2"):
            c = mx.sym.exp(b, name="scoped_exp")
    d = mx.sym.var("unscoped")
    assert a.attr("ctx_group") == "stage1"
    assert a.attr("__lr_mult__") == "2"
    assert b.attr("ctx_group") == "stage1"
    # inner scope overrides, inherits the rest
    assert c.attr("ctx_group") == "stage2"
    assert c.attr("__lr_mult__") == "2"
    assert d.attr("ctx_group") is None
    # explicit attr beats the scope (reference AttrScope.get contract)
    with mx.AttrScope(ctx_group="stage1"):
        e = mx.sym.var("explicit", attr={"ctx_group": "stage9"})
    assert e.attr("ctx_group") == "stage9"


def test_libinfo_and_util():
    from mxnet_tpu import libinfo, util

    assert libinfo.__version__.startswith("1.3.0")
    for p in libinfo.find_lib_path():
        import os

        assert os.path.isfile(p)
    assert mx.viz is mx.visualization
    assert util.get_gpu_count() >= 0


def test_simple_bind_shared_exec_memory_sharing():
    """shared_exec makes matching arg arrays the SAME NDArrays (the
    reference's shared data pool across bucketing executors,
    graph_executor.cc:651,926)."""
    data = mx.sym.var("data")
    out = mx.sym.FullyConnected(data, num_hidden=4, name="fcs")
    ex1 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4, name="fcs") \
        .simple_bind(mx.cpu(), data=(2, 3))
    ex2 = out.simple_bind(mx.cpu(), data=(5, 3), shared_exec=ex1)
    # weight shares (same shape); data does not (different shape)
    assert ex2.arg_dict["fcs_weight"] is ex1.arg_dict["fcs_weight"]
    assert ex2.arg_dict["data"] is not ex1.arg_dict["data"]
    ex1.arg_dict["fcs_weight"][:] = 7.0
    np.testing.assert_allclose(ex2.arg_dict["fcs_weight"].asnumpy(), 7.0)


def test_simple_bind_shared_buffer_and_stype_reject():
    buf = {}
    data = mx.sym.var("data")
    out = mx.sym.FullyConnected(data, num_hidden=4, name="fcb")
    ex1 = out.simple_bind(mx.cpu(), data=(2, 3), shared_buffer=buf)
    assert "fcb_weight" in buf
    ex2 = out.simple_bind(mx.cpu(), data=(2, 3), shared_buffer=buf)
    assert ex2.arg_dict["fcb_weight"] is ex1.arg_dict["fcb_weight"]
    with pytest.raises(mx.MXNetError, match="sparse argument storage"):
        out.simple_bind(mx.cpu(), data=(2, 3),
                        stype_dict={"fcb_weight": "row_sparse"})


def test_runtime_features():
    from mxnet_tpu import runtime

    feats = runtime.Features()
    assert feats.is_enabled("CPU")
    assert feats.is_enabled("PALLAS")
    assert "NATIVE_RUNTIME" in feats
    assert isinstance(runtime.feature_list(), list)
    assert not feats.is_enabled("NOPE")
