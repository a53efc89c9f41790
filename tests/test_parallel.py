"""Multi-device execution tests on the conftest 8-virtual-device CPU mesh.

The analogue of the reference's single-host distributed tests
(``tests/nightly/dist_sync_kvstore.py`` run via ``tools/launch.py -n 7
--launcher local``, exact-value assertions at dist_sync_kvstore.py:30) and
``tests/python/gpu/test_kvstore_gpu.py``: every check here runs over N
DISTINCT devices, not N aliases of device 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel
from mxnet_tpu.gluon import nn

N = min(8, len(jax.devices()))
DEVICES = jax.devices()[:N]

pytestmark = pytest.mark.skipif(
    N < 2, reason="needs >=2 devices (conftest forces an 8-device CPU mesh)")


def test_mesh_has_distinct_devices():
    mesh = parallel.device_mesh(N, devices=DEVICES)
    ids = [d.id for d in mesh.devices.flat]
    assert len(set(ids)) == N


def test_device_mesh_2d_shape():
    mesh = parallel.device_mesh(shape=(N // 2, 2), axis_names=("dp", "mp"),
                                devices=DEVICES)
    assert mesh.axis_names == ("dp", "mp")
    assert mesh.devices.shape == (N // 2, 2)


def test_device_mesh_bad_axis_names():
    with pytest.raises(mx.MXNetError):
        parallel.device_mesh(shape=(N,), axis_names=("a", "b"),
                             devices=DEVICES)


@pytest.mark.parametrize("op,ref", [
    ("sum", lambda cs: np.sum(cs, axis=0)),
    ("mean", lambda cs: np.mean(cs, axis=0)),
    ("max", lambda cs: np.max(cs, axis=0)),
    ("min", lambda cs: np.min(cs, axis=0)),
])
def test_all_reduce_distinct_devices(op, ref):
    rng = np.random.RandomState(3)
    copies_np = [rng.randn(4, 5).astype(np.float32) for _ in DEVICES]
    copies = [jax.device_put(c, d) for c, d in zip(copies_np, DEVICES)]
    total = parallel.all_reduce(copies, op=op)
    np.testing.assert_allclose(np.asarray(total), ref(copies_np), rtol=1e-6)
    # result is replicated on every participating device
    assert total.devices() == set(DEVICES)


def test_all_reduce_ndarray_inputs():
    copies = [mx.nd.NDArray(jax.device_put(np.full((2, 3), i + 1.0,
                                                   np.float32), d), mx.cpu())
              for i, d in enumerate(DEVICES)]
    total = parallel.all_reduce(copies)
    np.testing.assert_allclose(np.asarray(total),
                               np.full((2, 3), sum(range(1, N + 1))))


def test_all_reduce_same_device_fallback():
    # copies all on one device: plain on-device reduce path
    d0 = DEVICES[0]
    copies = [jax.device_put(np.full((2,), float(i)), d0) for i in range(3)]
    total = parallel.all_reduce(copies)
    np.testing.assert_allclose(np.asarray(total), [3.0, 3.0])


def test_broadcast_to_devices():
    outs = parallel.broadcast_to_devices(np.arange(6, dtype=np.float32),
                                         DEVICES)
    assert len(outs) == N
    for o, d in zip(outs, DEVICES):
        assert o.devices() == {d}
        np.testing.assert_allclose(np.asarray(o), np.arange(6))


def test_shard_for_device():
    copies = [jax.device_put(np.ones((2,), np.float32), d) for d in DEVICES]
    total = parallel.all_reduce(copies)
    piece = parallel.shard_for_device(total, DEVICES[1])
    assert piece.devices() == {DEVICES[1]}
    np.testing.assert_allclose(np.asarray(piece), [float(N)] * 2)


def _make_net(prefix):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Conv2D(4, kernel_size=3, padding=1))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Flatten())
        net.add(nn.Dense(3))
    return net


def _materialize(net, xs):
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs))


def _copy_params(src, dst):
    sp = src.collect_params()
    for name, p in dst.collect_params().items():
        src_name = name.split("_", 1)[1]
        match = [n for n in sp if n.split("_", 1)[1] == src_name]
        assert len(match) == 1, (name, match)
        p.set_data(nd.array(np.asarray(sp[match[0]].data()._data)))


def test_trainstep_multi_vs_single_device_parity():
    """N-device sharded TrainStep == 1-device run on the same global batch
    (the reference's dist_sync exact-value discipline)."""
    xs = np.random.RandomState(1).rand(2 * N, 2, 8, 8).astype(np.float32)
    ys = np.random.RandomState(2).randint(0, 3, (2 * N,))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    net_multi = _make_net("pm_")
    _materialize(net_multi, xs)
    net_single = _make_net("ps_")
    _materialize(net_single, xs)
    _copy_params(net_multi, net_single)

    step_multi = parallel.TrainStep(
        net_multi, loss_fn, "sgd", parallel.device_mesh(N, devices=DEVICES),
        optimizer_params={"learning_rate": 0.1})
    step_single = parallel.TrainStep(
        net_single, loss_fn, "sgd",
        parallel.device_mesh(1, devices=DEVICES[:1]),
        optimizer_params={"learning_rate": 0.1})

    for _ in range(3):
        lm = step_multi(nd.array(xs), nd.array(ys))
        ls = step_single(nd.array(xs), nd.array(ys))
        np.testing.assert_allclose(lm.asnumpy(), ls.asnumpy(),
                                   rtol=1e-5, atol=1e-6)
    for name, v_multi in step_multi.params.items():
        tail = name.split("_", 1)[1]
        v_single = next(v for n, v in step_single.params.items()
                        if n.split("_", 1)[1] == tail)
        np.testing.assert_allclose(np.asarray(v_multi), np.asarray(v_single),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_trainstep_loss_decreases():
    xs = np.random.RandomState(5).rand(2 * N, 6).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) > 3.0).astype(np.float32)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(1))
    net.initialize()
    step = parallel.TrainStep(
        net, gluon.loss.SigmoidBinaryCrossEntropyLoss(), "sgd",
        parallel.device_mesh(N, devices=DEVICES),
        optimizer_params={"learning_rate": 0.5})
    first = float(step(nd.array(xs), nd.array(ys)).asnumpy())
    for _ in range(20):
        last = float(step(nd.array(xs), nd.array(ys)).asnumpy())
    assert last < first


@pytest.mark.parametrize("opt,opt_params,dtype", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, "float32"),
    # adam exercises the t-dependent path: the fused scan must advance the
    # 1-based step counter exactly like sequential calls (t=0 would zero
    # Adam's bias correction -> NaN on the very first fused step).
    # epsilon is raised so near-zero grads (conv bias behind BN) don't
    # amplify scan-vs-straight-line fusion rounding into update diffs
    ("adam", {"learning_rate": 0.01, "epsilon": 1e-3}, "float32"),
    # bf16 params with f32 master optimizer state: the scan carry must stay
    # dtype-stable (weights cast back to bf16, state kept f32)
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, "bfloat16"),
])
def test_trainstep_multi_call_matches_sequential_steps(opt, opt_params,
                                                       dtype):
    """K steps fused in one lax.scan module (multi_call) must produce the
    same per-step losses and final params as K sequential step() calls —
    the engine-bulking analogue (threaded_engine.cc:289) must not change
    the math."""
    K = 3
    bf16 = dtype == "bfloat16"
    xs = np.random.RandomState(11).rand(K, 2 * N, 2, 8, 8).astype(np.float32)
    ys = np.random.RandomState(12).randint(0, 3, (K, 2 * N))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.device_mesh(N, devices=DEVICES)

    tag = opt + dtype[:2]
    net_seq = _make_net("ms_" + tag)
    _materialize(net_seq, xs[0])
    net_fused = _make_net("mf_" + tag)
    _materialize(net_fused, xs[0])
    _copy_params(net_seq, net_fused)
    if bf16:
        net_seq.cast(dtype)
        net_fused.cast(dtype)
        xs = xs.astype(jnp.bfloat16)

    step_seq = parallel.TrainStep(net_seq, loss_fn, opt, mesh,
                                  optimizer_params=dict(opt_params))
    step_fused = parallel.TrainStep(net_fused, loss_fn, opt, mesh,
                                    optimizer_params=dict(opt_params))

    seq_losses = [float(step_seq(nd.array(xs[i]), nd.array(ys[i])).asnumpy())
                  for i in range(K)]
    fused_losses = step_fused.multi_call(nd.array(xs), nd.array(ys)).asnumpy()
    assert fused_losses.shape == (K,)
    np.testing.assert_allclose(fused_losses.astype(np.float32), seq_losses,
                               rtol=1e-2 if bf16 else 1e-5,
                               atol=1e-3 if bf16 else 1e-6)
    assert step_fused._t == step_seq._t == K

    for name, v_fused in step_fused.params.items():
        tail = name.split("_", 1)[1]
        v_seq = next(v for n, v in step_seq.params.items()
                     if n.split("_", 1)[1] == tail)
        assert v_fused.dtype == v_seq.dtype, name  # carry dtype stability
        np.testing.assert_allclose(
            np.asarray(v_fused, np.float32), np.asarray(v_seq, np.float32),
            rtol=1e-1 if bf16 else 1e-4, atol=1e-2 if bf16 else 1e-5,
            err_msg=name)


def test_inferstep_single_and_multi_match_net_forward():
    """InferStep output == the net's own (predict-mode) forward, and the
    K-batch scanned path == K single calls stacked."""
    K = 3
    xs = np.random.RandomState(21).rand(K, N, 2, 8, 8).astype(np.float32)
    net = _make_net("is_")
    _materialize(net, xs[0])
    expect = np.stack([net(nd.array(xs[i])).asnumpy() for i in range(K)])

    infer = parallel.InferStep(net, parallel.device_mesh(N, devices=DEVICES))
    single = infer(nd.array(xs[0])).asnumpy()
    np.testing.assert_allclose(single, expect[0], rtol=1e-5, atol=1e-6)
    fused = infer.multi_call(nd.array(xs)).asnumpy()
    assert fused.shape == expect.shape
    np.testing.assert_allclose(fused, expect, rtol=1e-5, atol=1e-6)


def test_trainstep_copy_to_net_roundtrip():
    xs = np.random.RandomState(6).rand(N, 4).astype(np.float32)
    ys = np.random.RandomState(7).rand(N, 1).astype(np.float32)
    net = nn.Dense(1)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), "sgd",
                              parallel.device_mesh(N, devices=DEVICES),
                              optimizer_params={"learning_rate": 0.1})
    step(nd.array(xs), nd.array(ys))
    step.copy_to_net()
    for name, p in net.collect_params().items():
        np.testing.assert_allclose(np.asarray(p.data()._data),
                                   np.asarray(step.params[name]))
    # net params stay valid after the next (buffer-donating) step
    step(nd.array(xs), nd.array(ys))
    for p in net.collect_params().values():
        np.asarray(p.data()._data)


def test_all_reduce_multi_one_module():
    rng = np.random.RandomState(11)
    shapes = [(3, 4), (7,), (2, 2, 2)]
    groups_np = [[rng.randn(*s).astype(np.float32) for _ in DEVICES]
                 for s in shapes]
    groups = [[jax.device_put(c, d) for c, d in zip(g, DEVICES)]
              for g in groups_np]
    totals = parallel.all_reduce_multi(groups)
    assert len(totals) == len(shapes)
    for t, g_np in zip(totals, groups_np):
        np.testing.assert_allclose(np.asarray(t), np.sum(g_np, axis=0),
                                   rtol=1e-5)
        assert t.devices() == set(DEVICES)


def test_all_reduce_multi_single_device_fallback():
    d0 = DEVICES[0]
    groups = [[jax.device_put(np.ones((2,), np.float32), d0)] for _ in range(3)]
    totals = parallel.all_reduce_multi(groups)
    for t in totals:
        np.testing.assert_allclose(np.asarray(t), 1.0)


def _train_trainer(ctx_list, seed=13, steps=4):
    """One user script, parameterized ONLY by ctx list — the reference's
    multi-device contract (same code on 1 GPU and N GPUs, gluon
    split_and_load + Trainer)."""
    from mxnet_tpu.gluon.utils import split_and_load

    xs = np.random.RandomState(seed).rand(16, 6).astype(np.float32)
    ys = np.random.RandomState(seed + 1).rand(16, 1).astype(np.float32)
    net = nn.HybridSequential(prefix="tt_")
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(1))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"), ctx=ctx_list)
    # materialize deferred-init params identically regardless of ctx count
    mx.random.seed(99)
    with mx.autograd.pause():
        net(nd.array(xs).as_in_context(ctx_list[0]))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="tpu")
    loss_fn = gluon.loss.L2Loss()
    for _ in range(steps):
        data_slices = split_and_load(nd.array(xs), ctx_list)
        label_slices = split_and_load(nd.array(ys), ctx_list)
        with mx.autograd.record():
            losses = [loss_fn(net(x), y)
                      for x, y in zip(data_slices, label_slices)]
        for l in losses:
            l.backward()
        trainer.step(16)
    return {n: np.asarray(p.data(ctx_list[0])._data)
            for n, p in net.collect_params().items()}


def test_trainer_tpu_kvstore_1_vs_n_device_parity():
    """Same user script trains identically on 1 and N devices changing only
    the ctx argument (VERDICT round-3 task 4; reference contract
    gluon/trainer.py:282-304). The N-device run reduces every gradient in
    one fused XLA module via KVStoreTPU.pushpull_multi."""
    single = _train_trainer([mx.cpu(0)])
    multi = _train_trainer([mx.cpu(i) for i in range(N)])
    assert set(single) == set(multi)
    for name in single:
        np.testing.assert_allclose(multi[name], single[name],
                                   rtol=1e-4, atol=1e-6, err_msg=name)


def test_trainstep_batchnorm_is_sync_across_devices():
    """BatchNorm inside a sharded TrainStep normalizes over the GLOBAL batch:
    the cross-device SyncBatchNorm semantics of the reference
    (src/operator/contrib/sync_batch_norm-inl.h) fall out of sharding the
    batch axis. Verified against a hand-computed global-batch BN."""
    xs = np.random.RandomState(8).rand(2 * N, 3).astype(np.float32) * 5.0
    net = nn.BatchNorm()
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs))  # materialize

    # run one training forward via TrainStep machinery over the mesh
    mesh = parallel.device_mesh(N, devices=DEVICES)
    step = parallel.TrainStep(net, lambda o, l: mx.nd.sum(o * 0.0), "sgd",
                              mesh, optimizer_params={"learning_rate": 0.0})
    step(nd.array(xs), nd.array(np.zeros(2 * N, np.float32)))
    # moving stats after one step must reflect GLOBAL batch statistics
    params = {n.split("_", 1)[1]: v for n, v in step.params.items()}
    momentum = 0.9
    expect_mean = (1 - momentum) * xs.mean(axis=0)
    np.testing.assert_allclose(np.asarray(params["running_mean"]),
                               expect_mean, rtol=1e-4, atol=1e-5)


def test_pipeline_apply_matches_sequential():
    """GPipe pipeline over the pp axis == sequential stage application
    (activations hop via ppermute; fill/drain schedule M+S-1 ticks)."""
    import jax.numpy as jnp

    S = min(4, len(jax.devices()))
    mesh = parallel.device_mesh(S, axis_names=("pp",))
    rs = np.random.RandomState(0)
    M, B, D = 6, 2, 8
    Ws = rs.randn(S, D, D).astype(np.float32) * 0.3
    xs = rs.randn(M, B, D).astype(np.float32)
    out = parallel.pipeline_apply(lambda w, x: jnp.tanh(x @ w),
                                  jnp.asarray(Ws), jnp.asarray(xs), mesh)
    e = xs.copy()
    for s in range(S):
        e = np.tanh(e @ Ws[s])
    np.testing.assert_allclose(np.asarray(out), e, rtol=1e-4, atol=1e-5)
    # single microbatch degenerate case
    out1 = parallel.pipeline_apply(lambda w, x: jnp.tanh(x @ w),
                                   jnp.asarray(Ws),
                                   jnp.asarray(xs[:1]), mesh)
    np.testing.assert_allclose(np.asarray(out1), e[:1], rtol=1e-4,
                               atol=1e-5)
