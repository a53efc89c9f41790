"""Training-plane tests: the whole-step SPMD jit behind MXNET_TRAINSTEP.

The PR-5 discipline one level up: fp32 training through the graph plane
must be BIT-IDENTICAL to the eager fastpath (same host scalar prologue,
same tree kernel, same all-ones backward seed), telemetry must prove ONE
device dispatch per step, non-traceable models must fall back (never
crash), and the step counter must stay coherent when eager and in-graph
steps interleave. Runs on the conftest 8-virtual-device CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, telemetry, trainplane
from mxnet_tpu.gluon import nn

B = 8  # power of two: 1/B loss scaling is exact, so the eager path's
#        seed-ones-then-rescale and the graph plane's in-graph rescale
#        cannot differ by rounding


def _make_mlp(prefix):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(8))
    return net


def _init(net, xs):
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs[:B]))


def _copy_params(src, dst):
    sp = src.collect_params()
    for name, p in dst.collect_params().items():
        tail = name.split("_", 1)[1]
        match = [n for n in sp if n.split("_", 1)[1] == tail]
        assert len(match) == 1
        p.set_data(nd.array(np.asarray(sp[match[0]].data()._data)))


def _data(seed=3):
    rs = np.random.RandomState(seed)
    return (rs.rand(5 * B, 6).astype(np.float32),
            rs.randint(0, 8, (5 * B,)))


# ---------------------------------------------------------------------------
# bit-identity vs the eager fastpath
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opt,opt_params,ndev,bitwise", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 1, True),
    ("adam", {"learning_rate": 0.01}, 1, True),
    # a stateful host prologue (the m_schedule recurrence) and four device
    # scalars of its own in ``extras``, beside the packed t/lr/wd operand
    ("nadam", {"learning_rate": 0.01}, 1, True),
    # on a sharded mesh the dp-partial gradient reduction (per-device
    # matmul + psum) can differ from the single-device contraction order
    # by 1 ulp — the update math itself is still the identical kernel, so
    # the runs track within float32 rounding of the grad sum
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 2, False),
    ("adam", {"learning_rate": 0.01}, 2, False),
])
def test_graph_plane_matches_eager_fastpath(monkeypatch, opt, opt_params,
                                            ndev, bitwise):
    """Trainer-driven MLP via MXNET_TRAINSTEP=1 == the eager fastpath,
    bit-identical in fp32, over 5 steps (acceptance criterion)."""
    if len(jax.devices()) < ndev:
        pytest.skip("needs %d devices" % ndev)
    xs, ys = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tag = "%s%d_" % (opt, ndev)

    net_e = _make_mlp("e" + tag)
    _init(net_e, xs)
    net_e.hybridize()
    tr_e = gluon.Trainer(net_e.collect_params(), opt, dict(opt_params))

    net_g = _make_mlp("g" + tag)
    _init(net_g, xs)
    _copy_params(net_e, net_g)
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    tr_g = gluon.Trainer(net_g.collect_params(), opt, dict(opt_params))
    plane = trainplane.TrainPlane(net_g, loss_fn, tr_g,
                                  mesh=parallel.device_mesh(ndev))

    for s in range(5):
        x, y = xs[s * B:(s + 1) * B], ys[s * B:(s + 1) * B]
        with mx.autograd.record():
            le = loss_fn(net_e(nd.array(x)), nd.array(y))
        le.backward()
        tr_e.step(B)
        lg = plane.step(nd.array(x), nd.array(y))
        if bitwise:
            np.testing.assert_array_equal(lg.asnumpy(), le.asnumpy())
        else:
            np.testing.assert_allclose(lg.asnumpy(), le.asnumpy(),
                                       rtol=1e-5, atol=1e-6)
    assert plane.plane == "graph"

    pe, pg = net_e.collect_params(), net_g.collect_params()
    for name, p in pg.items():
        tail = name.split("_", 1)[1]
        ref = next(v for n, v in pe.items()
                   if n.split("_", 1)[1] == tail)
        if bitwise:
            np.testing.assert_array_equal(
                np.asarray(p.data()._data), np.asarray(ref.data()._data),
                err_msg=name)
        else:
            np.testing.assert_allclose(
                np.asarray(p.data()._data), np.asarray(ref.data()._data),
                rtol=1e-5, atol=1e-6, err_msg=name)
    # optimizer state lives in the trainer's updater, same layout as eager
    st_g = tr_g._updaters[0].states
    st_e = tr_e._updaters[0].states
    assert set(st_g) == set(st_e)
    if bitwise:
        for k in st_e:
            le_, lg_ = (jax.tree_util.tree_leaves(st[k])
                        for st in (st_e, st_g))
            assert len(le_) == len(lg_)
            for a, b in zip(le_, lg_):
                np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                              err_msg="state %s" % k)


def test_graph_plane_one_dispatch_per_step(monkeypatch):
    """Telemetry proof of the acceptance criterion: exactly 1 jit dispatch
    per step for the whole fwd+bwd+update — the step counter ticks once
    per call and the optimizer-update counters not at all."""
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    xs, ys = _data(11)
    net = _make_mlp("disp_")
    _init(net, xs)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(1))
    plane.step(nd.array(xs[:B]), nd.array(ys[:B]))  # activate + compile
    g0 = telemetry.STEP_DISPATCHES.value(plane="graph")
    o0 = (telemetry.OPT_DISPATCHES.value(path="perparam")
          + telemetry.OPT_DISPATCHES.value(path="fused"))
    for s in range(1, 4):
        plane.step(nd.array(xs[s * B:(s + 1) * B]),
                   nd.array(ys[s * B:(s + 1) * B]))
    assert telemetry.STEP_DISPATCHES.value(plane="graph") - g0 == 3
    assert (telemetry.OPT_DISPATCHES.value(path="perparam")
            + telemetry.OPT_DISPATCHES.value(path="fused")) - o0 == 0


# ---------------------------------------------------------------------------
# the packed scalar operand (PR 26): one host array, no device put
# ---------------------------------------------------------------------------


def _mlp_plane(monkeypatch, prefix, opt="sgd", opt_params=None, ndev=1):
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    xs, ys = _data(17)
    net = _make_mlp(prefix)
    _init(net, xs)
    tr = gluon.Trainer(net.collect_params(), opt, dict(
        opt_params or {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3}))
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(ndev))

    def step(s):
        return plane.step(nd.array(xs[s * B:(s + 1) * B]),
                          nd.array(ys[s * B:(s + 1) * B]))

    return plane, tr, step


def test_host_prologue_packs_one_float32_host_array(monkeypatch):
    """``_host_prologue`` hands back ONE numpy (3, rows) float32 array —
    t, lr, wd, rounded as the eager plane's ``jnp.asarray(x, float32)``
    rounds them — and counts each index exactly once."""
    plane, tr, step = _mlp_plane(monkeypatch, "pack_")
    step(0)
    opt = tr._optimizer
    indices = [i for i, _ in plane._rows]
    before = dict(opt._index_update_count)
    scalars, extras = plane._host_prologue(opt, indices)
    assert type(scalars) is np.ndarray
    assert scalars.shape == (3, len(indices))
    assert scalars.dtype == np.float32
    assert extras == [()] * len(indices)
    assert plane._prologue_puts(extras) == 0
    for k, i in enumerate(indices):
        assert opt._index_update_count[i] == before[i] + 1
        want = [jnp.asarray(v, dtype=jnp.float32) for v in (
            opt._index_update_count[i], opt._get_lr(i), opt._get_wd(i))]
        np.testing.assert_array_equal(scalars[:, k], np.asarray(want))
    # in-trace unpacking: the per-row lists tree_kernel takes, same bits
    ts, lrs, wds = jax.jit(trainplane._unpack_scalars)(scalars)
    assert len(ts) == len(lrs) == len(wds) == len(indices)
    np.testing.assert_array_equal(np.asarray([ts, lrs, wds]), scalars)


@pytest.mark.parametrize("opt,opt_params,puts_per_row", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 0),
    ("adam", {"learning_rate": 0.01}, 0),
    # Nadam's four schedule scalars are the optimizer's own device values
    ("nadam", {"learning_rate": 0.01}, 4),
])
def test_prologue_span_issues_no_device_put(monkeypatch, opt, opt_params,
                                            puts_per_row):
    """Inside ``train.prologue`` the plane itself makes no host->device
    transfer: ``jnp.asarray`` and ``jax.device_put`` are not called from
    ``trainplane`` at all, and the span's ``puts`` argument counts only
    what the optimizer put in ``extras``."""
    plane, tr, step = _mlp_plane(monkeypatch, "noput_%s_" % opt, opt,
                                 opt_params)
    step(0)  # activation, probe and compile stay outside the count
    assert plane.plane == "graph"
    seen = {"open": False, "calls": 0, "puts": []}

    class _Watch(telemetry.span):
        __slots__ = ()

        def __enter__(self):
            if self.name == "train.prologue":
                seen["open"] = True
            return super().__enter__()

        def set_args(self, **args):
            if self.name == "train.prologue":
                seen["puts"].append(args["puts"])
            return super().set_args(**args)

        def __exit__(self, *exc):
            if self.name == "train.prologue":
                seen["open"] = False
            return super().__exit__(*exc)

    def counting(real):
        def wrapper(*a, **k):
            seen["calls"] += seen["open"]
            return real(*a, **k)
        return wrapper

    monkeypatch.setattr(telemetry, "span", _Watch)
    monkeypatch.setattr(trainplane.jnp, "asarray",
                        counting(jnp.asarray))
    monkeypatch.setattr(trainplane.jax, "device_put",
                        counting(jax.device_put))
    for s in range(1, 4):
        step(s)
    rows = len(plane._rows)
    assert seen["puts"] == [puts_per_row * rows] * 3
    if puts_per_row == 0:
        assert seen["calls"] == 0
    else:  # the counter is live: the optimizer's own puts pass through it
        assert seen["calls"] >= 3 * puts_per_row * rows


def test_changing_lr_travels_in_the_operand_without_retrace(monkeypatch):
    """A learning-rate schedule changes the VALUES of the packed operand
    every step and nothing of its type: one jit entry, one trace of it, no
    recompile — and the weights still follow the eager loop bit for bit,
    so the step reads this step's values."""
    from mxnet_tpu import lr_scheduler

    xs, ys = _data(23)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def make(prefix):
        net = _make_mlp(prefix)
        _init(net, xs)
        opt = mx.optimizer.create(
            "sgd", learning_rate=0.4, momentum=0.9,
            lr_scheduler=lr_scheduler.FactorScheduler(step=1, factor=0.7))
        return net, opt, gluon.Trainer(net.collect_params(), opt)

    net_e, opt_e, tr_e = make("lre_")
    net_e.hybridize()
    net_g, opt_g, tr_g = make("lrg_")
    _copy_params(net_e, net_g)
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    plane = trainplane.TrainPlane(net_g, loss_fn, tr_g,
                                  mesh=parallel.device_mesh(1))

    def both(s):
        x, y = nd.array(xs[(s % 5) * B:(s % 5 + 1) * B]), \
            nd.array(ys[(s % 5) * B:(s % 5 + 1) * B])
        with mx.autograd.record():
            le = loss_fn(net_e(x), y)
        le.backward()
        tr_e.step(B)
        plane.step(x, y)

    both(0)  # activate + the one compile
    assert plane.plane == "graph"
    r0 = telemetry.RECOMPILES.value(site="trainplane.step")
    lrs = []
    for s in range(1, 6):
        both(s)
        lrs.append(opt_g.learning_rate)
    assert len(set(lrs)) == 5  # the schedule moved on every step
    assert telemetry.RECOMPILES.value(site="trainplane.step") - r0 == 0
    assert len(plane._jits) == 1
    (fn,) = plane._jits.values()
    assert fn._cache_size() == 1
    pe, pg = net_e.collect_params(), net_g.collect_params()
    for name, p in pg.items():
        ref = pe["lre_" + name.split("_", 1)[1]]
        np.testing.assert_array_equal(np.asarray(p.data()._data),
                                      np.asarray(ref.data()._data),
                                      err_msg=name)


def test_zero_branch_and_its_fallback_share_one_prologue(monkeypatch):
    """The ZeRO branch reads rows of the SAME packed array the replicated
    step takes as its operand: when the sharded step fails, the fallback
    dispatches the very array that step was handed, the counters have
    advanced once, and training tracks the eager loop."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    from mxnet_tpu.fastpath import zero

    xs, ys = _data(29)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_e = _make_mlp("zfe_")
    _init(net_e, xs)
    net_e.hybridize()
    tr_e = gluon.Trainer(net_e.collect_params(), "adam",
                         {"learning_rate": 0.01})
    net_g = _make_mlp("zfg_")
    _init(net_g, xs)
    _copy_params(net_e, net_g)
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    tr_g = gluon.Trainer(net_g.collect_params(), "adam",
                         {"learning_rate": 0.01})
    plane = trainplane.TrainPlane(net_g, loss_fn, tr_g,
                                  mesh=parallel.device_mesh(2))

    prologues, zero_got, step_got = [], [], []
    real_prologue = plane._host_prologue
    real_zero_call = plane._zero_graph_call
    real_jit_call = telemetry.jit_call

    def prologue(opt, indices):
        out = real_prologue(opt, indices)
        if opt is tr_g._optimizer:  # the probe counts on a throwaway copy
            prologues.append(out[0])
        return out

    def zero_call(zp, opt, updater, scalars, d, l, rng):
        zero_got.append(scalars)
        if len(zero_got) == 2:
            raise RuntimeError("sharded trace refused")
        return real_zero_call(zp, opt, updater, scalars, d, l, rng)

    def jit_call(site, fn, *args, **kwargs):
        if site == "trainplane.step" and plane._zero_broken:
            step_got.append(args[3])
        return real_jit_call(site, fn, *args, **kwargs)

    monkeypatch.setattr(plane, "_host_prologue", prologue)
    monkeypatch.setattr(plane, "_zero_graph_call", zero_call)
    monkeypatch.setattr(telemetry, "jit_call", jit_call)
    for s in range(3):
        x, y = nd.array(xs[s * B:(s + 1) * B]), \
            nd.array(ys[s * B:(s + 1) * B])
        monkeypatch.setenv("MXNET_ZERO", "0")
        with mx.autograd.record():
            le = loss_fn(net_e(x), y)
        le.backward()
        tr_e.step(B)
        monkeypatch.setenv("MXNET_ZERO", "1")
        lg = plane.step(x, y)
        np.testing.assert_allclose(lg.asnumpy(), le.asnumpy(),
                                   rtol=1e-5, atol=1e-6)
    assert plane.plane == "graph" and plane._zero_broken == "RuntimeError"
    assert zero.plane_of(tr_g._updaters[0]) is None
    # one prologue a step; step 1 sharded, step 2 sharded-then-replicated
    # on ONE array, step 3 replicated
    assert len(prologues) == 3 and len(zero_got) == 2
    assert zero_got[0] is prologues[0] and zero_got[1] is prologues[1]
    assert len(step_got) == 2
    assert step_got[0] is prologues[1] and step_got[1] is prologues[2]
    np.testing.assert_array_equal(prologues[1][0], 2.0)  # t, counted once
    opt = tr_g._optimizer
    assert opt.num_update == 3
    assert all(opt._index_update_count[i] == 3 for i, _ in plane._rows)
    pe, pg = net_e.collect_params(), net_g.collect_params()
    for name, p in pg.items():
        ref = pe["zfe_" + name.split("_", 1)[1]]
        np.testing.assert_allclose(np.asarray(p.data()._data),
                                   np.asarray(ref.data()._data),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# automatic fallback (acceptance: non-traceable models never crash)
# ---------------------------------------------------------------------------


class _HostSyncBlock(gluon.HybridBlock):
    """Untraceable: forces a device->host sync inside hybrid_forward."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.dense = nn.Dense(4)

    def hybrid_forward(self, F, x):
        _ = float(x.asnumpy().sum())  # concretization error under trace
        return self.dense(x)


class _PlainBlock(gluon.Block):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.dense = nn.Dense(4)

    def forward(self, x):
        return self.dense(x)


@pytest.mark.parametrize("cls,reason", [
    (_HostSyncBlock, "host sync in hybrid_forward"),
    (_PlainBlock, "plain Block"),
])
def test_nontraceable_falls_back_to_eager(monkeypatch, cls, reason):
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    xs, _ = _data(17)
    ys = np.random.RandomState(18).rand(5 * B, 4).astype(np.float32)
    net = cls(prefix="fb%s_" % cls.__name__[:5].lower())
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.L2Loss(), tr,
                                  mesh=parallel.device_mesh(1))
    losses = [float(plane.step(nd.array(xs[s * B:(s + 1) * B]),
                               nd.array(ys[s * B:(s + 1) * B]))
                    .asnumpy().mean()) for s in range(5)]
    assert plane.plane == "eager", reason
    assert losses[-1] < losses[0]  # it trained, eagerly


def test_ragged_final_batch_does_not_crash(monkeypatch):
    """The last partial batch of an epoch (not divisible by the dp axis)
    degrades to a replicated layout instead of raising in device_put —
    the never-a-crash contract covers mid-epoch shape changes too."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    xs, ys = _data(33)
    net = _make_mlp("rag_")
    _init(net, xs)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(2))
    plane.step(nd.array(xs[:B]), nd.array(ys[:B]))
    assert plane.plane == "graph"
    ragged = B - 3  # 5: not divisible by the 2-wide dp axis
    loss = plane.step(nd.array(xs[B:B + ragged]),
                      nd.array(ys[B:B + ragged]))
    assert plane.plane == "graph"
    assert np.isfinite(loss.asnumpy()).all() and loss.shape == (ragged,)


def test_failed_probe_leaves_params_unreplicated(monkeypatch):
    """A probe failure on a multi-device mesh must demote WITHOUT leaving
    params re-pointed at mesh-replicated arrays, or the promised eager
    fallback itself would die mixing single-device batches with
    mesh-committed params."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setenv("MXNET_TRAINSTEP", "auto")
    xs, _ = _data(34)
    ys = np.random.RandomState(35).rand(5 * B, 4).astype(np.float32)
    net = _HostSyncBlock(prefix="probe2_")
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.L2Loss(), tr,
                                  mesh=parallel.device_mesh(2))
    loss = plane.step(nd.array(xs[:B]), nd.array(ys[:B]))
    assert plane.plane == "eager"
    assert np.isfinite(loss.asnumpy()).all()
    for p in net.collect_params().values():
        assert len(p.data()._data.sharding.device_set) == 1


def test_trainstep_zero_forces_eager(monkeypatch):
    monkeypatch.setenv("MXNET_TRAINSTEP", "0")
    xs, ys = _data(21)
    net = _make_mlp("off_")
    _init(net, xs)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(1))
    plane.step(nd.array(xs[:B]), nd.array(ys[:B]))
    assert plane.plane == "eager"


# ---------------------------------------------------------------------------
# bf16 training mode
# ---------------------------------------------------------------------------


def test_bf16_mode_master_weights_and_loss(monkeypatch):
    """MXNET_TRAIN_DTYPE=bf16: params train in bfloat16, the optimizer
    keeps f32 master weights (multi-precision), and the graph-plane loss
    matches an explicit eager bf16 run within bf16 tolerance."""
    xs, ys = _data(31)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # eager bf16 reference: manual cast + multi_precision, the status quo
    net_e = _make_mlp("ebf_")
    _init(net_e, xs)
    net_e.cast("bfloat16")
    tr_e = gluon.Trainer(net_e.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9,
                          "multi_precision": True})

    net_g = _make_mlp("gbf_")
    _init(net_g, xs)
    _copy_params(net_e, net_g)  # fp32 values == bf16-cast values upcast
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    monkeypatch.setenv("MXNET_TRAIN_DTYPE", "bf16")
    tr_g = gluon.Trainer(net_g.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    plane = trainplane.TrainPlane(net_g, loss_fn, tr_g,
                                  mesh=parallel.device_mesh(1))

    for s in range(3):
        x = xs[s * B:(s + 1) * B]
        y = ys[s * B:(s + 1) * B]
        xe = mx.nd.NDArray(jnp.asarray(x, jnp.bfloat16), mx.cpu())
        with mx.autograd.record():
            le = loss_fn(net_e(xe), nd.array(y))
        le.backward()
        tr_e.step(B)
        lg = plane.step(nd.array(x), nd.array(y))
        np.testing.assert_allclose(
            lg.asnumpy().astype(np.float32),
            le.asnumpy().astype(np.float32), rtol=1e-2, atol=1e-2)

    assert plane.plane == "graph"
    for p in net_g.collect_params().values():
        assert p.data()._data.dtype == jnp.bfloat16
    # master weights stay f32 (the mp (master, base) state pair)
    states = tr_g._updaters[0].states
    for st in states.values():
        master, _base = st
        assert master.dtype == jnp.float32


# ---------------------------------------------------------------------------
# step-counter coherence (in-graph + eager interleave)
# ---------------------------------------------------------------------------


def test_mixed_trainstep_eager_counter_and_lr_schedule():
    """TrainStep._t and Optimizer.num_update share one source of truth:
    2 eager + 3 in-graph + 2 eager steps advance the lr schedule exactly
    like 7 eager steps would (regression for lr-schedule drift)."""
    from mxnet_tpu import lr_scheduler

    xs, _ = _data(41)
    lbl = np.random.RandomState(42).rand(B, 4).astype(np.float32)
    loss_fn = gluon.loss.L2Loss()

    def eager_step(net, tr):
        with mx.autograd.record():
            l = loss_fn(net(nd.array(xs[:B])), nd.array(lbl))
        l.backward()
        tr.step(B)

    sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
    opt = mx.optimizer.create("sgd", learning_rate=0.8, lr_scheduler=sched)
    net = nn.Dense(4, prefix="mix_")
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs[:B]))
    tr = gluon.Trainer(net.collect_params(), opt)
    step = parallel.TrainStep(net, loss_fn, opt, parallel.device_mesh(1))

    for _ in range(2):
        eager_step(net, tr)
    assert opt.num_update == 2
    for _ in range(3):
        step(nd.array(xs[:B]), nd.array(lbl))
    assert step._t == 5 and opt.num_update == 5
    for _ in range(2):
        eager_step(net, tr)
    assert opt.num_update == 7

    # reference: a pure-eager 7-step run reads the same schedule point
    ref_sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
    ref_sched.base_lr = 0.8
    assert opt.learning_rate == ref_sched(7)


def test_sync_num_update_seeds_fresh_indices():
    """An index first touched eagerly AFTER graph-only steps continues the
    counter at t + 1 — graph steps never populate _index_update_count, so
    sync must advance begin_num_update too, or Adam's bias correction
    would replay step 1 at step t + 1."""
    opt = mx.optimizer.create("adam", learning_rate=0.01)
    opt.sync_num_update(10)
    assert opt._index_update_count == {}  # graph steps left it empty
    opt._update_count(0)
    assert opt._index_update_count[0] == 11
    assert opt.num_update == 11


# ---------------------------------------------------------------------------
# Module.fit / model.fit routing
# ---------------------------------------------------------------------------


def _mlp_symbol(classes):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _fit_module(trainstep, xs, ys, monkeypatch):
    from mxnet_tpu import io as io_mod
    from mxnet_tpu.module import Module

    monkeypatch.setenv("MXNET_TRAINSTEP", trainstep)
    mx.random.seed(7)
    it = io_mod.NDArrayIter(xs, ys, batch_size=B, shuffle=False)
    mod = Module(_mlp_symbol(4), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier(rnd_type="uniform"))
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def test_module_fit_graph_plane_bitwise(monkeypatch):
    """Module.fit through the graph plane (MXNET_TRAINSTEP=1) trains
    bit-identically to the eager executor path, with ONE whole-step
    dispatch per batch and zero separate optimizer dispatches."""
    rs = np.random.RandomState(51)
    xs = rs.rand(4 * B, 6).astype(np.float32)
    ys = rs.randint(0, 4, (4 * B,)).astype(np.float32)

    g0 = telemetry.STEP_DISPATCHES.value(plane="graph")
    o0 = (telemetry.OPT_DISPATCHES.value(path="perparam")
          + telemetry.OPT_DISPATCHES.value(path="fused"))
    graph_params = _fit_module("1", xs, ys, monkeypatch)
    assert telemetry.STEP_DISPATCHES.value(plane="graph") - g0 == 8  # 2x4
    assert (telemetry.OPT_DISPATCHES.value(path="perparam")
            + telemetry.OPT_DISPATCHES.value(path="fused")) - o0 == 0

    eager_params = _fit_module("0", xs, ys, monkeypatch)
    assert set(graph_params) == set(eager_params)
    for name in graph_params:
        np.testing.assert_array_equal(graph_params[name],
                                      eager_params[name], err_msg=name)


def test_module_plane_demotes_on_grad_req_add(monkeypatch):
    """A param with grad_req='add' (accumulation across calls — a side
    effect the compiled step can't honor) demotes the WHOLE module to the
    eager path; it must never be silently frozen as a jit constant while
    the write-req params train."""
    from mxnet_tpu import io as io_mod
    from mxnet_tpu.module import Module

    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    rs = np.random.RandomState(71)
    xs = rs.rand(2 * B, 6).astype(np.float32)
    ys = rs.randint(0, 4, (2 * B,)).astype(np.float32)
    it = io_mod.NDArrayIter(xs, ys, batch_size=B)
    mod = Module(_mlp_symbol(4), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert trainplane.module_plane(mod) is not None  # eligible as bound

    mod._exec_group.execs[0].grad_req["fc1_weight"] = "add"
    assert trainplane.module_plane(mod) is None  # mixed write/add demotes


def test_feedforward_fit_rides_module_plane(monkeypatch):
    """model.fit (FeedForward) trains through Module.fit and therefore the
    plane; smoke: it runs under MXNET_TRAINSTEP=1 and learns."""
    from mxnet_tpu import io as io_mod
    from mxnet_tpu.model import FeedForward

    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    rs = np.random.RandomState(61)
    xs = rs.rand(4 * B, 6).astype(np.float32)
    ys = (xs.sum(axis=1) > 3.0).astype(np.float32)
    it = io_mod.NDArrayIter(xs, ys, batch_size=B)
    ff = FeedForward(_mlp_symbol(2), num_epoch=2, optimizer="sgd",
                     learning_rate=0.5)
    ff.fit(it)
    out = ff.predict(io_mod.NDArrayIter(xs, ys, batch_size=B))
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# fit() helper + pre-sharded feed
# ---------------------------------------------------------------------------


def test_fit_helper_sharded_feed(monkeypatch):
    """trainplane.fit drives epochs through the graph plane with the
    DevicePrefetchIter pre-sharded feed; training makes progress."""
    from mxnet_tpu import io as io_mod

    monkeypatch.setenv("MXNET_TRAINSTEP", "auto")
    monkeypatch.setenv("MXNET_SHARDED_FEED", "1")
    rs = np.random.RandomState(71)
    xs = rs.rand(8 * B, 6).astype(np.float32)
    ys = rs.randint(0, 4, (8 * B,)).astype(np.float32)
    net = _make_mlp("fith_")
    _init(net, xs)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.2})
    it = io_mod.NDArrayIter(xs, ys, batch_size=B, shuffle=False)

    seen = []
    plane = trainplane.fit(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr,
                           it, epochs=2,
                           batch_end_callback=lambda e, n, l: seen.append(
                               float(l.asnumpy().mean())))
    assert plane.plane == "graph"
    assert plane.step_count == 16  # 2 epochs x 8 batches
    assert seen[-1] < seen[0]


def test_device_prefetch_iter_skips_resident_batches():
    """Bugfix regression: an array already laid out on the target device/
    sharding passes through _stage untouched — no wasted D2D re-put."""
    from mxnet_tpu import io as io_mod

    rs = np.random.RandomState(81)
    xs = rs.rand(2 * B, 4).astype(np.float32)
    ys = rs.rand(2 * B).astype(np.float32)
    base = io_mod.NDArrayIter(xs, ys, batch_size=B)
    it = io_mod.DevicePrefetchIter(base, ctx=mx.cpu())
    batch = next(it)
    arr = batch.data[0]
    staged = it._stage(io_mod.DataBatch([arr], [batch.label[0]], pad=0))
    assert staged.data[0] is arr  # identity, not a copy
    assert staged.label[0] is batch.label[0]


def test_device_prefetch_iter_sharding_target():
    """sharding= lays batches out over the mesh's dp axis ahead of the
    step (callable ndim -> NamedSharding form)."""
    from mxnet_tpu import io as io_mod

    ndev = min(2, len(jax.devices()))
    mesh = parallel.device_mesh(ndev)
    rs = np.random.RandomState(91)
    xs = rs.rand(2 * B, 4).astype(np.float32)
    ys = rs.rand(2 * B).astype(np.float32)
    base = io_mod.NDArrayIter(xs, ys, batch_size=B)
    it = io_mod.DevicePrefetchIter(
        base, ctx=mx.cpu(),
        sharding=lambda ndim: parallel.batch_sharding(mesh, ndim))
    batch = next(it)
    data = batch.data[0]._data
    target = parallel.batch_sharding(mesh, data.ndim)
    assert data.sharding.is_equivalent_to(target, data.ndim)
    # the step's own shard pass is now the no-op equivalence check
    assert parallel.shard_to_mesh(batch.data[0], mesh) is data


def test_dataloader_sharding_stages_batches():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ndev = min(2, len(jax.devices()))
    mesh = parallel.device_mesh(ndev)
    rs = np.random.RandomState(95)
    ds = ArrayDataset(nd.array(rs.rand(4 * B, 5).astype(np.float32)),
                      nd.array(rs.rand(4 * B).astype(np.float32)))
    loader = DataLoader(
        ds, batch_size=B,
        sharding=lambda ndim: parallel.batch_sharding(mesh, ndim))
    for data, label in loader:
        tgt = parallel.batch_sharding(mesh, data._data.ndim)
        assert data._data.sharding.is_equivalent_to(tgt, data._data.ndim)
        break


def test_dataloader_sharding_keeps_namedtuple_batches():
    """The staged feed rebuilds containers field-for-field — a batchify_fn
    returning a namedtuple must come back as the same namedtuple."""
    import collections

    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.gluon.data.dataloader import default_batchify_fn

    Batch = collections.namedtuple("Batch", ["data", "label"])
    mesh = parallel.device_mesh(1)
    rs = np.random.RandomState(97)
    ds = ArrayDataset(nd.array(rs.rand(2 * B, 5).astype(np.float32)),
                      nd.array(rs.rand(2 * B).astype(np.float32)))
    loader = DataLoader(
        ds, batch_size=B,
        batchify_fn=lambda samples: Batch(*default_batchify_fn(samples)),
        sharding=lambda ndim: parallel.batch_sharding(mesh, ndim))
    batch = next(iter(loader))
    assert isinstance(batch, Batch)
    tgt = parallel.batch_sharding(mesh, batch.data._data.ndim)
    assert batch.data._data.sharding.is_equivalent_to(
        tgt, batch.data._data.ndim)


# ---------------------------------------------------------------------------
# fresh replication (TrainStep init HBM fix)
# ---------------------------------------------------------------------------


def test_fresh_replicate_never_aliases_source():
    """The replicated buffer must be fresh — the step jit donates it, and
    an alias would let donation delete the caller's array."""
    mesh1 = parallel.device_mesh(1)
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), jax.devices()[0])
    out = parallel.fresh_replicate(x, mesh1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert out.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()

    if len(jax.devices()) >= 2:
        mesh2 = parallel.device_mesh(2)
        out2 = parallel.fresh_replicate(x, mesh2)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(x))
        ptrs = {s.data.unsafe_buffer_pointer()
                for s in out2.addressable_shards}
        assert x.unsafe_buffer_pointer() not in ptrs
    # host source: one put, fresh by construction
    out3 = parallel.fresh_replicate(np.ones(4, np.float32), mesh1)
    np.testing.assert_array_equal(np.asarray(out3), np.ones(4))


def test_trainstep_net_params_survive_donating_steps():
    """After the fresh-replicate init, the net's own buffers stay valid
    across donating TrainStep calls (the isolation fresh_replicate buys)."""
    xs = np.random.RandomState(5).rand(B, 4).astype(np.float32)
    ys = np.random.RandomState(6).rand(B, 1).astype(np.float32)
    net = nn.Dense(1, prefix="iso_")
    net.initialize()
    with mx.autograd.pause():
        net(nd.array(xs))
    before = {n: np.asarray(p.data()._data).copy()
              for n, p in net.collect_params().items()}
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), "sgd",
                              parallel.device_mesh(1),
                              optimizer_params={"learning_rate": 0.1})
    for _ in range(2):
        step(nd.array(xs), nd.array(ys))
    for n, p in net.collect_params().items():
        np.testing.assert_array_equal(np.asarray(p.data()._data),
                                      before[n], err_msg=n)


# ---------------------------------------------------------------------------
# one compile per plane; backend refusals are not demotions (PR 22)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,ndev", [("fp32", 1), ("bf16", 1),
                                        ("fp32", 2)])
def test_whole_step_compiles_exactly_once(monkeypatch, dtype, ndev):
    """The step's inputs must have ONE type from the first call on: a
    first step fed single-device params (mesh-less type under jax >= 0.9)
    or a bf16-born momentum (the kernel returns fp32) compiled the whole
    program a second time at step 2 — minutes, on the chip."""
    if len(jax.devices()) < ndev:
        pytest.skip("needs %d devices" % ndev)
    monkeypatch.setenv("MXNET_TRAINSTEP", "1")
    monkeypatch.setenv("MXNET_TRAIN_DTYPE", dtype)
    xs, ys = _data()
    net = _make_mlp("once_%s%d_" % (dtype, ndev))
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    plane = trainplane.TrainPlane(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  tr, mesh=parallel.device_mesh(ndev))
    before = telemetry.RECOMPILES.value(site="trainplane.step")
    for k in range(3):
        plane.step(nd.array(xs[k * B:(k + 1) * B]),
                   nd.array(ys[k * B:(k + 1) * B]))
    assert plane.plane == "graph"
    assert telemetry.RECOMPILES.value(site="trainplane.step") - before == 1


def test_backend_error_propagates_trace_error_demotes(monkeypatch):
    """Demotion to the eager plane is for models that cannot be TRACED; a
    backend (XLA/Mosaic) compile or runtime error is the device saying no
    and must surface, not train quietly op-by-op."""
    monkeypatch.setenv("MXNET_TRAINSTEP", "auto")
    xs, ys = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def plane_failing_with(exc, tag):
        net = _make_mlp(tag)
        _init(net, xs)
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        plane = trainplane.TrainPlane(net, loss_fn, tr)

        def boom(*_a, **_k):
            raise exc
        monkeypatch.setattr(plane, "_prepare_graph", boom)
        return plane

    x, y = nd.array(xs[:B]), nd.array(ys[:B])
    before = sum(s["value"] for s in trainplane.FALLBACKS.series())
    refused = plane_failing_with(jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"), "refuse_")
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        refused.step(x, y)
    assert refused.plane == "undecided"
    assert sum(s["value"] for s in trainplane.FALLBACKS.series()) == before

    untraceable = plane_failing_with(
        jax.errors.ConcretizationTypeError.__new__(
            jax.errors.ConcretizationTypeError), "untrace_")
    loss = untraceable.step(x, y)
    assert untraceable.plane == "eager"
    assert np.isfinite(np.asarray(loss._data)).all()
