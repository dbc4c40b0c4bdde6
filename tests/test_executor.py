"""Executor semantics: gradients, optimizers, state, dataloaders, save/load.

Mirrors reference tests/test_transformer_ops.py's Executor+gradients pattern
with numpy as the oracle.
"""
import os

import numpy as np
import pytest

import hetu_tpu as ht


def test_gradients_linear():
    # loss = mean((x @ w - y)^2) -> dw = 2/N x^T (x @ w - y)
    rng = np.random.RandomState(0)
    xv = rng.randn(8, 3).astype(np.float32)
    yv = rng.randn(8, 2).astype(np.float32)
    wv = rng.randn(3, 2).astype(np.float32)

    x = ht.Variable(name="x", trainable=False)
    y = ht.Variable(name="y", trainable=False)
    w = ht.Variable(name="w", value=wv)
    diff = ht.matmul_op(x, w) - y
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    (gw,) = ht.gradients(loss, [w])

    ex = ht.Executor([loss, gw], ctx=ht.cpu(0))
    loss_val, gw_val = ex.run("default", feed_dict={x: xv, y: yv},
                              convert_to_numpy_ret_vals=True)
    resid = xv @ wv - yv
    np.testing.assert_allclose(loss_val, np.mean(np.sum(resid**2, 1)), rtol=1e-5)
    np.testing.assert_allclose(gw_val, 2.0 / 8 * xv.T @ resid, rtol=1e-4, atol=1e-5)


def test_sgd_training_step():
    rng = np.random.RandomState(1)
    xv = rng.randn(4, 3).astype(np.float32)
    yv = rng.randn(4, 1).astype(np.float32)
    wv = rng.randn(3, 1).astype(np.float32)

    x = ht.Variable(name="x", trainable=False)
    y = ht.Variable(name="y", trainable=False)
    w = ht.Variable(name="w", value=wv.copy())
    diff = ht.matmul_op(x, w) - y
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    opt = ht.optim.SGDOptimizer(learning_rate=0.1)
    train_op = opt.minimize(loss)

    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    ex.run("train", feed_dict={x: xv, y: yv})
    new_w = np.asarray(ex.state["params"][id(w)])
    expect = wv - 0.1 * (2.0 / 4 * xv.T @ (xv @ wv - yv))
    np.testing.assert_allclose(new_w, expect, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("opt_name", ["momentum", "nesterov", "adagrad", "adam"])
def test_optimizers_converge(opt_name):
    rng = np.random.RandomState(2)
    true_w = rng.randn(5, 1).astype(np.float32)
    xv = rng.randn(64, 5).astype(np.float32)
    yv = xv @ true_w

    x = ht.Variable(name="x", trainable=False)
    y = ht.Variable(name="y", trainable=False)
    w = ht.init.zeros((5, 1), name="w")
    diff = ht.matmul_op(x, w) - y
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    opt = {
        "momentum": lambda: ht.optim.MomentumOptimizer(0.05),
        "nesterov": lambda: ht.optim.MomentumOptimizer(0.05, nesterov=True),
        "adagrad": lambda: ht.optim.AdaGradOptimizer(0.5),
        "adam": lambda: ht.optim.AdamOptimizer(0.1),
    }[opt_name]()
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    losses = []
    for _ in range(150):
        (lv, _) = ex.run("train", feed_dict={x: xv, y: yv},
                         convert_to_numpy_ret_vals=True)
        losses.append(float(lv))
    assert losses[-1] < 1e-2, f"{opt_name} failed to converge: {losses[-5:]}"


def test_lr_scheduler_traced():
    rng = np.random.RandomState(3)
    xv = rng.randn(4, 2).astype(np.float32)
    x = ht.Variable(name="x", trainable=False)
    w = ht.init.ones((2, 1), name="w")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    sched = ht.lr.StepScheduler(0.1, step_size=2, gamma=0.5)
    opt = ht.optim.SGDOptimizer(learning_rate=sched)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    w0 = np.asarray(ex.state["params"][id(w)])
    ex.run("train", feed_dict={x: xv})
    w1 = np.asarray(ex.state["params"][id(w)])
    # lr at step 0 must be 0.1
    g = np.mean(xv, 0).reshape(2, 1) / 1.0
    np.testing.assert_allclose(w0 - w1, 0.1 * g, rtol=1e-4, atol=1e-6)


def test_dataloader_and_epoch():
    n, bs = 20, 5
    data_x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    data_y = np.ones((n, 1), dtype=np.float32)
    x = ht.dataloader_op([ht.Dataloader(data_x, bs, "train")])
    y = ht.dataloader_op([ht.Dataloader(data_y, bs, "train")])
    w = ht.init.ones((2, 1), name="w")
    diff = ht.matmul_op(x, w) - y
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    opt = ht.optim.SGDOptimizer(1e-4)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    assert ex.get_batch_num("train") == 4
    for _ in range(4):
        ex.run("train")
    assert ex.state["step"] == 4


def test_dropout_train_vs_eval():
    xv = np.ones((64, 64), dtype=np.float32)
    x = ht.Variable(name="x", trainable=False)
    w = ht.init.ones((64, 1), name="w")
    d = ht.dropout_op(x, 0.5)
    out = ht.matmul_op(d, w)
    loss = ht.reduce_mean_op(out, [0, 1])
    opt = ht.optim.SGDOptimizer(0.0)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [out, train_op], "eval": [out]}, ctx=ht.cpu(0))
    (train_out, _) = ex.run("train", feed_dict={x: xv})
    (eval_out,) = ex.run("eval", feed_dict={x: xv})
    # eval: dropout is identity
    np.testing.assert_allclose(eval_out.asnumpy(), np.full((64, 1), 64.0))
    # train: inverted dropout keeps expectation but not exact value
    assert abs(train_out.asnumpy().mean() - 64.0) > 1e-3
    assert 40.0 < train_out.asnumpy().mean() < 90.0


def test_batchnorm_state_updates():
    rng = np.random.RandomState(4)
    xv = (rng.randn(16, 3, 4, 4) * 3 + 5).astype(np.float32)
    x = ht.Variable(name="x", trainable=False)
    scale = ht.init.ones((3,), name="bn_scale")
    bias = ht.init.zeros((3,), name="bn_bias")
    bn = ht.batch_normalization_op(x, scale, bias, momentum=0.5, eps=1e-5)
    loss = ht.reduce_mean_op(bn, [0, 1, 2, 3])
    opt = ht.optim.SGDOptimizer(0.0)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [bn, train_op], "eval": [bn]}, ctx=ht.cpu(0))
    (out, _) = ex.run("train", feed_dict={x: xv})
    # train output is batch-normalized: near-zero mean per channel
    o = out.asnumpy()
    np.testing.assert_allclose(o.mean((0, 2, 3)), np.zeros(3), atol=1e-4)
    state = ex.state["op_state"][id(bn)]
    np.testing.assert_allclose(np.asarray(state["mean"]),
                               0.5 * xv.mean((0, 2, 3)), rtol=1e-4)


def test_save_load(tmp_path):
    xv = np.random.RandomState(5).randn(4, 3).astype(np.float32)
    x = ht.Variable(name="x", trainable=False)
    w = ht.init.random_normal((3, 2), stddev=1.0, name="w_saveload")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    opt = ht.optim.AdamOptimizer(0.01)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    ex.run("train", feed_dict={x: xv})
    ex.run("train", feed_dict={x: xv})
    w_after = np.asarray(ex.state["params"][id(w)])
    path = str(tmp_path / "ckpt")
    ex.save(path)
    assert os.path.exists(os.path.join(path, "w_saveload.npy"))

    # fresh executor, same graph
    ex2 = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0))
    ex2.load(path)
    np.testing.assert_allclose(np.asarray(ex2.state["params"][id(w)]), w_after)
    assert ex2.state["step"] == 2


def test_variable_value_and_fetch():
    w = ht.Variable(name="wfetch", value=np.ones((2, 2), np.float32) * 3)
    loss = ht.reduce_mean_op(w, [0, 1])
    ex = ht.Executor([loss], ctx=ht.cpu(0))
    (val,) = ex.fetch_dense_parameter_value([w])
    np.testing.assert_allclose(val.asnumpy(), 3 * np.ones((2, 2)))


def test_bf16_compute_mode():
    """dtype=bfloat16: compute runs in bf16 (MXU-rate path), master params
    and optimizer updates stay f32, loss tracks the f32 run loosely."""
    import jax.numpy as jnp
    import numpy as np
    import hetu_tpu as ht

    rng = np.random.RandomState(0)
    xv = rng.randn(32, 16).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 32)]
    wv = (rng.randn(16, 4) * 0.1).astype(np.float32)

    def build():
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y", trainable=False)
        w = ht.Variable("w", value=wv.copy())
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.5).minimize(loss)
        return x, y_, w, loss, train_op

    x, y_, w, loss, train_op = build()
    ex32 = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=3)
    l32 = [float(np.mean(ex32.run("train", feed_dict={x: xv, y_: yv},
                                  convert_to_numpy_ret_vals=True)[0]))
           for _ in range(5)]

    x, y_, w, loss, train_op = build()
    ex16 = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=3,
                       dtype=jnp.bfloat16)
    l16 = [float(np.mean(ex16.run("train", feed_dict={x: xv, y_: yv},
                                  convert_to_numpy_ret_vals=True)[0]))
           for _ in range(5)]
    # master params stay f32
    assert ex16.state["params"][id(w)].dtype == jnp.float32
    # bf16 training tracks f32 within bf16 tolerance and actually learns
    np.testing.assert_allclose(l32, l16, rtol=0.05, atol=0.02)
    assert l16[-1] < l16[0]


def test_phase_spans_in_a_capture(tmp_path):
    """The per-phase breakdown is in any jax.profiler capture (before PR 23
    an environment variable armed a ledger for it): one hetu_step a run
    call, its hetu.* phases inside it, the first call's build marked
    compiled; no switch."""
    import jax
    from conftest import read_hetu_spans
    x = ht.Variable(name="x", trainable=False)
    w = ht.Variable("wprof", value=np.ones((3, 2), np.float32))
    out = ht.matmul_op(x, w)
    ex = ht.Executor([out], ctx=ht.cpu(0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            ex.run("default", feed_dict={x: np.ones((4, 3), np.float32)})
    finally:
        jax.profiler.stop_trace()
    spans = read_hetu_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "hetu_step"]
    assert len(steps) == 3
    for lo, hi in ((s[1], s[2]) for s in steps):
        inside = [c[0] for c in spans
                  if c[0] != "hetu_step" and lo <= c[1] and c[2] <= hi]
        assert inside == ["hetu.boundary", "hetu.feed", "hetu.dl_wait",
                          "hetu.ps_pull", "hetu.build", "hetu.dispatch",
                          "hetu.prefetch", "hetu.ps_push", "hetu.poststep"]
    builds = [s for s in spans if s[0] == "hetu.build"]
    assert [b[3].get("compiled") for b in builds] == [1, None, None]
    # nothing is kept in the process when no one asked for stamps
    assert ex.subexecutors["default"].last_phases is None


def test_bf16_conv_bn_training():
    """Regression for a round-2 crash: conv under jax.grad in bf16 compute
    mode (the conv transpose rule must see matching dtypes), with
    BatchNorm running stats staying f32 (conv + BN + pool + matmul,
    Momentum: the ResNet recipe of chip_smoke.py's executor phase)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    xv = rng.randn(8, 3, 8, 8).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]

    def build():
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y", trainable=False)
        w1 = ht.Variable("w1", value=(rng.randn(8, 3, 3, 3) * 0.1).astype(np.float32))
        scale = ht.Variable("scale", value=np.ones(8, np.float32))
        bias = ht.Variable("bias", value=np.zeros(8, np.float32))
        w2 = ht.Variable("w2", value=(rng.randn(8 * 4 * 4, 4) * 0.1).astype(np.float32))
        h = ht.conv2d_op(x, w1, padding=1, stride=1)
        h = ht.batch_normalization_op(h, scale, bias)
        h = ht.relu_op(h)
        h = ht.max_pool2d_op(h, 2, 2, 0, 2)
        h = ht.array_reshape_op(h, [-1, 8 * 4 * 4])
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
        train_op = ht.optim.MomentumOptimizer(0.1).minimize(loss)
        return x, y_, scale, loss, train_op

    rng = np.random.RandomState(1)
    x, y_, scale, loss, train_op = build()
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=3,
                     dtype=jnp.bfloat16)
    losses = [float(np.mean(ex.run("train", feed_dict={x: xv, y_: yv},
                                   convert_to_numpy_ret_vals=True)[0]))
              for _ in range(8)]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # master params and BN running stats stay f32
    assert ex.state["params"][id(scale)].dtype == jnp.float32
    for st in jax.tree.leaves(ex.state["op_state"]):
        if hasattr(st, "dtype") and jnp.issubdtype(st.dtype, jnp.floating):
            assert st.dtype == jnp.float32


def test_dump_hlo_exposes_the_compiled_step(tmp_path):
    """dump_hlo returns the (stable)HLO of the whole jitted step and writes
    it to disk; the optimized stage reflects XLA's pass pipeline."""
    import hetu_tpu as ht

    x = ht.Variable(name="x", trainable=False)
    w = ht.Variable("w", value=np.eye(4, dtype=np.float32))
    out = ht.relu_op(ht.matmul_op(x, w))
    ex = ht.Executor([out], ctx=ht.cpu(0))
    ex.run("default", feed_dict={x: np.ones((2, 4), np.float32)})

    sub = ex.subexecutors["default"]
    txt = sub.dump_hlo(str(tmp_path / "step.mlir"))
    assert txt and "dot" in txt  # the matmul is in the program
    assert (tmp_path / "step.mlir").read_text() == txt
    opt = sub.dump_hlo(stage="optimized")
    assert opt and opt != txt
