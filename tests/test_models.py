"""Model-zoo smoke tests: every examples/cnn model builds, trains two steps,
and produces a finite decreasing-capable loss (reference runs these via
examples/cnn/scripts/*.sh)."""
import os
import sys

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.utils import import_example_models as _import_example_models


models = None


def setup_module():
    global models
    models = _import_example_models("cnn")


def _train_two_steps(model_fn, x_shape, num_class=10, lr=0.01, **kwargs):
    rng = np.random.RandomState(0)
    xv = rng.randn(8, *x_shape).astype(np.float32)
    yv = np.eye(num_class, dtype=np.float32)[rng.randint(0, num_class, 8)]
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    loss, y = model_fn(x, y_, num_class, **kwargs)
    opt = ht.optim.SGDOptimizer(lr)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, y, train_op]}, ctx=ht.cpu(0))
    w_node = ex.param_nodes[0]
    w_before = np.asarray(ex.state["params"][id(w_node)]).copy()
    l0 = float(ex.run("train", feed_dict={x: xv, y_: yv})[0].asnumpy())
    l1 = float(ex.run("train", feed_dict={x: xv, y_: yv})[0].asnumpy())
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    w_after = np.asarray(ex.state["params"][id(w_node)])
    assert not np.allclose(w_before, w_after), "parameters did not update"
    return l0, l1


def test_mlp():
    _train_two_steps(models.mlp, (3072,), input_dim=3072)


def test_logreg():
    _train_two_steps(models.logreg, (784,), input_dim=784)


def test_cnn_3_layers():
    _train_two_steps(models.cnn_3_layers, (1, 28, 28))


def test_lenet():
    _train_two_steps(models.lenet, (1, 28, 28))


def test_alexnet():
    _train_two_steps(models.alexnet, (3, 32, 32), lr=1e-4)


def test_resnet18():
    _train_two_steps(models.resnet18, (3, 32, 32))


@pytest.mark.slow
def test_resnet34():
    _train_two_steps(models.resnet34, (3, 32, 32))


@pytest.mark.slow
def test_vgg16():
    _train_two_steps(models.vgg16, (3, 32, 32))


def test_rnn():
    _train_two_steps(models.rnn, (784,))


def test_lstm():
    _train_two_steps(models.lstm, (784,))


def test_vit():
    l0, l1 = _train_two_steps(models.vit, (3, 32, 32), lr=1e-3, batch=8)
    assert l1 < l0 * 1.5  # attention model is stable from step one
