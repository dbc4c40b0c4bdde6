"""CTR/rec model-zoo tests (reference examples/ctr convergence scripts,
SURVEY §4.7): every model builds, trains a few steps locally, loss is finite
and decreasing on the synthetic task; WDL-Criteo also trains under
comm_mode='Hybrid' against a live PS cluster."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "rec"))

from test_ps import run_cluster


from hetu_tpu.utils import import_example_models as _import_example_models


DIM = 500  # small feature dimension for synthetic runs


def _train_criteo_model(model_name, steps=20, **kwargs):
    import hetu_tpu as ht
    models = _import_example_models("ctr")
    load_criteo_data = models.load_data.load_criteo_data

    (tr_dense, tr_sparse, tr_y), _ = load_criteo_data(
        feature_dimension=DIM, n_train=steps * 32, n_test=64)
    dense = ht.dataloader_op([ht.Dataloader(tr_dense, 32, "train")])
    sparse = ht.dataloader_op([ht.Dataloader(tr_sparse, 32, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(tr_y, 32, "train")])
    model_fn = getattr(models, model_name)
    loss, y, labels, train_op = model_fn(dense, sparse, y_,
                                         feature_dimension=DIM,
                                         embedding_size=16, **kwargs)
    # explicit seed: the default comes from numpy's global RNG, making
    # convergence assertions depend on which tests ran earlier
    ex = ht.Executor({"train": [loss, y, labels, train_op]}, ctx=ht.cpu(0),
                     seed=42)
    losses = []
    for _ in range(steps):
        out = ex.run("train", convert_to_numpy_ret_vals=True)
        losses.append(float(np.mean(out[0])))
    assert np.all(np.isfinite(losses)), losses
    return losses


# wdl_criteo's reference-scale 0.01 inits vanish through its 3-layer MLP
# (activations shrink ~100x by the output); near-Xavier stddev + a larger lr
# make 30-step convergence observable without changing the model defaults
_TRAIN_KWARGS = {"wdl_criteo": dict(stddev=0.06, learning_rate=0.05)}


@pytest.mark.parametrize("model_name", ["wdl_criteo", "dfm_criteo",
                                        "dcn_criteo", "dc_criteo"])
def test_criteo_model_trains(model_name):
    losses = _train_criteo_model(model_name, steps=30,
                                 **_TRAIN_KWARGS.get(model_name, {}))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), (
        model_name, losses[:5], losses[-5:])


def test_wdl_adult_trains():
    import hetu_tpu as ht
    models = _import_example_models("ctr")
    load_adult_data = models.load_data.load_adult_data

    (tr_deep, tr_wide, tr_y), _ = load_adult_data(n_train=640, n_test=64)
    X_deep = [ht.dataloader_op([ht.Dataloader(tr_deep[i], 32, "train")])
              for i in range(12)]
    X_wide = ht.dataloader_op([ht.Dataloader(tr_wide, 32, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(tr_y, 32, "train")])
    loss, y, labels, train_op = models.wdl_adult(X_deep, X_wide, y_)
    ex = ht.Executor({"train": [loss, y, labels, train_op]}, ctx=ht.cpu(0),
                     seed=42)
    losses = [float(np.mean(ex.run("train", convert_to_numpy_ret_vals=True)[0]))
              for _ in range(20)]
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_ncf_trains():
    import hetu_tpu as ht
    from hetu_ncf import neural_mf
    from movielens import getdata

    users, items, labels, nu, ni = getdata(num_users=100, num_items=200,
                                           n_pos=2000)
    user_in = ht.dataloader_op([ht.Dataloader(users, 256, "train")])
    item_in = ht.dataloader_op([ht.Dataloader(items, 256, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(labels, 256, "train")])
    # stddev raised for test speed: reference-scale 0.01 inits keep early
    # logits ~1e-4, needing thousands of batches before loss visibly moves
    loss, y, train_op = neural_mf(user_in, item_in, y_, nu, ni,
                                  learning_rate=0.3, embed_stddev=0.3)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=42)
    n = ex.get_batch_num("train")
    losses = []
    for _ in range(4):  # NCF needs a few epochs before the factors separate
        for _ in range(n):
            losses.append(float(np.mean(
                ex.run("train", convert_to_numpy_ret_vals=True)[0])))
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.01


def _wdl_hybrid_worker(client, rank, tmpdir):
    import hetu_tpu as ht
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "ctr"))
    import models
    from models.load_data import load_criteo_data

    (tr_dense, tr_sparse, tr_y), _ = load_criteo_data(
        feature_dimension=DIM, n_train=640, n_test=64, seed=rank)
    dense = ht.dataloader_op([ht.Dataloader(tr_dense, 32, "train")])
    sparse = ht.dataloader_op([ht.Dataloader(tr_sparse, 32, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(tr_y, 32, "train")])
    loss, y, labels, train_op = models.wdl_criteo(
        dense, sparse, y_, feature_dimension=DIM, embedding_size=16)
    ex = ht.Executor({"train": [loss, y, labels, train_op]}, ctx=ht.cpu(0),
                     comm_mode="Hybrid", seed=42)
    losses = [float(np.mean(ex.run("train", convert_to_numpy_ret_vals=True)[0]))
              for _ in range(20)]
    assert np.all(np.isfinite(losses)), losses


def test_wdl_criteo_hybrid_ps(tmp_path):
    run_cluster(_wdl_hybrid_worker, tmp_path, n_workers=2, timeout=300)
