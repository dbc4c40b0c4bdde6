"""Plain float32 reference of one Wide&Deep training step (reference
examples/ctr/models/wdl_criteo.py): one shared embedding table read by 26
fields, a deep MLP 13-256-256-256 (ReLU, no biases, linear last layer) over
the dense fields, the two concatenated into one linear layer and a
sigmoid; mean binary cross-entropy (probabilities clipped at 1e-7, as the
program's loss op); one SGD step.

numpy only. It starts from parameters read out of the program: the rows
the batch touches (`rows`, in the order of the sorted unique ids `uniq`)
and the dense weights by the example's names (W1_w, W2_w, W3_w, W4).
"""
import numpy as np


def first_step(dense, rows, uniq, batch, lr):
    """(loss, rows after the step, dense weights after the step)."""
    x = batch["dense"].astype(np.float32)
    y = batch["labels"].astype(np.float32)
    ids = batch["sparse"]
    B, S = ids.shape
    W = rows.shape[1]
    w1, w2, w3, w4 = (dense[k] for k in ("W1_w", "W2_w", "W3_w", "W4"))
    pos = np.searchsorted(uniq, ids)                 # (B, S) into `rows`

    emb = rows[pos].reshape(B, S * W)
    a1 = x @ w1
    h1 = np.maximum(a1, 0)
    a2 = h1 @ w2
    h2 = np.maximum(a2, 0)
    deep = h2 @ w3
    joint = np.concatenate([emb, deep], axis=1)
    p = 1.0 / (1.0 + np.exp(-(joint @ w4)))
    pc = np.clip(p, 1e-7, 1.0 - 1e-7)
    loss = np.mean(-(y * np.log(pc) + (1.0 - y) * np.log(1.0 - pc)))

    dz = (p - y) / B                                 # d loss / d logit
    dw4 = joint.T @ dz
    djoint = dz @ w4.T
    ddeep = djoint[:, S * W:]
    dw3 = h2.T @ ddeep
    da2 = (ddeep @ w3.T) * (a2 > 0)
    dw2 = h1.T @ da2
    da1 = (da2 @ w2.T) * (a1 > 0)
    dw1 = x.T @ da1
    drows = np.zeros_like(rows)
    np.add.at(drows, pos.ravel(), djoint[:, :S * W].reshape(B * S, W))

    new_dense = {"W1_w": w1 - lr * dw1, "W2_w": w2 - lr * dw2,
                 "W3_w": w3 - lr * dw3, "W4": w4 - lr * dw4}
    return float(loss), rows - lr * drows, new_dense
