"""Criteo-shaped CTR samples: `dense_fields` standard-normal features,
`sparse_fields` categorical ids drawn zipf (exponent `zipf_exponent`) inside
each field's own contiguous range of one shared table, and a label from a
seeded logistic teacher over both, so the loss can fall. `batches` x
`batch_size` samples, which the job's dataloaders cycle.
"""
import numpy as np


def slot_ranges(rows, n_slots):
    """[start, end) of each field's ids in the shared table."""
    edges = np.linspace(0, rows, n_slots + 1).astype(np.int64)
    return edges[:-1], edges[1:]


def generate(traffic, config, seed):
    rng = np.random.default_rng([int(seed), 0x637472])
    rows, n_slots = int(config["table_rows"]), int(config["sparse_fields"])
    n_dense = int(config["dense_fields"])
    n = int(traffic["batches"]) * int(traffic["batch_size"])
    dense = rng.standard_normal((n, n_dense), dtype=np.float32)
    starts, ends = slot_ranges(rows, n_slots)
    sparse = np.empty((n, n_slots), np.int64)
    # one zipf law for the widest field; a narrower one redraws by modulo,
    # which keeps the head of the distribution where it was
    widest = int((ends - starts).max())
    cdf = np.cumsum(np.arange(1, widest + 1, dtype=np.float64)
                    ** -float(traffic["zipf_exponent"]))
    cdf /= cdf[-1]
    for s in range(n_slots):
        rank = np.searchsorted(cdf, rng.random(n))
        sparse[:, s] = starts[s] + rank % (ends[s] - starts[s])
    # teacher: one weight a dense feature, one seeded scalar a table row
    w_dense = rng.standard_normal(n_dense).astype(np.float32) * 0.5
    row_key = np.random.default_rng([int(seed), 0x74656163])
    w_rows = row_key.standard_normal(rows, dtype=np.float32) * 0.5
    logit = dense @ w_dense + w_rows[sparse].sum(axis=1) / np.sqrt(n_slots)
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))
              ).astype(np.float32).reshape(n, 1)
    return {"dense": dense, "sparse": sparse.astype(np.int32),
            "labels": labels}
