"""Distributed 1.5D GCN training (reference ``examples/gnn/run_dist.py:17-49``
+ ``tests/test_DistGCN``'s mpirun -np 8 --replication 2 configuration).

TPU-native: instead of mpirun + per-process NCCL groups, one program over a
``(gr, gc)`` device mesh; ``hetu_tpu.parallel.distgcn`` provides the 1.5D
spmm (all_gather over gr = the column-group broadcasts, psum over gc = the
row-group allreduce). Run on 8 virtual devices with:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python run_dist.py --replication 2
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), '..', '..'))

if os.environ.get("JAX_PLATFORMS", "") == "cpu" \
        and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # virtual mesh run: default to 8 devices unless the user already forced
    # a count (last duplicate flag wins, so appending would override theirs)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--num-epoch", type=int, default=30)
    ap.add_argument("--hidden-size", type=int, default=32)
    ap.add_argument("--nodes", type=int, default=256)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--learning-rate", type=float, default=0.5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from hetu_tpu.parallel import distgcn
    from gnn_model import synthetic_graph, normalize_adj, convert_to_one_hot

    n_dev = len(jax.devices())
    r = args.replication
    assert n_dev % r == 0, (n_dev, r)
    gr = n_dev // r
    mesh = Mesh(np.array(jax.devices()).reshape(gr, r), ("gr", "gc"))
    print(f"mesh: gr={gr} gc={r} on {jax.devices()[0].platform}")

    n = args.nodes - args.nodes % (gr * r)  # divisible by both axes
    rows, cols, feats, labels = synthetic_graph(n, args.classes)
    vals = normalize_adj(rows, cols, n)
    onehot = jnp.asarray(convert_to_one_hot(labels, args.classes))
    mask = jnp.asarray(
        (np.random.RandomState(1).rand(n) < 0.7).astype(np.float32))

    adj, h = distgcn.shard_gcn_inputs(mesh, rows, cols, vals, feats, n)
    rng = np.random.RandomState(0)
    ws = [jnp.asarray(rng.randn(feats.shape[1], args.hidden_size) * 0.2,
                      jnp.float32),
          jnp.asarray(rng.randn(args.hidden_size, args.classes) * 0.2,
                      jnp.float32)]

    def loss_fn(ws):
        logits = distgcn.gcn_forward(mesh, adj, h, ws, n)
        logp = jax.nn.log_softmax(logits)
        per_node = -jnp.sum(onehot * logp, axis=1)
        return jnp.mean(per_node * mask), logits

    @jax.jit
    def step(ws):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(ws)
        return loss, logits, [w - args.learning_rate * g
                              for w, g in zip(ws, grads)]

    t0 = time.time()
    for epoch in range(args.num_epoch):
        loss, logits, ws = step(ws)
        pred = np.asarray(logits).argmax(1)
        test = np.asarray(mask) == 0
        acc = float((pred[test] == labels[test]).mean())
        print(f"epoch {epoch}: loss {float(loss):.4f} test acc {acc:.3f}")
    print(f"total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
