"""wdl-criteo as the reference's job script builds it (examples/ctr/
run_hetu.py): dataloader ops, the example's own model function,
`ht.Executor` with the comm_mode the traffic file states, and for
comm_mode "Hybrid" a PS cluster (`local_cluster`) that lives and dies with
the job. Cache policy, prefetch, dtype and kernel mode stay the program's
defaults.
"""
import contextlib
import os
import sys

import numpy as np

# Agreement of the first step with the float32 reference (reference.py):
# each parameter's update error as a share of its update, both as root mean
# squares over the parameter. The Executor computes in float32, but a
# float32 matmul on the TPU runs at the default precision (one bfloat16
# pass), the reference in exact float32. Measured on the v5e (my chip runs,
# PR 22, 7 seeds): loss within 2e-7; row updates (an outer product, nothing
# summed over the batch) within 3-4e-5, float32 rounding of row + update;
# dense weight updates off by 3.7-5.2 % (their worst element by 4-12 % of
# the largest update), because their gradient is a sum over the batch of
# terms of both signs and bfloat16 products do not cancel as exact ones do
# (the CPU, in float32, reads 8e-5). The bounds sit ~4x (dense) and ~10x
# (rows) above the largest seen. An update applied twice, dropped, or scaled
# by another learning rate is off by 50-100 % and fails either bound. Rows
# the step did not touch may not move at all.
LOSS_ABS_TOL = 1e-5
ROWS_REL_TOL = 5e-4
DENSE_REL_TOL = 0.2
TABLE = "snd_order_embedding"


def build(config, traffic, seed, devices, batches, spans):
    return WdlJob(config, traffic, seed, devices, batches, spans)


def _import_models():
    """examples/ctr/models, the package the job script imports as `models`."""
    import hetu_tpu
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(hetu_tpu.__file__))), "examples", "ctr")
    if path not in sys.path:
        sys.path.insert(0, path)
    import models
    return models


class WdlJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        self._stack = contextlib.ExitStack()
        try:
            self._build(config, traffic, seed, batches, spans)
        except BaseException:
            self._stack.close()
            raise

    def _build(self, config, traffic, seed, data, spans):
        import hetu_tpu as ht
        self.config, self.traffic, self.spans = config, traffic, spans
        self.hybrid = traffic["comm_mode"] == "Hybrid"
        if config["mlp_dims"] != [256, 256, 256]:
            raise ValueError("the example's wdl_criteo fixes the deep MLP at "
                             "256-256-256")
        if self.hybrid:
            from hetu_tpu import ps
            from hetu_tpu.ps.local_cluster import local_cluster
            self._stack.enter_context(local_cluster(
                n_servers=config["ps_servers"], n_workers=1))
            self._stack.callback(ps.worker_finish)
        bs = traffic["batch_size"]
        self.items_per_step = bs
        dense = ht.dataloader_op([ht.Dataloader(data["dense"], bs, "train")])
        sparse = ht.dataloader_op(
            [ht.Dataloader(data["sparse"], bs, "train")])
        y_ = ht.dataloader_op([ht.Dataloader(data["labels"], bs, "train")])
        loss, _y, _labels, train_op = _import_models().wdl_criteo(
            dense, sparse, y_, feature_dimension=config["table_rows"],
            embedding_size=config["embedding_width"],
            learning_rate=config["learning_rate"],
            n_slots=config["sparse_fields"], n_dense=config["dense_fields"],
            stddev=config["init_stddev"])
        self.ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.tpu(0),
                              comm_mode=traffic["comm_mode"], seed=seed)
        self._stack.callback(self.ex.close)
        self._out = None
        self._first = self._first_step(data, bs)

    # -- the loop ----------------------------------------------------------
    def step(self):
        with self.spans("run_call"):
            self._out = self.ex.run("train")

    def sync(self):
        with self.spans("sync"):
            return float(np.mean(self._out[0].asnumpy()))

    def counters(self):
        if not self.hybrid:
            return {}
        return {"ps": dict(self.ex.ps_runtime.perf),
                "steps": int(self.ex.state["step"])}

    def close(self):
        self._stack.close()

    # -- reading the program's parameters ----------------------------------
    def _dense_params(self):
        return {n.name: np.asarray(self.ex.state["params"][id(n)])
                for n in self.ex.param_nodes if n.name != TABLE}

    def _rows(self, ids):
        """Current rows `ids` of the table, wherever it lives. Callers pass
        the same number of ids whatever the seed: the device gather is a
        program of that shape, found in the compile cache by every run."""
        if self.hybrid:
            rt = self.ex.ps_runtime
            rt.drain()
            p = next(p for p in rt.params.values() if p.sparse)
            dest = np.zeros((ids.size, self.config["embedding_width"]),
                            np.float32)
            with rt._rpc_lock:
                rt.comm.SparsePull(p.ps_id, ids.astype(np.int64), dest)
            rt.comm.Wait(p.ps_id)
            return dest
        import jax.numpy as jnp
        node = next(n for n in self.ex.param_nodes if n.name == TABLE)
        return np.asarray(jnp.take(self.ex.state["params"][id(node)],
                                   jnp.asarray(ids, jnp.int32), axis=0))

    def _first_step(self, data, bs):
        """Run the first step and keep what the reference needs: the batch,
        the rows it touches and 64 it does not, and the dense parameters,
        before and after."""
        batch = {k: np.asarray(v[:bs]) for k, v in data.items()}
        ids = batch["sparse"].ravel()              # batch x fields, repeats
        touched, first = np.unique(ids, return_index=True)
        rng = np.random.default_rng(0)
        others = np.setdiff1d(rng.integers(
            0, self.config["table_rows"], 4096), touched)[:64]

        def read():
            return {"rows": self._rows(ids)[first],
                    "others": self._rows(others),
                    "dense": self._dense_params()}

        before = read()
        self.step()
        loss = self.sync()
        return {"batch": batch, "touched": touched, "loss": loss,
                "before": before, "after": read()}

    # -- correct -----------------------------------------------------------
    def check(self, reference):
        f = self._first
        want_loss, want_rows, want_dense = reference.first_step(
            f["before"]["dense"], f["before"]["rows"], f["touched"],
            f["batch"], lr=self.config["learning_rate"])

        def rel(got, want, base):
            rms = lambda a: float(np.sqrt(np.mean(np.square(a))))
            return rms(got - want) / (rms(want - base) or 1.0)

        out = {"first_loss": f["loss"], "reference_loss": float(want_loss),
               "loss_abs_err": abs(f["loss"] - float(want_loss)),
               "rows_touched": int(f["touched"].size),
               "rows_rel_err": rel(f["after"]["rows"], want_rows,
                                   f["before"]["rows"]),
               "dense_rel_err": max(
                   rel(f["after"]["dense"][k], want_dense[k],
                       f["before"]["dense"][k]) for k in want_dense),
               "untouched_rows_moved": bool(np.any(
                   f["after"]["others"] != f["before"]["others"]))}
        ok = (np.isfinite(f["loss"])
              and out["loss_abs_err"] <= LOSS_ABS_TOL
              and out["rows_rel_err"] <= ROWS_REL_TOL
              and out["dense_rel_err"] <= DENSE_REL_TOL
              and not out["untouched_rows_moved"])
        if self.hybrid:
            out["accounting"] = self._update_accounting()
            ok = ok and out["accounting"]["ok"]
        out["ok"] = bool(ok)
        return out

    def _update_accounting(self):
        """The PS's guarantee (the comparison of
        hetu_tpu.chaos.check_update_accounting): every write RPC the client
        saw acknowledged was applied by a server exactly once."""
        rt = self.ex.ps_runtime
        rt.drain()
        acked = int(rt.comm.ClientStats()["pushes_ok"])
        applied = sum(int(rt.comm.ServerStats(s)["updates"])
                      for s in range(self.config["ps_servers"]))
        return {"ok": acked == applied and acked > 0,
                "client_pushes_ok": acked, "server_updates": applied}
