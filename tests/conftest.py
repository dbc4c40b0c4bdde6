"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's local-process-cluster test strategy (SURVEY.md §4):
multi-chip behavior is validated on a virtual device mesh, no TPU pod needed.

The CPU pin is made on purpose, twice: the environment variable reaches
every child process a test spawns, and the config update holds in this
process even if jax was imported before this file ran.
"""
import os

# appended last: with duplicate flags, XLA takes the last occurrence
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# shared Executor-driving helpers for op-parity tests (used by test_ops.py
# and test_op_parity.py)
def run_graph_helper(out_node, feeds=None):
    import hetu_tpu as ht
    ex = ht.Executor([out_node], ctx=ht.cpu(0))
    (res,) = ex.run("default", feed_dict=feeds or {})
    return res.asnumpy()


def feed_helper(shape=None, val=None, seed=0, name="x"):
    import numpy as np
    import hetu_tpu as ht
    node = ht.Variable(name=name, trainable=False)
    if val is None:
        val = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return node, val


def read_hetu_spans(trace_dir):
    """The `hetu*` host spans of the newest jax.profiler capture under
    `trace_dir`, in time order: [(name, start_ns, end_ns, args)] of the
    thread that holds the most of them (shared by test_spans /
    test_executor)."""
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    best = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("hetu")]
            if len(spans) > len(best):
                best = spans
    return sorted(best, key=lambda s: (s[1], -s[2]))
