"""Parameter-server integration tests: a full local-process cluster
(scheduler + 2 servers + 2 workers) over loopback.

Mirrors the reference's tests/pstests/test_apis.py strategy (SURVEY.md §4.3):
all roles as local processes, config via env vars, workers cross-check
InitTensor/Push/Pull/sparse APIs against numpy oracles. Uses the ``spawn``
start method (children never touch the parent's JAX runtime — fork with JAX
threads deadlocks).
"""
import itertools
import multiprocessing as mp
import os
import queue as pyqueue
import shutil
import tempfile
import time

import numpy as np
import pytest

NITEM = 200
ITEM_LEN = 50

# Every live-cluster test of the suite draws its scheduler port here (the
# servers take port + 1 + idx). Under `-n 6 --dist loadfile` the files run
# in several processes at once, so each xdist worker owns a block of its
# own: all of them above the ports other tests hard-code (14310-19997)
# and below the OS's ephemeral range (32768), which client sockets use.
_PORT_BASE = 20000
_PORTS_PER_WORKER = 1000
_PORTS_PER_CLUSTER = 12
_MAX_WORKERS = 12


def port_blocks(xdist_worker=""):
    """Endless iterator of scheduler ports for one test process; ``port ..
    port + _PORTS_PER_CLUSTER - 1`` is that cluster's alone. `xdist_worker`
    is ``PYTEST_XDIST_WORKER`` ("gw3"; empty without xdist)."""
    index = int(xdist_worker[2:]) if xdist_worker else 0
    if not 0 <= index < _MAX_WORKERS:
        raise RuntimeError(
            f"xdist worker {xdist_worker!r}: the test ports are laid out "
            f"for at most {_MAX_WORKERS} workers")
    start = _PORT_BASE + index * _PORTS_PER_WORKER
    return itertools.cycle(range(
        start, start + _PORTS_PER_WORKER - _PORTS_PER_CLUSTER + 1,
        _PORTS_PER_CLUSTER))


_port_iter = port_blocks(os.environ.get("PYTEST_XDIST_WORKER", ""))


def _env(role, idx, port, n_workers=2, n_servers=2):
    env = {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(n_workers),
        "DMLC_NUM_SERVER": str(n_servers),
        "DMLC_ROLE": role,
        # the CPU pin, on purpose: spawned roles stay off a real TPU
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=8"),
    }
    if role == "server":
        env["SERVER_ID"] = str(idx)
        env["DMLC_PS_SERVER_URI"] = "127.0.0.1"
        env["DMLC_PS_SERVER_PORT"] = str(port + 1 + idx)
    elif role == "worker":
        env["WORKER_ID"] = str(idx)
    return env


def _run_scheduler(port, n_workers, n_servers):
    os.environ.update(_env("scheduler", 0, port, n_workers, n_servers))
    from hetu_tpu.ps import server as srv
    srv.start_scheduler_from_env()
    srv.scheduler_wait()
    srv.stop_scheduler()


def _worker_body(rank, port, n_workers, n_servers, fn, tmpdir, result_q):
    os.environ.update(_env("worker", rank, port, n_workers, n_servers))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    try:
        fn(client, rank, tmpdir)
        result_q.put((rank, "ok", None))
    except Exception:  # noqa: BLE001
        import traceback
        result_q.put((rank, "fail", traceback.format_exc()))
    finally:
        client.close()


def run_cluster(worker_fn, tmpdir="/tmp", n_workers=2, n_servers=2,
                timeout=120):
    """Spawn scheduler/servers (LIGHT subprocesses — ctypes-only, no
    hetu_tpu/jax import) and workers (spawn method, full framework);
    assert every worker body passed."""
    from hetu_tpu.ps.local_cluster import (reap_light_procs,
                                           spawn_light_role,
                                           spawn_light_server)
    ctx = mp.get_context("spawn")
    port = next(_port_iter)
    stopdir = tempfile.mkdtemp(prefix="hetups_stop_")
    stopfile = os.path.join(stopdir, "stop")
    result_q = ctx.Queue()
    infra = []
    procs = []
    results = {}
    deadline = time.time() + timeout
    try:
        # spawn INSIDE the try so a partial bootstrap still gets reaped
        infra.append(spawn_light_role(
            "scheduler", _env("scheduler", 0, port, n_workers, n_servers)))
        for s in range(n_servers):
            infra.append(spawn_light_server(
                s, _env("server", s, port, n_workers, n_servers), stopfile,
                port=str(port + 1 + s)))
        for w in range(n_workers):
            procs.append(ctx.Process(
                target=_worker_body,
                args=(w, port, n_workers, n_servers, worker_fn, str(tmpdir),
                      result_q)))
        for p in procs:
            p.start()
        # Poll instead of one blocking get so failures surface the moment
        # they happen rather than after the full timeout, and so queue.Empty
        # is reserved for the one retryable meaning: "host too slow".
        while len(results) < n_workers:
            try:
                rank, status, err = result_q.get(timeout=2)
                results[rank] = (status, err)
                if status != "ok":
                    # fail fast with the real traceback — a failed worker's
                    # peer may hang on a barrier forever, and that hang must
                    # not reclassify this failure as a timeout
                    raise AssertionError(f"worker {rank} failed:\n{err}")
                continue
            except pyqueue.Empty:
                pass
            # a worker that died without reporting (e.g. a native crash
            # _worker_body's except clause cannot catch, ANY exit code)
            dead = {i: p.exitcode for i, p in enumerate(procs)
                    if i not in results and not p.is_alive()}
            if dead:
                raise RuntimeError(
                    f"worker(s) died without reporting: "
                    f"{{rank: exitcode}} = {dead}")
            # scheduler/server crash (abnormal exit only — they run until
            # the stopfile during a healthy run)
            dead_infra = {i: p.returncode for i, p in enumerate(infra)
                          if p.poll() is not None and p.returncode != 0}
            if dead_infra:
                raise RuntimeError(
                    f"scheduler/server died: {{idx: exitcode}} = "
                    f"{dead_infra}")
            if time.time() > deadline:
                raise pyqueue.Empty
    finally:
        with open(stopfile, "w") as f:
            f.write("stop")
        for p in procs:
            p.join(timeout=20)
        for p in procs:
            if p.is_alive():
                p.terminate()
        reap_light_procs(infra, timeout=20)
        shutil.rmtree(stopdir, ignore_errors=True)
    for rank, (status, err) in sorted(results.items()):
        assert status == "ok", f"worker {rank} failed:\n{err}"
    assert len(results) == n_workers, "some workers produced no result"
    return results


# ---------------------------------------------------------------------------
# worker bodies (module-level: spawn pickles them by reference)
# ---------------------------------------------------------------------------

def _dense_ops(client, rank, tmpdir):
    client.InitTensor(0, sparse=False, length=NITEM * ITEM_LEN, width=1,
                      init_type="constant", init_a=1.5)
    out = client.Pull(0, np.empty(NITEM * ITEM_LEN, np.float32))
    client.Wait(0)
    np.testing.assert_allclose(out, 1.5, rtol=1e-6)
    client.BarrierWorker()

    # accumulate push from both workers: server does += (SGD semantics with
    # worker-side lr pre-scaling, reference PSFHandle.h:51)
    grad = np.full(NITEM * ITEM_LEN, 0.25, np.float32)
    client.Push(0, grad)
    client.Wait(0)
    client.BarrierWorker()
    out = client.Pull(0, out)
    client.Wait(0)
    np.testing.assert_allclose(out, 1.5 + 0.25 * 2, rtol=1e-6)
    client.BarrierWorker()

    # DDPushPull returns post-update values
    client.DDPushPull(0, grad, np.empty_like(out))
    client.Wait(0)
    client.BarrierWorker()
    out = client.Pull(0, out)
    client.Wait(0)
    np.testing.assert_allclose(out, 2.0 + 0.25 * 2, rtol=1e-6)
    client.BarrierWorker()
    if rank == 0:
        client.ClearOnServer(0)
    client.BarrierWorker()
    out = client.Pull(0, out)
    client.Wait(0)
    np.testing.assert_allclose(out, 0.0)


def _random_init(client, rank, tmpdir):
    # normal init happens ON the servers (reference init_on_ps,
    # initializers.py:28-39): all workers must pull identical values
    client.InitTensor(1, sparse=False, length=NITEM * ITEM_LEN, width=1,
                      init_type="normal", init_a=0.0, init_b=1.0, seed=7)
    out = client.Pull(1, np.empty(NITEM * ITEM_LEN, np.float32))
    client.Wait(1)
    assert np.std(out) > 0.5
    np.save(os.path.join(tmpdir, f"init_{rank}.npy"), out)
    client.BarrierWorker()


def _sparse_ops(client, rank, tmpdir):
    client.InitTensor(2, sparse=True, length=NITEM, width=ITEM_LEN,
                      init_type="constant", init_a=0.0)
    client.BarrierWorker()
    rng = np.random.RandomState(42 + rank)
    idx = rng.randint(0, NITEM, 64).astype(np.int64)
    vals = np.ones((64, ITEM_LEN), np.float32)
    client.SparsePush(2, idx, vals)
    client.Wait(2)
    client.BarrierWorker()

    # oracle: both workers' scatter-adds
    expect = np.zeros((NITEM, ITEM_LEN), np.float32)
    for r in range(2):
        rr = np.random.RandomState(42 + r)
        for i in rr.randint(0, NITEM, 64):
            expect[i] += 1.0
    pull_idx = np.arange(NITEM, dtype=np.int64)
    out = client.SparsePull(2, pull_idx,
                            np.empty((NITEM, ITEM_LEN), np.float32))
    client.Wait(2)
    np.testing.assert_allclose(out, expect, rtol=1e-6)
    client.BarrierWorker()

    # duplicate keys within one push accumulate (worker-side dedup sums)
    dup_idx = np.zeros(4, np.int64)
    client.SparsePush(2, dup_idx, np.ones((4, ITEM_LEN), np.float32))
    client.Wait(2)
    client.BarrierWorker()
    out1 = client.SparsePull(2, np.zeros(1, np.int64),
                             np.empty((1, ITEM_LEN), np.float32))
    client.Wait(2)
    np.testing.assert_allclose(out1[0], expect[0] + 8.0, rtol=1e-6)


def _ss_pushpull(client, rank, tmpdir):
    client.InitTensor(3, sparse=True, length=NITEM, width=ITEM_LEN,
                      init_type="constant", init_a=2.0)
    client.BarrierWorker()
    idx = np.arange(10, dtype=np.int64) + rank * 10  # disjoint per worker
    vals = np.full((10, ITEM_LEN), 0.5, np.float32)
    out = client.SSPushPull(3, idx, vals, idx,
                            np.empty((10, ITEM_LEN), np.float32))
    client.Wait(3)
    np.testing.assert_allclose(out, 2.5, rtol=1e-6)  # own push visible


def _server_optimizer(client, rank, tmpdir):
    # server-side adagrad: w -= lr * g / (sqrt(sum g^2) + eps)
    client.InitTensor(4, sparse=False, length=100, width=1,
                      init_type="constant", init_a=1.0,
                      opt_type="adagrad", lrs=(0.5, 1e-7))
    client.BarrierWorker()
    if rank == 0:
        client.Push(4, np.full(100, 2.0, np.float32))
        client.Wait(4)
    client.BarrierWorker()
    out = client.Pull(4, np.empty(100, np.float32))
    client.Wait(4)
    np.testing.assert_allclose(out, 1.0 - 0.5 * 2.0 / 2.0, rtol=1e-5)


def _save_load(client, rank, tmpdir):
    client.InitTensor(5, sparse=False, length=500, width=1,
                      init_type="uniform", init_a=-1.0, init_b=1.0, seed=3)
    before = client.Pull(5, np.empty(500, np.float32))
    client.Wait(5)
    client.BarrierWorker()  # both workers snapshot before rank 0 mutates
    if rank == 0:
        client.SaveParam(5, tmpdir)
        client.ClearOnServer(5)
    client.BarrierWorker()
    zero = client.Pull(5, np.empty(500, np.float32))
    client.Wait(5)
    np.testing.assert_allclose(zero, 0.0)
    if rank == 0:
        client.LoadParam(5, tmpdir)
    client.BarrierWorker()
    after = client.Pull(5, np.empty(500, np.float32))
    client.Wait(5)
    np.testing.assert_allclose(after, before, rtol=1e-6)


def _data_push_pull(client, rank, tmpdir):
    ids = np.array([10 + rank, 20 + rank], np.uint64)
    lens = np.array([3, 4], np.int64)
    vals = np.arange(7, dtype=np.float32) + rank * 100
    qid = client.PushData(9, ids, vals, lens)
    client.WaitData(qid)
    client.BarrierWorker()
    out = np.empty(7, np.float32)
    qid, out = client.PullData(9, ids, out, lens)
    client.WaitData(qid)
    np.testing.assert_allclose(out, vals)


def _loads_recording(client, rank, tmpdir):
    client.InitTensor(6, sparse=False, length=64, width=1,
                      init_type="constant", init_a=0.0)
    client.startRecord(tmpdir)
    client.Push(6, np.ones(64, np.float32))
    client.Wait(6)
    loads = client.getLoads()
    assert loads.get("push", 0) == 64 * 4


def _oob_row_ids(client, rank, tmpdir):
    # out-of-range embedding ids (straight from user data) must come back as
    # a clean error, not corrupt the server's heap
    client.InitTensor(9, sparse=True, length=NITEM, width=ITEM_LEN,
                      init_type="constant", init_a=0.0)
    client.BarrierWorker()
    bad = np.array([NITEM + 5], np.int64)
    vals = np.ones((1, ITEM_LEN), np.float32)
    try:
        client.SparsePush(9, bad, vals)
        client.Wait(9)
        raise AssertionError("OOB row id did not raise")
    except RuntimeError as e:
        assert "out of range" in str(e), e
    client.BarrierWorker()
    # the server survived and the table is untouched
    idx = np.arange(NITEM, dtype=np.int64)
    out = client.SparsePull(9, idx, np.empty((NITEM, ITEM_LEN), np.float32))
    client.Wait(9)
    np.testing.assert_allclose(out, 0.0)


def _exits_without_reporting(client, rank, tmpdir):
    os._exit(3)   # simulates a native crash: no result ever enqueued


# ---------------------------------------------------------------------------

def test_xdist_workers_never_share_a_port():
    """Two workers drawing at once, each far past its block's wrap, never
    hold the same port in ``port .. port + n_servers``."""
    owned = []
    for worker in ["", "gw1", "gw5", f"gw{_MAX_WORKERS - 1}"]:
        draws = list(itertools.islice(port_blocks(worker), 200))
        ports = {p + i for p in draws for i in range(_PORTS_PER_CLUSTER)}
        # inside one worker a cluster's range meets no other cluster's
        assert len(ports) == len(set(draws)) * _PORTS_PER_CLUSTER
        assert len(set(draws)) >= 58        # the suite's draws, run serially
        assert 19997 < min(ports) and max(ports) < 32768
        owned.append(ports)
    assert next(port_blocks("gw0")) == next(port_blocks(""))
    for i, a in enumerate(owned):
        for b in owned[i + 1:]:
            assert not a & b
    with pytest.raises(RuntimeError, match="at most"):
        port_blocks(f"gw{_MAX_WORKERS}")


def test_ps_dense_ops(tmp_path):
    run_cluster(_dense_ops, tmp_path)


def test_dead_worker_is_not_a_timeout(tmp_path):
    # a worker that dies without reporting must surface as the distinct
    # dead-worker RuntimeError (never retried by callers), not as the
    # retryable slow-host queue.Empty
    import pytest
    # generous deadline: on an oversubscribed host the worker needs time to
    # even START before it can die; what's under test is that its death is
    # CLASSIFIED as the dead-worker error, never the retryable queue.Empty
    # (observed flaking at timeout=20 under concurrent torch compiles)
    with pytest.raises(RuntimeError, match="died without reporting"):
        run_cluster(_exits_without_reporting, tmp_path, n_workers=1,
                    timeout=90)


def test_ps_oob_row_ids(tmp_path):
    run_cluster(_oob_row_ids, tmp_path)


def test_ps_random_init_consistency(tmp_path):
    run_cluster(_random_init, tmp_path)
    a = np.load(os.path.join(tmp_path, "init_0.npy"))
    b = np.load(os.path.join(tmp_path, "init_1.npy"))
    np.testing.assert_allclose(a, b)


def test_ps_sparse_ops(tmp_path):
    run_cluster(_sparse_ops, tmp_path)


def test_ps_ss_pushpull(tmp_path):
    run_cluster(_ss_pushpull, tmp_path)


def test_ps_server_optimizer(tmp_path):
    run_cluster(_server_optimizer, tmp_path)


def test_ps_save_load(tmp_path):
    run_cluster(_save_load, tmp_path)


def test_ps_data_push_pull(tmp_path):
    run_cluster(_data_push_pull, tmp_path)


def test_ps_loads_recording(tmp_path):
    run_cluster(_loads_recording, tmp_path)
