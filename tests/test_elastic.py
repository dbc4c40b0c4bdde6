"""hetu-elastic: live worker/PS membership changes (docs/FAULT_TOLERANCE.md
"Elastic membership").

Layers under test, cheapest first: the pure accounting math (v2 shard IO,
key-range repartition, exactly-once era partitions), the scheduler's
two-phase resize protocol over raw sockets, stale-epoch rejection at the
server, live key-range migration onto a joining server, and the end-to-end
scale-down / scale-up worlds with exact sample accounting (multi-process
PSClient workers; the Executor integration rides test_elastic_executor).
"""
import multiprocessing as mp
import os
import queue as pyqueue
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

from hetu_tpu import elastic
from test_ps import _port_iter


# ---------------------------------------------------------------------------
# pure accounting: v2 shard IO + key-range repartition
# ---------------------------------------------------------------------------

def _mk_sparse_shard(rows, width, otype, seed, row0=0):
    rng = np.random.RandomState(seed)
    nslots = elastic._SLOT_COUNTS[otype]
    return {"kind": 1, "rows": rows, "len": rows * width, "width": width,
            "otype": otype, "step": 7, "lrs": np.asarray([0.1, 0.9, 0.999,
                                                          1e-7], np.float32),
            "data": rng.randn(rows * width).astype(np.float32),
            "accum": (rng.randn(rows * width).astype(np.float32)
                      if nslots >= 1 else np.empty(0, np.float32)),
            "accum2": (rng.randn(rows * width).astype(np.float32)
                       if nslots >= 2 else np.empty(0, np.float32)),
            "versions": np.arange(row0, row0 + rows, dtype=np.int64)}


def test_v2_shard_roundtrip(tmp_path):
    sh = _mk_sparse_shard(10, 4, otype=4, seed=0)
    path = str(tmp_path / "param_3_shard0.bin")
    elastic.write_v2_shard(path, sh)
    back = elastic.read_v2_shard(path)
    for k in ("kind", "rows", "len", "width", "otype", "step"):
        assert back[k] == sh[k], k
    for k in ("lrs", "data", "accum", "accum2", "versions"):
        np.testing.assert_array_equal(back[k], sh[k])


def test_repartition_sparse_rows_move_with_slots():
    # 2 -> 3 shards of a 10-row Adam table: every row's data/m/v/version
    # must land on its new owner bit-for-bit
    width = 4
    a = _mk_sparse_shard(5, width, 4, seed=1, row0=0)
    b = _mk_sparse_shard(5, width, 4, seed=2, row0=5)
    out = elastic.repartition_key([a, b], 3)
    full = {k: np.concatenate([a[k], b[k]])
            for k in ("data", "accum", "accum2", "versions")}
    # worker.h row_range(10, s) with S=3: [0,3), [3,6), [6,10)
    bounds = [(0, 3), (3, 6), (6, 10)]
    assert [s["rows"] for s in out] == [3, 3, 4]
    for sh, (lo, hi) in zip(out, bounds):
        np.testing.assert_array_equal(sh["data"],
                                      full["data"][lo * width:hi * width])
        np.testing.assert_array_equal(sh["accum"],
                                      full["accum"][lo * width:hi * width])
        np.testing.assert_array_equal(sh["accum2"],
                                      full["accum2"][lo * width:hi * width])
        np.testing.assert_array_equal(sh["versions"], full["versions"][lo:hi])
        assert sh["step"] == 7


def test_repartition_dense_formula_matches_worker_partitioner():
    # dense 2 -> 3: new shard lengths must follow dense_range exactly
    total = 103
    full = np.arange(total, dtype=np.float32)
    shards = []
    for lo, hi in elastic._range_split(total, 2):
        shards.append({"kind": 0, "rows": 0, "len": hi - lo, "width": 1,
                       "otype": 0, "step": 0,
                       "lrs": np.asarray([0.1], np.float32),
                       "data": full[lo:hi],
                       "accum": np.empty(0, np.float32),
                       "accum2": np.empty(0, np.float32),
                       "versions": np.empty(0, np.int64)})
    out = elastic.repartition_key(shards, 3)
    for sh, (lo, hi) in zip(out, elastic._range_split(total, 3)):
        assert sh["len"] == hi - lo
        np.testing.assert_array_equal(sh["data"], full[lo:hi])


# ---------------------------------------------------------------------------
# exactly-once era accounting
# ---------------------------------------------------------------------------

def test_era_partitions_exactly_once_across_resizes():
    # world {0,1} from step 0; worker 1 leaves (progress 5) while worker 0
    # drains at step 7; later worker 2 joins (assigned start 11) while
    # worker 0 drains at step 9. Every sample is consumed at most once and
    # the final chunks cover exactly the unconsumed rest.
    n, bs = 960, 4
    eras = [
        {"version": 1, "n_workers": 2, "n_servers": 1,
         "members": [0, 1], "start_steps": [0, 0], "end_steps": [7, 5]},
        {"version": 2, "n_workers": 1, "n_servers": 1,
         "members": [0], "start_steps": [7], "end_steps": [9]},
        {"version": 3, "n_workers": 2, "n_servers": 2,
         "members": [0, 2], "start_steps": [9, 11], "end_steps": [-1, -1]},
    ]
    chunks, tail = elastic.era_partitions(n, bs, eras)
    assert len(chunks) == 2
    # consumed so far: era0 = 7 and 5 batches; era1 = 2 batches
    consumed = elastic.consumed_samples(
        n, bs, eras[:2] + [dict(eras[2])], {0: 9, 2: 11})
    everything = np.concatenate([consumed, *chunks, tail])
    assert everything.size == n
    assert np.unique(everything).size == n  # disjoint AND complete
    assert consumed.size == (7 + 5 + 2) * bs


def test_era_partitions_era0_matches_init_states_split():
    """The launch era's chunks must follow Dataloader.init_states'
    ``n // nrank`` split (that IS how era-0 data was sharded), not the
    batch-aligned bounds later eras use — with a non-divisible dataset the
    two formulas disagree and mixing them double-consumes the straddle."""
    from hetu_tpu.dataloader import Dataloader
    n, bs, m = 110, 10, 2
    eras = [
        {"version": 1, "members": [0, 1], "start_steps": [0, 0],
         "end_steps": [3, 2]},
        {"version": 2, "members": [0], "start_steps": [3],
         "end_steps": [-1]},
    ]
    chunks, tail = elastic.era_partitions(n, bs, eras)
    # what the two loaders ACTUALLY consumed in era 0 (init_states split)
    raw = np.arange(n, dtype=np.float32).reshape(n, 1)
    consumed = []
    for rank, steps in ((0, 3), (1, 2)):
        dl = Dataloader(raw, bs, name="t")
        dl.init_states(rank, m)
        consumed += [dl.get_arr().ravel().astype(np.int64)
                     for _ in range(steps)]
    everything = np.concatenate(consumed + chunks + [tail])
    assert everything.size == n
    assert np.unique(everything).size == n, \
        "era-0 accounting disagrees with init_states' actual split"


def test_era_partitions_epoch_wrap_falls_back():
    eras = [{"version": 1, "members": [0, 1], "start_steps": [0, 0],
             "end_steps": [100, 100]},       # 100 batches >> per-chunk
            {"version": 2, "members": [0], "start_steps": [100],
             "end_steps": [-1]}]
    assert elastic.era_partitions(64, 4, eras) is None


def test_dataloader_elastic_partition():
    from hetu_tpu.dataloader import Dataloader
    raw = np.arange(40, dtype=np.float32).reshape(40, 1)
    dl = Dataloader(raw, batch_size=2, name="train")
    dl.init_states(0, 2)
    for _ in range(3):
        dl.get_arr()
    idx = np.arange(25, 33)
    dl.load_elastic_partition(idx)
    assert dl.batch_num == 4
    got = np.concatenate([dl.get_arr().ravel() for _ in range(4)])
    np.testing.assert_array_equal(got, np.arange(25, 33, dtype=np.float32))
    # state_dict/load_state_dict keep working on the new partition
    sd = dl.state_dict()
    dl2 = Dataloader(raw, batch_size=2, name="train")
    dl2.load_elastic_partition(idx)
    dl2.load_state_dict(sd)
    np.testing.assert_array_equal(dl2.get_arr(), dl.get_arr())


# ---------------------------------------------------------------------------
# satellites: typed scheduler error, fault kinds, scale policy
# ---------------------------------------------------------------------------

def test_query_servers_scheduler_unreachable():
    from hetu_tpu.ps.supervisor import SchedulerUnreachable, query_servers
    port = next(_port_iter)  # nothing listens here
    with pytest.raises(SchedulerUnreachable) as ei:
        query_servers("127.0.0.1", port, timeout=0.3)
    assert f"127.0.0.1:{port}" in str(ei.value)
    # still an OSError so PSSupervisor._poll_once keeps polling through it
    assert isinstance(ei.value, OSError)


def test_fault_injector_elastic_kinds(monkeypatch):
    from hetu_tpu.resilience import FaultInjector
    fi = FaultInjector("worker_lost@5:1,ps_join@7")
    assert fi.entries[0]["kind"] == "worker_lost"
    assert fi.entries[0]["arg"] == 1.0
    assert fi.entries[1] == {"kind": "ps_join", "step": 7, "arg": None,
                             "fired": False}
    # gated exactly like every destructive kind
    monkeypatch.delenv("HETU_TEST_MODE", raising=False)
    monkeypatch.setenv("HETU_FAULT_SPEC", "worker_lost@1")
    assert FaultInjector.from_env() is None
    # worker_lost with a NON-matching rank filter is consumed, not fired
    monkeypatch.setenv("WORKER_ID", "0")
    fi = FaultInjector("worker_lost@2:1")
    fi.inject_host(2)  # must not SIGKILL this process
    assert fi.entries[0]["fired"]


def test_scale_policy_recommends_growth():
    pol = elastic.ScalePolicy(max_servers=3, apply_ms_hi=1.0,
                              req_rate_hi=100.0, sustain=2, cooldown_s=0.0)
    mk = lambda req, ns, ap: [[0, 0, -1, 0, 1, req, ns, ap, -1, 0]]
    t = 100.0
    assert pol.observe(mk(0, 0, 0), now=t) is None          # no baseline
    # hot: 1000 reqs/s between polls
    assert pol.observe(mk(1000, 0, 0), now=t + 1) is None    # sustain 1/2
    d = pol.observe(mk(2000, 0, 0), now=t + 2)               # sustain 2/2
    assert d == {"action": "grow_server", "n_servers": 2}
    # at max_servers the policy stays quiet
    pol2 = elastic.ScalePolicy(max_servers=1, req_rate_hi=100.0, sustain=1,
                               cooldown_s=0.0)
    pol2.observe(mk(0, 0, 0), now=t)
    assert pol2.observe(mk(1000, 0, 0), now=t + 1) is None


# ---------------------------------------------------------------------------
# live-cluster helpers
# ---------------------------------------------------------------------------

def _env(role, idx, port, n_workers, n_servers):
    env = {"DMLC_PS_ROOT_URI": "127.0.0.1",
           "DMLC_PS_ROOT_PORT": str(port),
           "DMLC_NUM_WORKER": str(n_workers),
           "DMLC_NUM_SERVER": str(n_servers),
           "DMLC_ROLE": role,
           "JAX_PLATFORMS": "cpu"}
    if role == "server":
        env.update({"SERVER_ID": str(idx), "DMLC_PS_SERVER_URI": "127.0.0.1",
                    "DMLC_PS_SERVER_PORT": "0"})
    elif role == "worker":
        env["WORKER_ID"] = str(idx)
    return env


class _Cluster:
    """scheduler + N light servers; workers are the caller's business."""

    def __init__(self, n_workers, n_servers):
        from hetu_tpu.ps.local_cluster import (spawn_light_role,
                                               spawn_light_server)
        self.port = next(_port_iter)
        self.n_workers, self.n_servers = n_workers, n_servers
        self.stopdir = tempfile.mkdtemp(prefix="hetu_el_stop_")
        self.stopfile = os.path.join(self.stopdir, "stop")
        self.infra = [spawn_light_role(
            "scheduler", _env("scheduler", 0, self.port, n_workers,
                              n_servers))]
        for s in range(n_servers):
            self.infra.append(spawn_light_server(
                s, _env("server", s, self.port, n_workers, n_servers),
                self.stopfile))

    def spawn_server(self, sid, n_servers_new):
        from hetu_tpu.ps.local_cluster import spawn_light_server
        p = spawn_light_server(
            sid, _env("server", sid, self.port, self.n_workers,
                      n_servers_new), self.stopfile)
        self.infra.append(p)
        return p

    def checkout_worker(self, rank):
        """Identity-tagged kShutdown for a raw-socket fake worker, so the
        scheduler's teardown wait completes instead of timing out."""
        try:
            with _connect_retry(self.port, deadline_s=2) as s:
                s.sendall(elastic._MSG_HDR.pack(3, 0, 0, 1, 0, -1, 0)
                          + elastic._arg_i32([1, rank]))
        except OSError:
            pass

    def close(self, worker_ranks=()):
        from hetu_tpu.ps.local_cluster import reap_light_procs
        for r in worker_ranks:
            self.checkout_worker(r)
        with open(self.stopfile, "w") as f:
            f.write("stop")
        reap_light_procs(self.infra, timeout=10)
        shutil.rmtree(self.stopdir, ignore_errors=True)


def _connect_retry(port, deadline_s=30.0):
    import socket
    deadline = time.time() + deadline_s
    while True:
        try:
            return socket.create_connection(("127.0.0.1", port), timeout=30)
        except OSError:
            if time.time() > deadline:
                raise
            time.sleep(0.1)  # the light scheduler is still booting


def _register_fake_worker(port, rank, results):
    """kRegister over a raw socket (no native lib): makes the scheduler's
    initial assembly complete so the resize protocol can be driven from
    plain sockets."""
    with _connect_retry(port) as s:
        s.settimeout(30)
        meta = elastic._arg_i32([1, rank, 0])
        host = elastic._arg_str("127.0.0.1")
        s.sendall(elastic._MSG_HDR.pack(0, 0, 0, 2, 0, -1, 0) + meta + host)
        head = elastic._MSG_HDR.unpack(
            elastic._recv_exact(s, elastic._MSG_HDR.size))
        for _ in range(head[3]):
            _, _, n = elastic._ARG_HDR.unpack(
                elastic._recv_exact(s, elastic._ARG_HDR.size))
            elastic._recv_exact(s, n)
    results[rank] = True


def test_resize_protocol_two_phase():
    """Propose/drain/finish against a real scheduler + server, with fake
    raw-socket workers: capacity grows at propose, the drain barrier parks
    committers until finish, the committed world carries per-member step
    accounting, and the log records the era history."""
    cl = _Cluster(n_workers=2, n_servers=1)
    try:
        regs = {}
        ths = [threading.Thread(target=_register_fake_worker,
                                args=(cl.port, r, regs)) for r in (0, 1)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=30)
        assert regs == {0: True, 1: True}
        st = elastic.resize_state("127.0.0.1", cl.port)
        assert st["world_version"] == 1 and st["pending_version"] == 0
        assert st["members"] == [0, 1]

        ver = elastic.propose_resize("127.0.0.1", cl.port, 2, 2)
        assert ver == 2
        # idempotent re-propose; conflicting proposal is an error
        assert elastic.propose_resize("127.0.0.1", cl.port, 2, 2) == 2
        with pytest.raises(RuntimeError, match="pending"):
            elastic.propose_resize("127.0.0.1", cl.port, 3, 2)
        st = elastic.resize_state("127.0.0.1", cl.port)
        assert st["pending_version"] == 2 and st["drain_needed"] == 2
        assert not st["new_servers_ready"]
        cl.spawn_server(1, 2)
        deadline = time.time() + 30
        while not elastic.resize_state("127.0.0.1",
                                       cl.port)["new_servers_ready"]:
            assert time.time() < deadline, "joining server never registered"
            time.sleep(0.05)

        # two committers drain at DIFFERENT steps and park until finish
        worlds = {}

        def commit(rank, step):
            worlds[rank] = elastic.commit_resize("127.0.0.1", cl.port,
                                                 rank, step)
        t0 = threading.Thread(target=commit, args=(0, 7))
        t1 = threading.Thread(target=commit, args=(1, 5))
        t0.start()
        t1.start()
        deadline = time.time() + 30
        while elastic.resize_state("127.0.0.1", cl.port)["drain_count"] < 2:
            assert time.time() < deadline, "drain barrier never filled"
            time.sleep(0.05)
        assert t0.is_alive() and t1.is_alive()  # parked, not returned
        assert elastic.finish_resize("127.0.0.1", cl.port) == 2
        t0.join(timeout=30)
        t1.join(timeout=30)
        assert worlds[0]["world_version"] == 2
        assert worlds[0]["members"] == [0, 1]
        assert worlds[0]["n_servers"] == 2
        assert worlds[0]["dp_rank"] == 0 and worlds[1]["dp_rank"] == 1

        log = elastic.resize_log("127.0.0.1", cl.port)
        assert len(log) == 2
        assert log[0]["members"] == [0, 1]
        assert log[0]["start_steps"] == [0, 0]
        assert log[0]["end_steps"] == [7, 5]   # per-member drain steps
        assert log[1]["members"] == [0, 1]
        assert log[1]["start_steps"] == [7, 5]
        assert log[1]["end_steps"] == [-1, -1]  # era still open

        # a commit with NO pending resize returns immediately
        w = elastic.commit_resize("127.0.0.1", cl.port, 0, 9, timeout=10)
        assert w["world_version"] == 2
    finally:
        cl.close(worker_ranks=(0, 1))


def test_resize_abort_releases_workers():
    cl = _Cluster(n_workers=1, n_servers=1)
    try:
        regs = {}
        _register_fake_worker(cl.port, 0, regs)
        assert elastic.propose_resize("127.0.0.1", cl.port, 1, 2) == 2
        out = {}

        def commit():
            out["w"] = elastic.commit_resize("127.0.0.1", cl.port, 0, 3)
        t = threading.Thread(target=commit)
        t.start()
        deadline = time.time() + 30
        while elastic.resize_state("127.0.0.1", cl.port)["drain_count"] < 1:
            assert time.time() < deadline
            time.sleep(0.05)
        # coordinator gives up (e.g. the joining server never came): abort
        assert elastic.finish_resize("127.0.0.1", cl.port, abort=True) == 1
        t.join(timeout=30)
        assert out["w"]["world_version"] == 1   # world unchanged
        st = elastic.resize_state("127.0.0.1", cl.port)
        assert st["pending_version"] == 0 and st["n_servers"] == 1
    finally:
        cl.close(worker_ranks=(0,))


def test_snapshot_epochs_count_only_tagged_aborts():
    """Regression: the scheduler's snapshot_epochs counter advances ONLY
    on hetusave's snapshot-tagged abort (sent after its job manifest
    committed) — an identical-world resize aborted for any other reason
    (drain timeout, failed migration, a snapshot that died pre-commit)
    must never be miscounted as a completed coordinated epoch."""
    cl = _Cluster(n_workers=1, n_servers=1)
    try:
        regs = {}
        _register_fake_worker(cl.port, 0, regs)

        def park_then_abort(**abort_kw):
            out = {}

            def commit():
                out["w"] = elastic.commit_resize("127.0.0.1", cl.port, 0, 3)

            t = threading.Thread(target=commit)
            t.start()
            deadline = time.time() + 30
            while elastic.resize_state("127.0.0.1",
                                       cl.port)["drain_count"] < 1:
                assert time.time() < deadline
                time.sleep(0.05)
            elastic.finish_resize("127.0.0.1", cl.port, abort=True,
                                  **abort_kw)
            t.join(timeout=30)
            assert out["w"]["world_version"] == 1

        def epochs():
            return elastic.resize_state("127.0.0.1",
                                        cl.port)["snapshot_epochs"]

        assert epochs() == 0
        # identical-world propose aborted UNTAGGED (the failed-snapshot /
        # drain-timeout shape): not a completed epoch
        elastic.propose_resize("127.0.0.1", cl.port, 1, 1)
        park_then_abort()
        assert epochs() == 0
        # hetusave's post-commit tagged release: exactly one epoch
        elastic.propose_resize("127.0.0.1", cl.port, 1, 1)
        park_then_abort(snapshot=True)
        assert epochs() == 1
    finally:
        cl.close(worker_ranks=(0,))


# ---------------------------------------------------------------------------
# multi-process worker bodies (module level: spawn pickles by reference)
# ---------------------------------------------------------------------------

N_SAMPLES = 96
BATCH = 4
PLEN = 4


def _worker_env(rank, port, n_workers, n_servers):
    env = _env("worker", rank, port, n_workers, n_servers)
    env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    return env


def _chunk_batches(chunk, start):
    """Sequential batches of a partition from local batch cursor `start`."""
    nb = chunk.size // BATCH
    for i in range(start, nb):
        yield chunk[i * BATCH:(i + 1) * BATCH]


def _survivor_body(rank, port, q):
    """Scale-down survivor: consumes 6 batches, waits for the resize, then
    consumes everything that remains. Pushes grad = ones(PLEN)*sum(batch)
    under server-side SGD(+=), so the final param value IS the sample-sum
    ledger."""
    os.environ.update(_worker_env(rank, port, 2, 1))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    try:
        client.SetWorldVersion(1)
        client.InitTensor(0, sparse=False, length=PLEN, width=1,
                          init_type="constant", init_a=0.0, opt_type="sgd",
                          lrs=(1.0,))
        client.BarrierWorker()   # both workers see the table before pushes
        samples = np.arange(1, N_SAMPLES + 1, dtype=np.float32)
        chunk = samples[:N_SAMPLES // 2] if rank == 0 \
            else samples[N_SAMPLES // 2:]
        step = 0
        for batch in _chunk_batches(chunk, 0):
            if step >= 6:
                break
            client.Push(0, np.full(PLEN, batch.sum(), np.float32))
            client.Wait(0)
            step += 1
        # wait for the proposed shrink, then drain-commit at OUR step
        deadline = time.time() + 60
        while True:
            st = elastic.resize_state("127.0.0.1", port)
            if st["pending_version"] > 1:
                break
            assert time.time() < deadline, "no resize ever proposed"
            time.sleep(0.05)
        world = elastic.commit_resize("127.0.0.1", port, rank, step)
        client.SetWorldVersion(world["world_version"])
        eras = elastic.resize_log("127.0.0.1", port)
        chunks, _tail = elastic.era_partitions(N_SAMPLES, BATCH, eras)
        mine = samples[chunks[world["dp_rank"]]]
        for batch in _chunk_batches(mine, 0):
            client.Push(0, np.full(PLEN, batch.sum(), np.float32))
            client.Wait(0)
            step += 1
        out = client.Pull(0, np.empty(PLEN, np.float32))
        client.Wait(0)
        q.put((rank, "ok", out.copy(), world["world_version"]))
    except Exception:  # noqa: BLE001
        import traceback
        q.put((rank, "fail", traceback.format_exc(), None))
    finally:
        client.close(raise_on_error=False)


def _departing_body(rank, port, q, progress_path):
    """Scale-down victim: pushes exactly 5 batches of its chunk, records
    its progress (the cursor/state_dict stand-in the launcher reads), and
    dies without checking out — a SIGKILL'd preempted host."""
    os.environ.update(_worker_env(rank, port, 2, 1))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    client.SetWorldVersion(1)
    client.InitTensor(0, sparse=False, length=PLEN, width=1,
                      init_type="constant", init_a=0.0, opt_type="sgd",
                      lrs=(1.0,))
    client.BarrierWorker()
    samples = np.arange(1, N_SAMPLES + 1, dtype=np.float32)
    chunk = samples[N_SAMPLES // 2:]
    for step, batch in enumerate(_chunk_batches(chunk, 0)):
        if step >= 5:
            break
        client.Push(0, np.full(PLEN, batch.sum(), np.float32))
        client.Wait(0)
    with open(progress_path, "w") as f:
        f.write("5")
    q.put((rank, "dying", None, None))
    q.close()
    q.join_thread()  # flush the feeder: os._exit would otherwise eat it
    os._exit(137)


def test_scale_down_exact_sample_accounting(tmp_path):
    """Lose a worker mid-run: the survivor re-partitions over the
    remaining samples and the final PS value equals the full-epoch sum —
    every sample consumed exactly once, none twice, none lost."""
    cl = _Cluster(n_workers=2, n_servers=1)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    progress = str(tmp_path / "progress_r1")
    procs = []
    try:
        procs.append(ctx.Process(target=_survivor_body,
                                 args=(0, cl.port, q)))
        procs.append(ctx.Process(target=_departing_body,
                                 args=(1, cl.port, q, progress)))
        for p in procs:
            p.start()
        # the victim reports, records progress 5, and dies
        rank, status, _, _ = q.get(timeout=120)
        assert (rank, status) == (1, "dying")
        procs[1].join(timeout=30)
        assert procs[1].exitcode == 137
        # the launcher-side shrink: dead rank's progress rides the proposal
        coord = elastic.ElasticCoordinator("127.0.0.1", cl.port,
                                           drain_timeout_s=60)
        with open(progress) as f:
            dead_step = int(f.read())
        report = coord.resize(1, 1, removed=[1], removed_steps=[dead_step])
        assert report["members"] == [0]
        rank, status, out, ver = q.get(timeout=120)
        assert status == "ok", out
        assert ver == 2
        # exact accounting: server-side SGD(+=) accumulated every sample
        # exactly once => sum(1..96) in every param element
        np.testing.assert_array_equal(
            out, np.full(PLEN, np.arange(1, N_SAMPLES + 1).sum(),
                         np.float32))
        procs[0].join(timeout=30)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        cl.close()


def _scaleup_first_body(rank, port, q):
    """Scale-up founding worker: trains an Adam dense param + Adam sparse
    table alone for 4 steps, drain-commits through the grow (1w/1s ->
    2w/2s), proves migration preserved values/counters bit-for-bit, then
    consumes its post-resize partition."""
    os.environ.update(_worker_env(rank, port, 1, 1))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    try:
        client.SetWorldVersion(1)
        client.InitTensor(0, sparse=False, length=PLEN, width=1,
                          init_type="normal", init_a=0.0, init_b=1.0,
                          seed=5, opt_type="adam", lrs=(0.1, 0.9, 0.999,
                                                        1e-7))
        client.InitTensor(1, sparse=True, length=24, width=3,
                          init_type="normal", init_a=0.0, init_b=1.0,
                          seed=6, opt_type="adam", lrs=(0.1, 0.9, 0.999,
                                                        1e-7))
        samples = np.arange(1, N_SAMPLES + 1, dtype=np.float32)
        consumed = []
        step = 0
        rng = np.random.RandomState(3)
        for batch in _chunk_batches(samples, 0):
            if step >= 4:
                break
            client.Push(0, np.full(PLEN, 0.01 * batch.sum(), np.float32))
            client.Wait(0)
            rows = rng.randint(0, 24, 6).astype(np.int64)
            client.SparsePush(1, rows, np.ones((6, 3), np.float32))
            client.Wait(1)
            consumed.append(batch)
            step += 1
        # values at the drain boundary (the migration must preserve these)
        dense_pre = client.Pull(0, np.empty(PLEN, np.float32))
        client.Wait(0)
        all_rows = np.arange(24, dtype=np.int64)
        sparse_pre = client.SparsePull(1, all_rows,
                                       np.empty((24, 3), np.float32))
        client.Wait(1)
        updates_pre = client.ServerStats(0)["updates"]

        deadline = time.time() + 90
        while elastic.resize_state("127.0.0.1", port)["pending_version"] <= 1:
            assert time.time() < deadline, "no grow ever proposed"
            time.sleep(0.05)
        world = elastic.commit_resize("127.0.0.1", port, rank, step)
        client.SetWorldVersion(world["world_version"])
        n = client.RefreshServers()
        assert n == 2, n
        assert world["n_servers"] == 2

        # bit-exact state across the key-range move (rows + Adam slots
        # migrated; only their SERVER changed)
        dense_post = client.Pull(0, np.empty(PLEN, np.float32))
        client.Wait(0)
        sparse_post = client.SparsePull(1, all_rows,
                                        np.empty((24, 3), np.float32))
        client.Wait(1)
        np.testing.assert_array_equal(dense_pre, dense_post)
        np.testing.assert_array_equal(sparse_pre, sparse_post)
        updates_post = (client.ServerStats(0)["updates"]
                        + client.ServerStats(1)["updates"])
        assert updates_post == updates_pre, (updates_pre, updates_post)

        # post-resize: consume MY partition of the remaining samples
        eras = elastic.resize_log("127.0.0.1", port)
        chunks, _ = elastic.era_partitions(N_SAMPLES, BATCH, eras)
        mine = samples[chunks[world["dp_rank"]]]
        for batch in _chunk_batches(mine, 0):
            consumed.append(batch)
            client.Push(0, np.full(PLEN, 0.01 * batch.sum(), np.float32))
            client.Wait(0)
        q.put((rank, "ok", np.concatenate(consumed), None))
    except Exception:  # noqa: BLE001
        import traceback
        q.put((rank, "fail", traceback.format_exc(), None))
    finally:
        client.close(raise_on_error=False)


def _scaleup_joiner_body(rank, port, q):
    """Late joiner: reconstructs the era history from the scheduler's log,
    takes its partition, trains it to exhaustion. InitTensor is idempotent
    server-side, so re-declaring the tensors is safe."""
    os.environ.update(_worker_env(rank, port, 2, 2))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    try:
        eras = elastic.resize_log("127.0.0.1", port)
        client.SetWorldVersion(eras[-1]["version"])
        client.InitTensor(0, sparse=False, length=PLEN, width=1,
                          init_type="normal", init_a=0.0, init_b=1.0,
                          seed=5, opt_type="adam", lrs=(0.1, 0.9, 0.999,
                                                        1e-7))
        samples = np.arange(1, N_SAMPLES + 1, dtype=np.float32)
        chunks, _ = elastic.era_partitions(N_SAMPLES, BATCH, eras)
        pos = eras[-1]["members"].index(rank)
        mine = samples[chunks[pos]]
        consumed = []
        for batch in _chunk_batches(mine, 0):
            consumed.append(batch)
            client.Push(0, np.full(PLEN, 0.01 * batch.sum(), np.float32))
            client.Wait(0)
        q.put((rank, "ok", np.concatenate(consumed) if consumed
               else np.empty(0, np.float32), None))
    except Exception:  # noqa: BLE001
        import traceback
        q.put((rank, "fail", traceback.format_exc(), None))
    finally:
        client.close(raise_on_error=False)


def test_scale_up_worker_and_server_join(tmp_path):
    """Gain a worker AND a PS server mid-run: key ranges migrate onto the
    joining server with bit-exact values and update counters, the joiner
    reconstructs its partition from the world log, and the union of both
    workers' consumed samples is exactly the whole epoch."""
    cl = _Cluster(n_workers=1, n_servers=1)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=_scaleup_first_body, args=(0, cl.port, q))]
    try:
        procs[0].start()
        coord = elastic.ElasticCoordinator(
            "127.0.0.1", cl.port, workdir=str(tmp_path),
            drain_timeout_s=90)

        def spawn_server(sid):
            cl.spawn_server(sid, 2)

        def spawn_worker(r):
            p = ctx.Process(target=_scaleup_joiner_body, args=(r, cl.port, q))
            procs.append(p)
            p.start()

        # wait until the founder trains against the ONE-server world (it
        # drains when the proposal lands). A fixed sleep lost to a slow
        # spawn: a founder that first reads the address book after the
        # joining server has registered shards its tensors over two servers
        # under world v1, and the migration then moves half of each.
        def founder_has_pushed():
            try:
                addrs, _ = elastic._query_book("127.0.0.1", cl.port)
                return bool(addrs) and \
                    elastic.server_stats_raw(addrs[0])[0] >= 1
            except OSError:     # scheduler or server not listening yet
                return False

        deadline = time.time() + 90
        while not founder_has_pushed():
            assert time.time() < deadline, "the founder never pushed"
            time.sleep(0.05)
        report = coord.resize(2, 2, spawn_server=spawn_server,
                              spawn_worker=spawn_worker)
        assert report["migration"] is not None
        assert report["migration"]["updates_before"] == \
            report["migration"]["updates_after"]
        assert report["joined_workers"] == [1]

        got = {}
        for _ in range(2):
            rank, status, consumed, _ = q.get(timeout=180)
            assert status == "ok", consumed
            got[rank] = consumed
        allc = np.concatenate([got[0], got[1]])
        # exactly once: union of both workers' samples is the whole epoch
        assert np.unique(allc).size == allc.size == N_SAMPLES
        for p in procs:
            p.join(timeout=30)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        cl.close()


# ---------------------------------------------------------------------------
# stale-epoch rejection at the server
# ---------------------------------------------------------------------------

def _stale_epoch_body(rank, port, q):
    os.environ.update(_worker_env(rank, port, 1, 1))
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.client import PSClient
    client = PSClient.from_env()
    try:
        client.InitTensor(0, sparse=False, length=8, width=1,
                          init_type="constant", init_a=1.0)
        addrs, _ = elastic._query_book("127.0.0.1", port)
        # the server moves to world 5; this worker still stamps world 4
        elastic.server_set_world(addrs[0], 5)
        client.SetWorldVersion(4)
        try:
            client.Push(0, np.ones(8, np.float32))
            client.Wait(0)
            q.put((rank, "fail", "stale-epoch push was NOT rejected", None))
            return
        except RuntimeError as e:
            assert "stale world" in str(e), e
        # the rejected push left the param untouched
        client.SetWorldVersion(5)
        out = client.Pull(0, np.empty(8, np.float32))
        client.Wait(0)
        np.testing.assert_array_equal(out, np.ones(8, np.float32))
        # synced worker traffic flows again
        client.Push(0, np.ones(8, np.float32))
        client.Wait(0)
        # unversioned legacy traffic (world 0) is always accepted
        client.SetWorldVersion(0)
        client.Push(0, np.ones(8, np.float32))
        client.Wait(0)
        out = client.Pull(0, np.empty(8, np.float32))
        client.Wait(0)
        np.testing.assert_array_equal(out, np.full(8, 3.0, np.float32))
        q.put((rank, "ok", None, None))
    except Exception:  # noqa: BLE001
        import traceback
        q.put((rank, "fail", traceback.format_exc(), None))
    finally:
        client.close(raise_on_error=False)


def test_stale_epoch_request_rejected(tmp_path):
    cl = _Cluster(n_workers=1, n_servers=1)
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_stale_epoch_body, args=(0, cl.port, q))
    try:
        p.start()
        rank, status, err, _ = q.get(timeout=120)
        assert status == "ok", err
        p.join(timeout=30)
    finally:
        if p.is_alive():
            p.terminate()
        cl.close()
