"""hetusave — coordinated job-wide consistent checkpoints + exactly-once
whole-job crash recovery (docs/FAULT_TOLERANCE.md "Coordinated job
snapshots").

The cluster tests are the acceptance proofs: the ``kSnapshotNow`` PSF
publishes a durable epoch-stamped snapshot whose ``LATEST_s<rank>``
pointer flip is atomic (a server killed BETWEEN the directory publish
and the pointer write must leave restore on the previous complete
snapshot — the satellite regression), and the CLI soak runs a whole-job
kill inside a coordinated snapshot phase, restores from the newest
committed manifest only, and proves the restored run loss-bit-identical
to a fault-free twin under exactly-once update accounting. The unit
tests pin the one-atomic-commit manifest contract (torn epochs of every
shape are never restore-eligible), the checkpointer's retention policy,
the ``job_kill@S[:PHASE]`` fault grammar + arming, and the dataloader's
exact-sample-sequence resume across an epoch wrap with shuffle on.
"""
import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# manifest: ONE atomic commit per epoch; newest COMMITTED wins
# ---------------------------------------------------------------------------

def test_commit_manifest_atomic_no_tmp_left(tmp_path):
    from hetu_tpu import recovery
    jobdir = str(tmp_path)
    m = recovery._fake_epoch(jobdir, 1, step=5)
    path = recovery.manifest_path(jobdir, 1)
    assert os.path.isfile(path)
    # the commit is tmp+rename: no .tmp survives a successful commit
    assert not os.path.exists(path + ".tmp")
    got, edir = recovery.latest_committed_manifest(jobdir)
    assert got["epoch"] == 1 and got["step"] == m["step"]
    assert edir == os.path.join(jobdir, recovery.epoch_dir_name(1))


def test_torn_epochs_of_every_shape_never_restore_eligible(tmp_path):
    """A manifest that exists but references missing pieces — or never
    finished its own write — is torn, and restore must fall back to the
    newest epoch whose EVERY piece is on disk."""
    from hetu_tpu import recovery
    jobdir = str(tmp_path)
    recovery._fake_epoch(jobdir, 1, step=4)                    # committed
    recovery._fake_epoch(jobdir, 2, step=8, commit=False,
                         torn="tmp_manifest")                  # died mid-commit
    recovery._fake_epoch(jobdir, 3, step=12, torn="manifest.bin")
    recovery._fake_epoch(jobdir, 4, step=16, torn="worker")
    recovery._fake_epoch(jobdir, 5, step=20, torn="pointer")
    got, _ = recovery.latest_committed_manifest(jobdir)
    assert got["epoch"] == 1, "every torn shape must be skipped"
    rows = {r["epoch"]: r["status"] for r in recovery.list_epochs(jobdir)}
    assert rows[1] == "committed"
    for e in (2, 3, 4, 5):
        assert rows[e].startswith("torn"), (e, rows[e])
    # a later healthy commit immediately takes over
    recovery._fake_epoch(jobdir, 6, step=24)
    got, _ = recovery.latest_committed_manifest(jobdir)
    assert got["epoch"] == 6
    # new epochs never collide with torn leftovers
    assert recovery.next_epoch(jobdir) == 7


def test_checkpointer_prunes_committed_keeps_fresh_torn(tmp_path):
    """Retention: newest ``keep`` committed epochs survive; older ones
    (committed or torn) are swept; a torn epoch NEWER than the newest
    committed one is crash evidence and must be left for post-mortems."""
    from hetu_tpu import recovery
    jobdir = str(tmp_path)
    for e in (1, 2, 3):
        recovery._fake_epoch(jobdir, e, step=4 * e)
    recovery._fake_epoch(jobdir, 4, step=16, torn="pointer")   # fresh torn
    ck = recovery.JobCheckpointer(jobdir, keep=2)
    ck._prune()
    left = {r["epoch"] for r in recovery.list_epochs(jobdir)}
    assert left == {2, 3, 4}, left
    got, _ = recovery.latest_committed_manifest(jobdir)
    assert got["epoch"] == 3


# ---------------------------------------------------------------------------
# job_kill fault kind: grammar + phase arming
# ---------------------------------------------------------------------------

def test_job_kill_spec_grammar():
    from hetu_tpu.recovery import PHASES
    from hetu_tpu.resilience import FaultInjector
    fi = FaultInjector("job_kill@3:server_write,job_kill@7")
    assert fi.entries[0]["kind"] == "job_kill"
    assert fi.entries[0]["step"] == 3
    assert fi.entries[0]["arg"] == "server_write"
    assert fi.entries[1]["arg"] is None
    for phase in PHASES:
        FaultInjector(f"job_kill@1:{phase}")  # every real phase parses
    with pytest.raises(ValueError, match="job_kill phase"):
        FaultInjector("job_kill@2:mid_flight")
    with pytest.raises(ValueError, match="fault-kind catalogue"):
        FaultInjector("job_nuke@2")


def test_job_kill_phase_arming_and_single_consumption(monkeypatch):
    from hetu_tpu import recovery
    from hetu_tpu.resilience import FaultInjector
    fired = []
    monkeypatch.setattr(recovery, "kill_whole_job",
                        lambda step=None, phase=None:
                        fired.append((step, phase)))
    fi = FaultInjector("job_kill@3:pre_commit,job_kill@5")
    fi.inject_host(2)
    assert recovery.armed_kill_phase() is None
    fi.inject_host(3)  # phase-targeted: arms the NEXT snapshot's window
    assert recovery.armed_kill_phase() == "pre_commit"
    assert fired == []
    recovery._maybe_kill("server_write")     # wrong phase: no fire
    assert fired == [] and recovery.armed_kill_phase() == "pre_commit"
    recovery._maybe_kill("pre_commit")       # fires, consumed once
    assert fired == [(None, "pre_commit")]
    recovery._maybe_kill("pre_commit")
    assert fired == [(None, "pre_commit")]
    fi.inject_host(5)                        # bare job_kill: dies NOW
    assert fired[-1] == (5, None)


def test_kill_whole_job_gated_on_test_mode(monkeypatch):
    from hetu_tpu import recovery
    monkeypatch.delenv("HETU_TEST_MODE", raising=False)
    with pytest.raises(RuntimeError, match="HETU_TEST_MODE"):
        recovery.kill_whole_job(0)


# ---------------------------------------------------------------------------
# dataloader: exact sample sequence across an epoch wrap with shuffle
# ---------------------------------------------------------------------------

def test_dataloader_resume_exact_sequence_across_epoch_wrap():
    """Snapshot mid-epoch-1, then consume through the epoch-2 reshuffle:
    the restored twin must replay the IDENTICAL batch sequence — cursor,
    permutation, and the RNG state that generates the NEXT permutation
    all have to survive the round trip."""
    import hetu_tpu as ht
    data = np.arange(40, dtype=np.float32).reshape(20, 2)   # 5 batches/epoch

    def mk():
        return ht.Dataloader(data, 4, "train", shuffle=True, seed=3)

    a = mk()
    for _ in range(3):          # park mid-epoch-1
        a.get_arr()
    sd = a.state_dict()
    # reference: 12 more batches crosses the epoch-1→2 wrap (reshuffle)
    # and the 2→3 wrap — two RNG-consuming events past the snapshot
    ref = [np.array(a.get_arr(), copy=True) for _ in range(12)]
    b = mk()
    b.load_state_dict(sd)
    got = [np.array(b.get_arr(), copy=True) for _ in range(12)]
    for i, (r, g) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(r, g, err_msg=f"batch {i} diverged")
    # the wrap actually reshuffled (epoch 2 is a different permutation
    # than the tail of epoch 1 re-read in order) — otherwise this test
    # would pass with a loader that never shuffles again after restore
    epoch2 = np.concatenate(ref[2:7])
    assert not np.array_equal(np.sort(epoch2.ravel()),
                              epoch2.ravel()), "epoch 2 never shuffled"
    # …while still covering every sample exactly once per epoch
    np.testing.assert_array_equal(np.sort(epoch2, axis=0), data)


# ---------------------------------------------------------------------------
# kSnapshotNow PSF: durable epoch-stamped snapshots on a live server
# ---------------------------------------------------------------------------

def test_snapshot_now_psf_publishes_durable_versions(tmp_path, monkeypatch):
    from hetu_tpu.ps.local_cluster import local_cluster
    from hetu_tpu import ps as ps_pkg
    snapdir = str(tmp_path / "snap")
    monkeypatch.setenv("DMLC_PS_SNAPSHOT_DIR", snapdir)
    with local_cluster(n_servers=1, n_workers=1):
        ps_pkg.worker_init()
        try:
            comm = ps_pkg.get_worker_communicate()
            comm.InitTensor(0, sparse=False, length=32, width=1,
                            init_type="constant", init_a=1.5)
            comm.Push(0, np.ones(32, np.float32))
            comm.Wait(0)
            r1 = comm.SnapshotNow(0, epoch=7)
            # quiesced (Wait drained the push): the snapshot covers the
            # live counter exactly — hetusave's consistency proof
            assert r1["version"] == 1
            assert r1["epoch"] == 7
            assert r1["counter"] == r1["updates"] == 1, r1
            name = f"snap_s0_v{r1['version']}"
            d = os.path.join(snapdir, name)
            assert os.path.isdir(d), "returned version must be durable"
            with open(os.path.join(d, "manifest.bin"), "rb") as f:
                (magic,) = struct.unpack("<q", f.read(8))
                head = struct.unpack("<4Q", f.read(32))
            assert magic == -7001 and head[0] == 1 and head[1] == 1, (
                magic, head)
            with open(os.path.join(snapdir, "LATEST_s0")) as f:
                assert f.read().strip() == name
            comm.Push(0, np.ones(32, np.float32))
            comm.Wait(0)
            r2 = comm.SnapshotNow(0, epoch=8)
            assert r2["version"] == 2 and r2["counter"] == 2, r2
            with open(os.path.join(snapdir, "LATEST_s0")) as f:
                assert f.read().strip() == f"snap_s0_v{r2['version']}"
        finally:
            ps_pkg.worker_finish()


def test_snapshot_now_concurrent_with_periodic_snapshots(tmp_path,
                                                         monkeypatch):
    """Regression (ABBA deadlock): the kSnapshotNow dispatch thread used
    to hold the requester's dedup-slot mutex while waiting on
    snap_take_mu_, while the periodic snapshot thread held snap_take_mu_
    and locked that same slot during its ledger walk. With the periodic
    snapshotter spinning at a 1ms interval and every push dirtying state,
    this loop deadlocked within a few iterations; now the dispatch path
    drops the slot across handle() and every RPC snapshot completes."""
    from hetu_tpu.ps.local_cluster import local_cluster
    from hetu_tpu import ps as ps_pkg
    snapdir = str(tmp_path / "snap")
    monkeypatch.setenv("DMLC_PS_SNAPSHOT_DIR", snapdir)
    monkeypatch.setenv("DMLC_PS_SNAPSHOT_MS", "1")
    with local_cluster(n_servers=1, n_workers=1):
        ps_pkg.worker_init()
        try:
            comm = ps_pkg.get_worker_communicate()
            comm.InitTensor(0, sparse=False, length=8, width=1,
                            init_type="constant", init_a=0.0)
            last = None
            for i in range(30):
                comm.Push(0, np.ones(8, np.float32))
                comm.Wait(0)
                last = comm.SnapshotNow(0, epoch=i)
            assert last["updates"] == 30
            # quiesced between pushes: the RPC snapshot covers the live
            # counter exactly, periodic-thread races notwithstanding
            assert last["counter"] == 30
        finally:
            ps_pkg.worker_finish()


def test_kill_between_publish_and_pointer_restores_previous(tmp_path,
                                                            monkeypatch):
    """Satellite regression: the server dies AFTER publishing the v2
    snapshot directory but BEFORE flipping LATEST_s0. The pointer must
    still name v1, and a fresh server restoring from the directory must
    land on v1's state and counter — never on the unpointed v2."""
    from hetu_tpu.ps.local_cluster import get_live_cluster, local_cluster
    from hetu_tpu import ps as ps_pkg
    snapdir = str(tmp_path / "snap")
    monkeypatch.setenv("DMLC_PS_SNAPSHOT_DIR", snapdir)
    monkeypatch.setenv("HETU_TEST_MODE", "1")
    monkeypatch.setenv("HETU_PS_TEST_KILL_BEFORE_POINTER", "2")

    with local_cluster(n_servers=1, n_workers=1):
        ps_pkg.worker_init()
        try:
            comm = ps_pkg.get_worker_communicate()
            comm.InitTensor(0, sparse=False, length=16, width=1,
                            init_type="constant", init_a=0.0)
            comm.Push(0, np.full(16, 1.0, np.float32))
            comm.Wait(0)
            r1 = comm.SnapshotNow(0, epoch=1)
            assert r1["version"] == 1 and r1["counter"] == 1
            val_v1 = comm.Pull(0, np.empty(16, np.float32))
            comm.Wait(0)    # the buffer is filled by now, not before
            comm.Push(0, np.full(16, 1.0, np.float32))
            comm.Wait(0)
            val_later = comm.Pull(0, np.empty(16, np.float32))
            comm.Wait(0)
            assert not np.array_equal(val_v1, val_later)
            # v2: dir publishes, then std::_Exit(137) before the pointer
            with pytest.raises(Exception):
                comm.SnapshotNow(0, epoch=2)
            assert os.path.isdir(os.path.join(snapdir, "snap_s0_v2")), \
                "v2 dir must have been published before the death"
            with open(os.path.join(snapdir, "LATEST_s0")) as f:
                assert f.read().strip() == "snap_s0_v1", \
                    "pointer must still name the last COMPLETE flip"
        finally:
            # the server is gone — put the rest of the cluster out of its
            # misery so finalize fails fast instead of waiting on a barrier
            for p in get_live_cluster().get("procs", []):
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass
            try:
                ps_pkg.worker_finish()
            except Exception:  # noqa: BLE001 — dead cluster
                pass

    # restore leg: a fresh incarnation follows LATEST_s0 → v1
    monkeypatch.delenv("HETU_PS_TEST_KILL_BEFORE_POINTER")
    monkeypatch.delenv("DMLC_PS_SNAPSHOT_DIR", raising=False)
    monkeypatch.setenv("DMLC_PS_RESTORE_DIR", snapdir)
    with local_cluster(n_servers=1, n_workers=1):
        ps_pkg.worker_init()
        try:
            comm = ps_pkg.get_worker_communicate()
            # idempotent re-init: a restored (sized) param is untouched
            comm.InitTensor(0, sparse=False, length=16, width=1,
                            init_type="constant", init_a=0.0)
            stats = comm.ServerStats(0)
            assert stats["restored_updates"] == 1, stats
            got = comm.Pull(0, np.empty(16, np.float32))
            comm.Wait(0)
            np.testing.assert_array_equal(got, val_v1)
            assert not np.array_equal(got, val_later)
        finally:
            ps_pkg.worker_finish()


# ---------------------------------------------------------------------------
# coordinator guards: multi-worker refusal + grace-budget barrier timeout
# ---------------------------------------------------------------------------

def test_take_job_snapshot_refuses_multi_worker(tmp_path, monkeypatch):
    """Regression: the coordinator captures only its own rank's worker
    state, so a multi-worker job must be refused BEFORE the barrier is
    even proposed — a committed epoch missing ranks would pass every
    completeness check yet be unrestorable for every other rank."""
    from hetu_tpu import elastic, recovery
    from hetu_tpu import ps as ps_pkg

    class Rt:
        def drain(self):
            pass

    class Ex:
        ps_runtime = Rt()
        state = {"step": 3}

    jobdir = str(tmp_path / "job")
    monkeypatch.setenv("DMLC_PS_SNAPSHOT_DIR", str(tmp_path / "snap"))
    monkeypatch.setattr(ps_pkg, "get_worker_communicate", lambda: object())
    monkeypatch.setattr(elastic, "resize_state",
                        lambda host, port: {"n_workers": 2, "n_servers": 1})

    def no_propose(*a, **k):
        raise AssertionError("barrier proposed for an unrestorable epoch")

    monkeypatch.setattr(elastic, "propose_resize", no_propose)
    with pytest.raises(recovery.RecoveryError, match="2 workers"):
        recovery.take_job_snapshot(Ex(), jobdir)
    assert recovery.latest_committed_manifest(jobdir) is None


def test_job_checkpointer_grace_budget_barrier_timeout(tmp_path,
                                                       monkeypatch):
    """Regression: the SIGTERM-grace coordinated save must bound its
    drain barrier BELOW the preemption grace period (grace_s /
    HETU_PREEMPT_GRACE_S), leaving headroom for the worker-local
    fallback — take_job_snapshot's 120s default would ride a 30s grace
    window straight into the SIGKILL and cost BOTH saves."""
    from hetu_tpu import recovery
    jd = str(tmp_path)
    monkeypatch.delenv("HETU_PREEMPT_GRACE_S", raising=False)
    ck = recovery.JobCheckpointer(jd)
    assert ck.grace_s == 30.0                    # heturun's default window
    assert ck.grace_timeout() == 25.0
    assert recovery.JobCheckpointer(jd, grace_s=4).grace_timeout() == 2.0
    monkeypatch.setenv("HETU_PREEMPT_GRACE_S", "60")
    assert recovery.JobCheckpointer(jd).grace_timeout() == 55.0
    # an explicit barrier_timeout below the grace bound wins
    assert recovery.JobCheckpointer(
        jd, barrier_timeout=7.5, grace_s=60).grace_timeout() == 7.5

    # save_preempt threads the bound into take_job_snapshot; a cadence
    # save keeps the 120s default
    seen = []

    def fake_take(ex, jobdir, *, on_phase=None, timeout=120.0):
        seen.append(timeout)
        return {"epoch": 1}

    monkeypatch.setattr(recovery, "take_job_snapshot", fake_take)
    ck = recovery.JobCheckpointer(jd, grace_s=30)
    ck.save_preempt(None, 5)
    ck.save(None, 6)
    assert seen == [25.0, 120.0]


# ---------------------------------------------------------------------------
# CLI: jax-free self-test, inventory, and the live whole-job-kill soak
# ---------------------------------------------------------------------------

def test_hetusave_check_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetusave"),
         "--check"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr + out.stdout
    assert "newest-committed" in out.stdout, out.stdout


def test_hetusave_list_cli(tmp_path):
    from hetu_tpu import recovery
    jobdir = str(tmp_path)
    recovery._fake_epoch(jobdir, 1, step=4)
    recovery._fake_epoch(jobdir, 2, step=8, torn="pointer")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetusave"),
         "--list", jobdir], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr + out.stdout
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    assert {r["epoch"]: r["status"].split(" ")[0] for r in rows} == \
        {1: "committed", 2: "torn"}


def test_hetusave_soak_cli():
    """The CI soak: whole-job kill at pre_commit inside a coordinated
    snapshot, restore from the newest committed manifest, exactly-once
    accounting, and the restored run's losses + final params
    bit-identical to a fault-free twin — end to end through the real
    CLI. The timeout is a hang bound, not a verdict."""
    env = dict(os.environ, HETU_TEST_MODE="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetusave"),
         "--seed", "1", "--steps", "6", "--phase", "pre_commit"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr + out.stdout
    assert "checks green" in out.stdout, out.stdout


@pytest.mark.slow
def test_hetusave_full_phase_matrix_with_resize():
    """The acceptance matrix: five seeds, the kill rotating through every
    snapshot phase (pre_barrier, server_write, pre_commit, post_commit),
    the last seed restoring into a DIFFERENT world size (2 → 1 servers)
    with re-split counter algebra and optimizer state bit-equality."""
    env = dict(os.environ, HETU_TEST_MODE="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetusave"),
         "--seeds", "1,2,3,4,5", "--steps", "9", "--resize", "1"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr + out.stdout
    assert out.stdout.count("checks green") == 5, out.stdout
