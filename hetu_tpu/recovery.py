"""hetusave: coordinated job-wide consistent checkpoints + exactly-once
whole-job crash recovery (docs/FAULT_TOLERANCE.md "Coordinated job
snapshots").

Every durable piece of state in the stack is recoverable *separately* —
worker emergency checkpoints, per-server PS snapshots with lost-update
accounting, dataloader cursors and the elastic world log — but a
whole-job failure (power loss, pool preemption, OOM-killer sweep) leaves
them mutually INCONSISTENT: worker state at step N, PS shards at
assorted update counts, cursors somewhere in between. This module makes
them one recovery point:

- :func:`take_job_snapshot` rides the two-phase resize machinery
  (propose -> drain-park -> abort) as a **quiesce barrier**: the worker
  parks at a step boundary with all in-flight pushes drained through the
  req_id dedup ledger (``pushes_ok == sum(server updates)`` is the
  quiesce PROOF, not an assumption), every PS server writes one
  epoch-stamped snapshot (``kSnapshotNow``) under the per-param shared
  locks, the worker persists params, optimizer slots, ``qresid``,
  dataloader cursors, RNG and the world log, and ONE job manifest is
  committed atomically (temp+rename). A torn or uncommitted epoch is
  never eligible for restore.
- :func:`prepare_restore` + :func:`load_worker_state` reconstruct the
  job from the newest COMMITTED manifest — including into a different
  world size via the offline key-range re-split (:func:`resplit_epoch`,
  optimizer slots move bit-for-bit with their rows), with the
  update-counter algebra verified before training resumes
  (:func:`verify_restored_job`).
- :func:`run_soak` proves the protocol under whole-job kills injected at
  every snapshot phase (``PHASES``): the restored lineage's losses,
  consumed-sample multiset and final params are compared BIT-IDENTICALLY
  against an uninterrupted fault-free twin.

Everything above ``take_job_snapshot`` is stdlib+numpy (``bin/hetusave
--check`` must run jax-free); jax/hetu imports are lazy in the drivers.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import sys
import threading
import time
from typing import Callable, Optional

import numpy as np

from . import faults

#: the crash windows of one coordinated snapshot, in protocol order —
#: the ``job_kill@step:phase`` fault kind targets exactly these, and the
#: shared fault registry (hetu_tpu.faults) owns the tuple so the injector
#: grammar and this module can never disagree:
#:   pre_barrier   before the quiesce barrier is even proposed
#:   server_write  after the FIRST server snapshot landed (torn epoch:
#:                 some servers newer than others, no manifest)
#:   pre_commit    all state written, job manifest NOT yet committed
#:   post_commit   manifest committed (the epoch must be restorable)
PHASES = faults.JOB_KILL_PHASES

MANIFEST_FORMAT = 1
_MANIFEST_PREFIX = "job_epoch_"
_EPOCH_PREFIX = "epoch_"

# per-server snapshot manifest constants (csrc/ps/server.h) — the offline
# re-split writes manifests the native restore path parses directly
_PS_MANIFEST_MAGIC = -7001


class RecoveryError(RuntimeError):
    """A broken recovery invariant (failed quiesce proof, no committed
    epoch, counter-algebra mismatch) — never swallowed."""


class JobKilled(BaseException):
    """The simulated whole-job death the soak injects mid-snapshot.
    Derives from BaseException so ordinary ``except Exception`` hardening
    inside the job cannot absorb it — a power loss is not absorbable."""


# ---------------------------------------------------------------------------
# job_kill arming (consumed by take_job_snapshot at phase boundaries)
# ---------------------------------------------------------------------------

_armed_kill: dict = {"phase": None}


def arm_job_kill(phase: str) -> None:
    """Arm a whole-job kill at ``phase`` of the NEXT coordinated snapshot
    (the ``job_kill@step:phase`` fault kind's executor). Consumed once."""
    if phase not in PHASES:
        raise ValueError(f"job_kill phase {phase!r} not in {PHASES}")
    _armed_kill["phase"] = phase


def armed_kill_phase() -> Optional[str]:
    return _armed_kill["phase"]


def kill_whole_job(step: Optional[int] = None,
                   phase: Optional[str] = None) -> None:
    """Whole-job death, no grace, no cleanup: SIGKILL every live
    local-cluster process (scheduler + servers), then this worker —
    the power-loss / pool-sweep shape only a committed job epoch
    recovers from. HETU_TEST_MODE-gated like every destructive hook."""
    import signal as _signal

    from .resilience import test_mode_enabled
    if not test_mode_enabled():
        raise RuntimeError("job_kill requires HETU_TEST_MODE")
    where = f"phase {phase}" if phase else f"step {step}"
    print(f"# hetu fault: job_kill — whole job dying at {where}",
          file=sys.stderr, flush=True)
    try:
        from .ps.local_cluster import get_live_cluster
        for p in get_live_cluster().get("procs", []):
            try:
                p.kill()
            except Exception:  # noqa: BLE001 — already dead is fine
                pass
    except Exception:  # noqa: BLE001 — no live cluster: still die
        pass
    os.kill(os.getpid(), _signal.SIGKILL)


def _maybe_kill(phase: str) -> None:
    """Fire an armed job_kill when the snapshot reaches its phase."""
    if _armed_kill["phase"] == phase:
        _armed_kill["phase"] = None
        kill_whole_job(phase=phase)


# ---------------------------------------------------------------------------
# Job manifest: ONE atomic commit per epoch (jax-free)
# ---------------------------------------------------------------------------

def epoch_dir_name(epoch: int) -> str:
    return f"{_EPOCH_PREFIX}{int(epoch)}"


def manifest_path(jobdir: str, epoch: int) -> str:
    return os.path.join(jobdir, f"{_MANIFEST_PREFIX}{int(epoch)}.json")


def commit_manifest(jobdir: str, manifest: dict) -> str:
    """THE commit point of a snapshot epoch: the manifest JSON lands via
    write-temp + fsync + rename, so it either exists complete or not at
    all — a job that dies mid-write leaves a ``.tmp`` that
    :func:`latest_committed_manifest` never looks at."""
    path = manifest_path(jobdir, manifest["epoch"])
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _epoch_numbers(jobdir: str) -> list[int]:
    """Every epoch number with ANY on-disk trace (manifest or epoch dir,
    committed or torn) — what next_epoch must never collide with."""
    out = set()
    try:
        names = os.listdir(jobdir)
    except FileNotFoundError:
        return []
    for n in names:
        num = None
        if n.startswith(_MANIFEST_PREFIX) and n.endswith(".json"):
            num = n[len(_MANIFEST_PREFIX):-len(".json")]
        elif n.startswith(_EPOCH_PREFIX):
            num = n[len(_EPOCH_PREFIX):].split("_", 1)[0]
        if num and num.isdigit():
            out.add(int(num))
    return sorted(out)


def next_epoch(jobdir: str) -> int:
    nums = _epoch_numbers(jobdir)
    return (nums[-1] + 1) if nums else 1


def _manifest_complete(jobdir: str, m: dict) -> Optional[str]:
    """None when every file the manifest references exists (the epoch is
    restorable); else a human-readable reason it is torn."""
    if m.get("format") != MANIFEST_FORMAT:
        return f"unknown manifest format {m.get('format')!r}"
    edir = os.path.join(jobdir, epoch_dir_name(m.get("epoch", -1)))
    if not os.path.isdir(edir):
        return f"epoch dir {edir} missing"
    for s in m.get("servers", []):
        snap = os.path.join(edir, s.get("snapshot", ""))
        if not os.path.isfile(os.path.join(snap, "manifest.bin")):
            return f"server snapshot {snap} missing/incomplete"
        ptr = os.path.join(edir, f"LATEST_s{s.get('rank')}")
        if not os.path.isfile(ptr):
            return f"pointer {ptr} missing"
    for w in m.get("workers", []):
        wf = os.path.join(edir, w.get("state_file", ""))
        if not os.path.isfile(wf):
            return f"worker state {wf} missing"
    return None


def latest_committed_manifest(jobdir: str) -> Optional[tuple[dict, str]]:
    """The NEWEST epoch whose manifest is committed AND whose referenced
    files all exist: ``(manifest, epoch_dir)``; None when no epoch is
    restorable. Torn epochs — an uncommitted ``.tmp`` manifest, a
    manifest whose snapshot dirs never all landed, unparseable JSON —
    are skipped (with a stderr note), never selected: the core
    crash-consistency guarantee the job_kill soak pins."""
    candidates: list[tuple[int, str]] = []
    try:
        names = os.listdir(jobdir)
    except FileNotFoundError:
        return None
    for n in names:
        if n.startswith(_MANIFEST_PREFIX) and n.endswith(".json"):
            num = n[len(_MANIFEST_PREFIX):-len(".json")]
            if num.isdigit():
                candidates.append((int(num), os.path.join(jobdir, n)))
    for epoch, path in sorted(candidates, reverse=True):
        try:
            with open(path) as f:
                m = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# hetusave: skipping unreadable manifest {path}: {e}",
                  file=sys.stderr)
            continue
        reason = _manifest_complete(jobdir, m)
        if reason is not None:
            print(f"# hetusave: skipping torn epoch {epoch}: {reason}",
                  file=sys.stderr)
            continue
        return m, os.path.join(jobdir, epoch_dir_name(epoch))
    return None


def list_epochs(jobdir: str) -> list[dict]:
    """Inventory for ``bin/hetusave --list``: every on-disk epoch with
    its committed/torn status and (when committed) step + world."""
    out = []
    for epoch in _epoch_numbers(jobdir):
        row: dict = {"epoch": epoch}
        path = manifest_path(jobdir, epoch)
        if not os.path.isfile(path):
            row["status"] = "torn (no committed manifest)"
        else:
            try:
                with open(path) as f:
                    m = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                row["status"] = f"torn (unreadable manifest: {e})"
            else:
                reason = _manifest_complete(jobdir, m)
                if reason is None:
                    row.update(status="committed", step=m.get("step"),
                               world=m.get("world"),
                               total_updates=m.get("total_updates"))
                else:
                    row["status"] = f"torn ({reason})"
        out.append(row)
    return out


def _write_pointer(dirpath: str, rank: int, snap_name: str) -> None:
    """LATEST_s<rank> pointer file, temp+rename like the server's own
    flip — a crash mid-write can never leave a torn pointer."""
    ptr = os.path.join(dirpath, f"LATEST_s{rank}")
    tmp = os.path.join(dirpath, f".LATEST_s{rank}.tmp")
    with open(tmp, "w") as f:
        f.write(snap_name)
    os.replace(tmp, ptr)


# ---------------------------------------------------------------------------
# Offline re-split: restore into a DIFFERENT world size (jax-free)
# ---------------------------------------------------------------------------

def _write_ps_manifest(path: str, counter: int, n_params: int) -> None:
    """A per-server snapshot manifest the native ``load_manifest``
    (csrc/ps/server.h) parses: magic, {version, counter, n_params,
    n_clients=0}. The resend-dedup ledger is deliberately EMPTY: a
    restored job's workers are fresh incarnations whose req_id streams
    start over, so no pre-crash resend can ever arrive — dropping the
    ledger loses nothing and can never mask a replay."""
    with open(path, "wb") as f:
        np.asarray([_PS_MANIFEST_MAGIC], np.int64).tofile(f)
        np.asarray([1, counter, n_params, 0], np.uint64).tofile(f)


def _split_counter(total: int, n: int) -> list[int]:
    """Distribute the job's total update counter over ``n`` restored
    shards, sum-preserving. The per-shard split is ARBITRARY (update
    counts are a per-server odometer, not per-key bookkeeping), so the
    even split here is just a convention; the invariant restore verifies
    is the SUM (:func:`verify_restored_job`)."""
    base = int(total) // n
    out = [base] * n
    out[0] += int(total) - base * n
    return out


def resplit_epoch(epoch_dir: str, dst_dir: str, new_ns: int,
                  manifest: dict) -> dict:
    """Re-shard one committed epoch's PS state from its recorded world
    size into ``new_ns`` key-range shards, offline (no cluster). Rows
    move WITH their optimizer slots and version counters bit-for-bit
    (``elastic.repartition_key`` — the same split formula the live
    worker partitioner uses, following the cross-replica optimizer
    sharding discipline of arXiv:2004.13336). Output layout matches a
    native snapshot root (``snap_s<r>_v1`` dirs + ``LATEST_s<r>``
    pointers + per-server manifests), so servers restore from it through
    the unchanged ``DMLC_PS_RESTORE_DIR`` path. Built in a temp dir and
    renamed into place: a torn re-split is never restore-eligible."""
    from .elastic import read_v2_shard, repartition_key, write_v2_shard
    old = sorted(manifest["servers"], key=lambda s: s["rank"])
    old_ns = len(old)
    new_ns = int(new_ns)
    if new_ns < 1:
        raise RecoveryError("re-split needs at least one server")
    tmp = dst_dir + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    snap_dirs = []
    for r in range(new_ns):
        d = os.path.join(tmp, f"snap_s{r}_v1")
        os.makedirs(d)
        snap_dirs.append(d)
    # key inventory: every param shard file in every old snapshot dir
    keys: set[int] = set()
    for s in old:
        sdir = os.path.join(epoch_dir, s["snapshot"])
        for n in os.listdir(sdir):
            if n.startswith("param_") and n.endswith(".bin"):
                k = n[len("param_"):].split("_", 1)[0]
                if k.isdigit():
                    keys.add(int(k))
    n_keys = 0
    for key in sorted(keys):
        shards = []
        for s in old:
            p = os.path.join(epoch_dir, s["snapshot"],
                             f"param_{key}_shard{s['rank']}.bin")
            if os.path.isfile(p):
                shards.append(read_v2_shard(p))
        if not shards:
            continue
        if len(shards) != old_ns:
            raise RecoveryError(
                f"param {key}: only {len(shards)}/{old_ns} shards present "
                f"in committed epoch — manifest claims a complete epoch")
        for r, d in enumerate(repartition_key(shards, new_ns)):
            write_v2_shard(
                os.path.join(snap_dirs[r], f"param_{key}_shard{r}.bin"), d)
        n_keys += 1
    counters = _split_counter(manifest["total_updates"], new_ns)
    for r in range(new_ns):
        _write_ps_manifest(os.path.join(snap_dirs[r], "manifest.bin"),
                           counters[r], n_keys)
        _write_pointer(tmp, r, f"snap_s{r}_v1")
    shutil.rmtree(dst_dir, ignore_errors=True)
    os.rename(tmp, dst_dir)
    return {"old_n_servers": old_ns, "new_n_servers": new_ns,
            "n_params": n_keys, "counters": counters,
            "total_updates": int(manifest["total_updates"]),
            "dst": dst_dir}


def prepare_restore(jobdir: str, n_servers: Optional[int] = None) -> dict:
    """Resolve a restore: pick the newest COMMITTED epoch and (when the
    target world size differs from the recorded one) build the offline
    re-split. Returns ``manifest``, ``epoch_dir``, the directory servers
    should restore from (``server_restore_dir`` — pass as
    DMLC_PS_RESTORE_DIR), the effective ``n_servers``, and the re-split
    report (None when the world size is unchanged). Raises
    :class:`RecoveryError` when nothing is restorable."""
    got = latest_committed_manifest(jobdir)
    if got is None:
        raise RecoveryError(
            f"no committed snapshot epoch under {jobdir} — torn epochs are "
            "never restore-eligible")
    m, epoch_dir = got
    ns_rec = int(m["world"]["n_servers"])
    ns = int(n_servers) if n_servers else ns_rec
    resplit = None
    restore_dir = epoch_dir
    if ns != ns_rec:
        restore_dir = f"{epoch_dir}_resplit{ns}"
        resplit = resplit_epoch(epoch_dir, restore_dir, ns, m)
    return {"manifest": m, "epoch_dir": epoch_dir,
            "server_restore_dir": restore_dir, "n_servers": ns,
            "resplit": resplit}


def verify_restored_job(manifest: dict, server_stats: list[dict]) -> dict:
    """The update-counter algebra gate BEFORE training resumes: the sum
    of the counters the restored servers actually loaded must equal the
    total the job manifest committed — anything else means a shard
    restored from the wrong epoch (or a torn re-split) and the job must
    not silently train on it."""
    restored = sum(max(int(s.get("restored_updates", -1)), 0)
                   for s in server_stats)
    want = int(manifest["total_updates"])
    ok = restored == want
    report = {"name": "restored_counter_algebra", "ok": ok,
              "restored_updates": restored, "manifest_updates": want,
              "epoch": manifest["epoch"]}
    if not ok:
        raise RecoveryError(
            f"restored update counters {restored} != committed total "
            f"{want} (epoch {manifest['epoch']}) — a shard restored from "
            "the wrong state; refusing to resume")
    return report


# ---------------------------------------------------------------------------
# The coordinator (lazy jax/hetu imports from here down)
# ---------------------------------------------------------------------------

def take_job_snapshot(ex, jobdir: str, *,
                      on_phase: Optional[Callable[[str], None]] = None,
                      timeout: float = 120.0) -> dict:
    """ONE globally consistent snapshot epoch of the whole job, riding
    the two-phase resize machinery as a quiesce barrier:

    1. drain this worker's async PS traffic, then propose an
       IDENTICAL-world resize (scheduler accepts; nothing migrates);
    2. park the worker's rank at the drain barrier (a side thread blocks
       in ``commit_resize`` while this thread coordinates) and poll
       until every survivor is parked;
    3. prove quiescence: ``pushes_ok == sum(updates - restored)`` across
       servers — every write this incarnation issued has been applied,
       nothing is in flight;
    4. drive each server's epoch-stamped ``kSnapshotNow`` (synchronous:
       snapshot dir published + LATEST pointer flipped before it
       replies), then COPY the pinned snapshot dirs into the epoch dir —
       the epoch owns immutable state the server's own prune can never
       touch, and restore pins exactly the manifest's snapshots;
    5. persist the worker: params, optimizer slots, qresid, dataloader
       cursors, RNG, plus the scheduler's era log;
    6. commit ONE job manifest atomically (:func:`commit_manifest`);
    7. ABORT the "resize" — every parked worker resumes under the old
       world, training state untouched.

    Any failure (or armed job_kill) aborts the barrier best-effort and
    re-raises; a death at any point leaves either the previous committed
    epoch or a torn epoch restore never selects.

    Multi-worker jobs are refused up front (:class:`RecoveryError`,
    before the barrier is proposed): this coordinator persists only its
    own rank's worker state, and an epoch missing ranks must never
    commit — it would pass every completeness check yet be unrestorable.
    """
    from . import ps as ps_pkg
    from .elastic import (commit_resize, finish_resize, propose_resize,
                          resize_log, resize_state, sched_addr_from_env)
    rt = getattr(ex, "ps_runtime", None)
    if rt is None:
        raise RecoveryError(
            "coordinated snapshot needs a PS job (comm_mode='PS')")
    snap_root = os.environ.get("DMLC_PS_SNAPSHOT_DIR")
    if not snap_root:
        raise RecoveryError(
            "coordinated snapshot needs servers launched with "
            "DMLC_PS_SNAPSHOT_DIR (heturun --ha / local_cluster(ha=True))")
    comm = ps_pkg.get_worker_communicate()
    host, port = sched_addr_from_env()
    rank = int(os.environ.get("WORKER_ID", "0"))
    step = int(ex.state.get("step", 0))
    t0 = time.perf_counter()

    def _phase(name: str) -> None:
        if on_phase is not None:
            on_phase(name)
        _maybe_kill(name)

    os.makedirs(jobdir, exist_ok=True)
    epoch = next_epoch(jobdir)
    edir = os.path.join(jobdir, epoch_dir_name(epoch))

    _phase("pre_barrier")
    rt.drain()
    st = resize_state(host, port)
    nw, ns = int(st["n_workers"]), int(st["n_servers"])
    if nw != 1:
        # this coordinator captures only its OWN rank's worker state; a
        # committed epoch for a bigger world would pass every on-disk
        # completeness check yet be unrestorable (load_worker_state raises
        # for every other rank). Refuse up front — before the barrier is
        # even proposed — rather than hand the operator an epoch that
        # looks restorable and is not. Multi-rank capture is the lift
        # required to relax this.
        raise RecoveryError(
            f"coordinated snapshot with {nw} workers is not supported: "
            "the coordinator persists only its own rank's state, so the "
            "committed epoch could never restore the other ranks — "
            "refusing to write an unrestorable epoch")
    propose_resize(host, port, nw, ns)

    parked: dict = {}

    def _park():
        try:
            parked["world"] = commit_resize(host, port, rank, step,
                                            timeout=timeout)
        except Exception as e:  # noqa: BLE001 — surfaced by coordinator
            parked["error"] = e

    th = threading.Thread(target=_park, name="hetusave-park", daemon=True)
    released = False
    try:
        th.start()
        deadline = time.monotonic() + timeout
        while True:
            st = resize_state(host, port)
            if st["pending_version"] and \
                    st["drain_count"] >= st["drain_needed"]:
                break
            if "error" in parked:
                raise RecoveryError(
                    f"drain barrier failed: {parked['error']!r}")
            if time.monotonic() > deadline:
                raise RecoveryError(
                    f"drain barrier timeout: {st['drain_count']}/"
                    f"{st['drain_needed']} survivors parked after "
                    f"{timeout}s")
            # tight poll: the whole drain window is on the snapshot's
            # critical path, and the stall budget is single-digit
            # percent — 2ms keeps the barrier sub-step-scale while still
            # yielding the GIL to the parked commit thread
            time.sleep(0.002)

        # quiesce proof — the dedup-ledger accounting invariant, exact
        # because the nw == 1 gate above guarantees this worker's
        # pushes_ok is the WHOLE job's push count
        cs = comm.ClientStats()
        sstats = [comm.ServerStats(s) for s in range(ns)]
        applied = sum(int(s["updates"]) - max(int(s["restored_updates"]), 0)
                      for s in sstats)
        pushed = int(cs["pushes_ok"])
        if pushed != applied:
            raise RecoveryError(
                f"quiesce proof failed: client pushes_ok {pushed} != "
                f"servers' applied updates {applied} — in-flight writes "
                "survived the drain barrier; refusing to snapshot")

        shutil.rmtree(edir, ignore_errors=True)
        os.makedirs(edir)
        servers = []
        for s in range(ns):
            res = comm.SnapshotNow(s, epoch)
            if res["counter"] != res["updates"]:
                raise RecoveryError(
                    f"server {s} advanced mid-snapshot (covered "
                    f"{res['counter']} != live {res['updates']}) inside "
                    "the drain window — quiescence broken")
            name = f"snap_s{s}_v{res['version']}"
            shutil.copytree(os.path.join(snap_root, name),
                            os.path.join(edir, name))
            _write_pointer(edir, s, name)
            servers.append({"rank": s, "snapshot": name,
                            "version": int(res["version"]),
                            "counter": int(res["counter"]),
                            "updates": int(res["updates"])})
            if s == 0:
                _phase("server_write")
        if ns == 1:
            # the server_write window must exist even with one server
            pass

        from .resilience import capture_executor_state
        wstate = capture_executor_state(ex)
        # hetuq error-feedback residuals ride along (Executor._save keeps
        # them for the same reason: a resumed run's first quantized steps
        # must not re-pay absorbed compression error)
        wstate["qresid"] = {
            str(i): np.asarray(ex.state["qresid"][id(n)])
            for i, n in enumerate(ex._qresid_ordered())}
        wstate["client_stats"] = cs
        wfile = f"worker_{rank}.pkl"
        with open(os.path.join(edir, wfile), "wb") as f:
            pickle.dump(wstate, f)
        eras = resize_log(host, port)

        _phase("pre_commit")
        wall_ms = round((time.perf_counter() - t0) * 1e3, 3)
        manifest = {
            "format": MANIFEST_FORMAT, "epoch": epoch, "step": step,
            "world": {"n_workers": nw, "n_servers": ns,
                      "world_version": int(st["world_version"])},
            "servers": servers,
            "total_updates": sum(s["counter"] for s in servers),
            "pushes_ok": pushed,
            "workers": [{"rank": rank, "state_file": wfile}],
            "eras": eras,
            "wall_ms": wall_ms,
        }
        commit_manifest(jobdir, manifest)
        _phase("post_commit")

        # snapshot=True tags this abort as the release of a COMMITTED
        # epoch — the scheduler counts snapshot_epochs from the tag, so a
        # failed snapshot's best-effort abort (the except path below)
        # never inflates the counter
        finish_resize(host, port, abort=True, snapshot=True)
        released = True
        th.join(timeout=timeout)
        if "error" in parked:
            raise RecoveryError(
                f"parked worker failed to release: {parked['error']!r}")
        _export_snapshot_telemetry(epoch, wall_ms)
        return manifest
    except BaseException:
        # best-effort release of every parked worker before propagating —
        # a failed snapshot must not leave the job wedged at the barrier
        if not released:
            try:
                finish_resize(host, port, abort=True)
            except Exception:  # noqa: BLE001 — scheduler may be gone
                pass
            th.join(timeout=5.0)
        raise


def _export_snapshot_telemetry(epoch: int, wall_ms: float) -> None:
    """hetu_job_epoch + snapshot-duration gauges through the telemetry
    bus (no-op when telemetry is off). Never raises."""
    try:
        from . import telemetry as _telemetry
        tel = _telemetry.get()
        if tel is None:
            return
        tel.metrics.gauge("hetu_job_epoch").set(int(epoch))
        tel.metrics.gauge("hetu_snapshot_last_ms").set(float(wall_ms))
        tel.metrics.histogram("hetu_snapshot_duration_ms").observe(
            float(wall_ms))
    except Exception:  # noqa: BLE001 — observability only
        pass


class JobCheckpointer:
    """The Supervisor-facing handle: ``save(ex, step)`` takes one
    coordinated epoch into ``jobdir`` and prunes old ones; wire it as
    ``Supervisor(job_ckptr=...)`` so a SIGTERM grace window upgrades the
    worker-local emergency save to a globally consistent epoch, and/or
    call :meth:`maybe_save` at a step cadence.

    ``barrier_timeout`` bounds the drain barrier (and every other wait
    inside :func:`take_job_snapshot`) for cadence saves; ``None`` means
    take_job_snapshot's 120s default. :meth:`save_preempt` — the
    Supervisor's SIGTERM-grace upgrade path — instead bounds the barrier
    by the preemption grace budget (``grace_s`` or the
    ``HETU_PREEMPT_GRACE_S`` env var, defaulting to heturun's 30s
    window) minus 5s of headroom (floor 2s): a coordinated save
    attempted inside a grace window must fail with time LEFT, so the
    worker-local fallback save still lands before the SIGKILL."""

    #: headroom (seconds) reserved inside the grace window for the
    #: worker-local fallback save after a hung/failed barrier
    GRACE_HEADROOM_S = 5.0

    def __init__(self, jobdir: str, every: Optional[int] = None,
                 keep: int = 2,
                 on_phase: Optional[Callable[[str], None]] = None,
                 barrier_timeout: Optional[float] = None,
                 grace_s: Optional[float] = None):
        self.jobdir = jobdir
        self.every = every
        self.keep = max(1, int(keep))
        self.on_phase = on_phase
        if grace_s is None:
            env = os.environ.get("HETU_PREEMPT_GRACE_S")
            # heturun's SIGTERM grace default is 30s; assume it rather
            # than let a hung barrier ride a 120s default into SIGKILL
            grace_s = float(env) if env else 30.0
        self.grace_s = float(grace_s)
        self.barrier_timeout = barrier_timeout
        self.last_manifest: Optional[dict] = None

    def grace_timeout(self) -> float:
        """Barrier bound for a save inside the preemption grace window."""
        t = max(2.0, self.grace_s - self.GRACE_HEADROOM_S)
        if self.barrier_timeout is not None:
            t = min(t, float(self.barrier_timeout))
        return t

    def save(self, ex, step: int, *,
             timeout: Optional[float] = None) -> dict:
        t = timeout if timeout is not None else self.barrier_timeout
        kw = {"timeout": float(t)} if t is not None else {}
        m = take_job_snapshot(ex, self.jobdir, on_phase=self.on_phase,
                              **kw)
        self.last_manifest = m
        self._prune()
        return m

    def save_preempt(self, ex, step: int) -> dict:
        """The SIGTERM grace-window save: same epoch, but the drain
        barrier is bounded a few seconds below the known grace period so
        the caller's except-based worker-local fallback still runs."""
        return self.save(ex, step, timeout=self.grace_timeout())

    def maybe_save(self, ex, step: int) -> Optional[dict]:
        if self.every and (int(step) + 1) % int(self.every) == 0:
            return self.save(ex, step)
        return None

    def _prune(self) -> None:
        """Keep the newest ``keep`` COMMITTED epochs; drop older ones and
        any torn epoch older than the newest committed one (a torn epoch
        NEWER than it is evidence from a crash-in-progress — left for
        post-mortems, restore skips it anyway)."""
        committed = [e["epoch"] for e in list_epochs(self.jobdir)
                     if e["status"] == "committed"]
        if not committed:
            return
        survivors = set(committed[-self.keep:])
        newest = committed[-1]
        for epoch in _epoch_numbers(self.jobdir):
            if epoch in survivors or epoch > newest:
                continue
            for path in (manifest_path(self.jobdir, epoch),
                         os.path.join(self.jobdir,
                                      epoch_dir_name(epoch))):
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                else:
                    try:
                        os.remove(path)
                    except FileNotFoundError:
                        pass
            # stale re-splits of a pruned epoch go with it
            base = os.path.join(self.jobdir, epoch_dir_name(epoch))
            for n in list(os.listdir(self.jobdir)):
                full = os.path.join(self.jobdir, n)
                if full.startswith(base + "_resplit"):
                    shutil.rmtree(full, ignore_errors=True)


def load_worker_state(ex, manifest: dict, epoch_dir: str) -> dict:
    """Re-impose this rank's persisted state onto a freshly built
    executor (params, optimizer slots, op state, dataloader cursors +
    RNG, step, qresid). The executor must have been built with
    HETU_ELASTIC_JOIN=1 so its init did not overwrite the restored PS
    tables. Returns the raw state dict (the soak reads its
    client_stats)."""
    from .resilience import load_executor_state
    rank = int(os.environ.get("WORKER_ID", "0"))
    rec = next((w for w in manifest["workers"] if int(w["rank"]) == rank),
               None)
    if rec is None:
        raise RecoveryError(
            f"manifest epoch {manifest['epoch']} has no state for worker "
            f"rank {rank}")
    with open(os.path.join(epoch_dir, rec["state_file"]), "rb") as f:
        state = pickle.load(f)
    load_executor_state(ex, state)
    qr = state.get("qresid", {})
    if qr:
        import jax.numpy as jnp
        for i, n in enumerate(ex._qresid_ordered()):
            if str(i) in qr:
                ex.state["qresid"][id(n)] = jnp.asarray(qr[str(i)],
                                                        jnp.float32)
    return state


def restore_executor_from_env(ex, jobdir: str) -> dict:
    """``heturun --restore`` worker leg (Executor calls this when the
    launcher set HETU_RESTORE_DIR): re-resolve the newest committed
    epoch — deterministic, so every rank and the launcher agree without
    another coordination round — re-impose this rank's state, and gate
    on the counter algebra across the restored servers."""
    got = latest_committed_manifest(jobdir)
    if got is None:
        raise RecoveryError(
            f"HETU_RESTORE_DIR={jobdir}: no committed snapshot epoch")
    m, edir = got
    state = load_worker_state(ex, m, edir)
    from . import ps as ps_pkg
    comm = ps_pkg.get_worker_communicate()
    ns = int(os.environ.get("DMLC_NUM_SERVER", "0")) or \
        int(m["world"]["n_servers"])
    verify_restored_job(m, [comm.ServerStats(s) for s in range(ns)])
    print(f"# hetusave: worker restored from epoch {m['epoch']} "
          f"(step {m['step']}, {m['total_updates']} updates verified)",
          file=sys.stderr)
    return state


# ---------------------------------------------------------------------------
# Soak driver (live local_cluster job; modeled on hetu_tpu.chaos.run_job)
# ---------------------------------------------------------------------------

#: the soak job's fixed shape (tiny: one seed's full twin+kill+restore
#: cycle must stay in CI time)
SOAK_ROWS, SOAK_WIDTH, SOAK_SLOTS, SOAK_BATCH = 60, 8, 4, 16


def _soak_batch(seed: int, step: int):
    """Batches are a PURE function of (seed, step): a restored leg
    regenerates exactly the batches the dead job would have consumed —
    the determinism the bit-identity proof needs."""
    rng = np.random.RandomState((int(seed) * 1000003 + int(step))
                                % (2 ** 31 - 1))
    bidx = rng.randint(0, SOAK_ROWS,
                       (SOAK_BATCH, SOAK_SLOTS)).astype(np.float32)
    by = ((bidx >= SOAK_ROWS // 2).sum(axis=1) >
          SOAK_SLOTS // 2).reshape(-1, 1).astype(np.float32)
    return bidx, by


class _scoped_env:
    """Set env vars for one leg, restoring previous values on exit (the
    soak runs several clusters in one process — a leaked DMLC_PS_*
    would contaminate the next leg)."""

    def __init__(self, **kv):
        self.kv = {k: v for k, v in kv.items() if v is not None}
        self.saved: dict = {}

    def __enter__(self):
        for k, v in self.kv.items():
            self.saved[k] = os.environ.get(k)
            os.environ[k] = str(v)
        return self

    def __exit__(self, *exc):
        for k, old in self.saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def run_leg(seed: int, total_steps: int, n_servers: int, jobdir: str,
            snapshot_root: str, *, snap_steps=(), kill_phase=None,
            kill_at_snap: int = 0, restore: bool = False) -> dict:
    """One life of the job. Fresh start or restore-from-jobdir, train to
    ``total_steps`` on (seed, step)-pure batches, coordinated snapshots
    after completing each step in ``snap_steps``; ``kill_phase`` arms a
    simulated whole-job death (every cluster process SIGKILLed, then
    :class:`JobKilled`) at that phase of snapshot number
    ``kill_at_snap`` (0-based among this leg's snapshots)."""
    from .ps.local_cluster import get_live_cluster, local_cluster
    from . import ps as ps_pkg

    prep = prepare_restore(jobdir, n_servers) if restore else None
    snap_count = {"n": 0}

    def on_phase(phase: str) -> None:
        if kill_phase is not None and phase == kill_phase \
                and snap_count["n"] == kill_at_snap:
            for p in get_live_cluster().get("procs", []):
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass
            raise JobKilled(f"job_kill at {phase} of snapshot "
                            f"#{kill_at_snap}")

    env = {"DMLC_PS_SNAPSHOT_DIR": snapshot_root}
    if restore:
        env["DMLC_PS_RESTORE_DIR"] = prep["server_restore_dir"]
        env["HETU_ELASTIC_JOIN"] = "1"
    killed = None
    with _scoped_env(**env):
        with local_cluster(n_servers=n_servers, n_workers=1):
            import hetu_tpu as ht
            ps_pkg.worker_init()
            comm = ps_pkg.get_worker_communicate()
            embed = ht.init.random_normal(
                (SOAK_ROWS, SOAK_WIDTH), stddev=0.1, name="save_embed",
                is_embed=True)
            idx = ht.Variable(name="idx", trainable=False)
            y_ = ht.Variable(name="y_", trainable=False)
            vec = ht.embedding_lookup_op(embed, idx)
            flat = ht.array_reshape_op(vec, (-1, SOAK_SLOTS * SOAK_WIDTH))
            w = ht.init.xavier_uniform((SOAK_SLOTS * SOAK_WIDTH, 1),
                                       name="save_w")
            prob = ht.sigmoid_op(ht.matmul_op(flat, w))
            loss = ht.reduce_mean_op(
                ht.binarycrossentropy_op(prob, y_), [0])
            train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
            ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                             seed=0, comm_mode="PS", prefetch=False)
            losses, consumed, restored_report = [], [], None
            start = 0
            try:
                if restore:
                    load_worker_state(ex, prep["manifest"],
                                      prep["epoch_dir"])
                    sstats = [comm.ServerStats(s)
                              for s in range(n_servers)]
                    restored_report = verify_restored_job(
                        prep["manifest"], sstats)
                    start = int(prep["manifest"]["step"])
                    ex.state["step"] = start
                for step in range(start, int(total_steps)):
                    bidx, by = _soak_batch(seed, step)
                    out = ex.run("train", feed_dict={idx: bidx, y_: by})
                    losses.append(float(out[0].asnumpy()))
                    consumed.append(step * SOAK_BATCH +
                                    np.arange(SOAK_BATCH))
                    if (step + 1) in snap_steps:
                        take_job_snapshot(ex, jobdir, on_phase=on_phase)
                        snap_count["n"] += 1
                rt = ex.ps_runtime
                rt.drain()
                finals = []
                for p in sorted(rt.params.values(),
                                key=lambda p: p.ps_id):
                    if p.sparse:
                        finals.append(rt.pull_sparse_rows(
                            p, np.arange(SOAK_ROWS)))
                    else:
                        finals.append(rt.pull_dense_value(p))
                client_stats = comm.ClientStats()
                server_stats = [comm.ServerStats(s)
                                for s in range(n_servers)]
            except JobKilled as e:
                killed = str(e)
                finals, client_stats, server_stats = [], {}, []
            finally:
                try:
                    ex.close()
                except Exception:  # noqa: BLE001 — cluster may be dead
                    pass
                try:
                    ps_pkg.worker_finish()
                except Exception:  # noqa: BLE001
                    pass
            return {"losses": losses, "finals": finals,
                    "consumed": (np.concatenate(consumed) if consumed
                                 else np.zeros(0, np.int64)),
                    "start": start, "killed": killed,
                    "client_stats": client_stats,
                    "server_stats": server_stats,
                    "restored": restored_report,
                    "prep": prep}


def _check_restored_accounting(client_stats: dict,
                               server_stats: list[dict]) -> dict:
    """Exactly-once write accounting for a RESTORED leg: the fresh
    incarnation's ``pushes_ok`` must equal the updates applied SINCE
    restore (live counter minus restored stamp) summed over servers —
    a lost update or a replayed pre-crash resend breaks the equality."""
    applied = sum(int(s["updates"]) - max(int(s["restored_updates"]), 0)
                  for s in server_stats)
    pushed = int(client_stats.get("pushes_ok", -1))
    ok = pushed == applied
    report = {"name": "restored_update_accounting", "ok": ok,
              "pushes_ok": pushed, "applied_since_restore": applied}
    if not ok:
        from .chaos import InvariantViolation
        raise InvariantViolation(
            f"restored-leg accounting broken: pushes_ok {pushed} != "
            f"updates applied since restore {applied}")
    return report


def run_soak(seed: int, steps: int = 12, n_servers: int = 2,
             kill_phase: str = "pre_commit",
             restore_n_servers: Optional[int] = None,
             jobdir: Optional[str] = None) -> dict:
    """One seeded acceptance cycle: fault-free twin (no snapshots), then
    a life that snapshots twice and is whole-job-killed at
    ``kill_phase`` of the SECOND snapshot, then the restore leg —
    optionally into a different world size (``restore_n_servers``).
    Proves, per docs/FAULT_TOLERANCE.md "Coordinated job snapshots":

    - restore selects the newest COMMITTED epoch only (the kill leaves a
      torn epoch 2 for every phase except post_commit, and the restored
      step pins which epoch was chosen);
    - the restored lineage is loss-bit-identical to the twin and its
      final params match bit-for-bit;
    - sample consumption is exactly-once along the committed lineage;
    - update-counter algebra holds across death and restore;
    - a world-size-changed restore re-splits optimizer state bit-equal.

    Requires HETU_TEST_MODE (set by bin/hetusave like bin/hetuchaos).
    Raises on any broken invariant; returns the full report dict."""
    import tempfile

    from .chaos import (InvariantViolation, check_bit_identical,
                        check_exactly_once_consumption)
    if kill_phase not in PHASES:
        raise ValueError(f"kill_phase {kill_phase!r} not in {PHASES}")
    steps = int(steps)
    snap1, snap2 = max(1, steps // 3), max(2, (2 * steps) // 3)
    owned = jobdir is None
    jobdir = jobdir or tempfile.mkdtemp(prefix="hetusave_job_")
    snaproot = tempfile.mkdtemp(prefix="hetusave_snap_")
    restore_ns = int(restore_n_servers or n_servers)
    try:
        twin = run_leg(seed, steps, n_servers, jobdir + "_twin", snaproot)
        assert twin["killed"] is None

        leg1 = run_leg(seed, steps, n_servers, jobdir, snaproot,
                       snap_steps=(snap1, snap2), kill_phase=kill_phase,
                       kill_at_snap=1)
        if leg1["killed"] is None:
            raise InvariantViolation(
                f"kill at {kill_phase} never fired (snapshots at "
                f"{snap1}/{snap2}, {steps} steps)")

        # the committed lineage the restore must land on
        expect_step = snap2 if kill_phase == "post_commit" else snap1
        got = latest_committed_manifest(jobdir)
        if got is None:
            raise InvariantViolation("no committed epoch after the kill")
        if int(got[0]["step"]) != expect_step:
            raise InvariantViolation(
                f"restore selected step {got[0]['step']}, expected "
                f"{expect_step} — a torn epoch was chosen after a "
                f"{kill_phase} kill")
        torn = [e for e in list_epochs(jobdir)
                if e["status"] != "committed"]
        if kill_phase in ("server_write", "pre_commit") and not torn:
            raise InvariantViolation(
                f"a {kill_phase} kill must leave a torn epoch on disk "
                "(it proves torn-epoch skipping) — none found")

        leg2 = run_leg(seed, steps, restore_ns, jobdir, snaproot,
                       restore=True)
        assert leg2["killed"] is None and leg2["start"] == expect_step

        checks = [
            leg2["restored"],
            _check_restored_accounting(leg2["client_stats"],
                                       leg2["server_stats"]),
            check_bit_identical(
                [np.asarray(leg2["losses"])],
                [np.asarray(twin["losses"][expect_step:])],
                "restored-lineage losses"),
            check_exactly_once_consumption(
                leg2["consumed"],
                twin["consumed"][expect_step * SOAK_BATCH:]),
            check_bit_identical(leg2["finals"], twin["finals"],
                                "final params"),
        ]
        resplit_check = None
        if restore_ns != n_servers:
            resplit_check = _check_resplit_bit_equal(
                leg2["prep"], n_servers)
            checks.append(resplit_check)
        report = {
            "seed": int(seed), "steps": steps, "kill_phase": kill_phase,
            "n_servers": n_servers, "restore_n_servers": restore_ns,
            "snap_steps": [snap1, snap2],
            "restored_step": expect_step,
            "epochs": list_epochs(jobdir),
            "checks": checks,
            "final_loss": leg2["losses"][-1] if leg2["losses"] else None,
            "ok": all(c["ok"] for c in checks),
        }
        return report
    finally:
        shutil.rmtree(snaproot, ignore_errors=True)
        if owned:
            shutil.rmtree(jobdir, ignore_errors=True)
            shutil.rmtree(jobdir + "_twin", ignore_errors=True)


def _check_resplit_bit_equal(prep: dict, old_ns: int) -> dict:
    """The world-size-changed restore's optimizer-state proof: for every
    param, the concatenation of the re-split shards (data + accum +
    accum2 + versions) must be BIT-EQUAL to the concatenation of the
    committed epoch's original shards — rows moved, nothing changed."""
    from .chaos import InvariantViolation
    from .elastic import read_v2_shard
    m = prep["manifest"]
    edir, rdir = prep["epoch_dir"], prep["server_restore_dir"]
    new_ns = prep["n_servers"]
    old = sorted(m["servers"], key=lambda s: s["rank"])
    keys: set[int] = set()
    for s in old:
        for n in os.listdir(os.path.join(edir, s["snapshot"])):
            if n.startswith("param_") and n.endswith(".bin"):
                keys.add(int(n[len("param_"):].split("_", 1)[0]))
    bad = []
    for key in sorted(keys):
        olds = [read_v2_shard(os.path.join(
            edir, s["snapshot"], f"param_{key}_shard{s['rank']}.bin"))
            for s in old]
        news = [read_v2_shard(os.path.join(
            rdir, f"snap_s{r}_v1", f"param_{key}_shard{r}.bin"))
            for r in range(new_ns)]
        for sect in ("data", "accum", "accum2", "versions"):
            a = np.concatenate([s[sect] for s in olds])
            b = np.concatenate([s[sect] for s in news])
            if a.shape != b.shape or (a.tobytes() != b.tobytes()):
                bad.append((key, sect))
    ok = not bad
    report = {"name": "resplit_bit_equal", "ok": ok,
              "n_params": len(keys), "old_n_servers": old_ns,
              "new_n_servers": new_ns, "mismatches": bad}
    if not ok:
        raise InvariantViolation(
            f"re-split changed optimizer state bits: {bad}")
    return report


# ---------------------------------------------------------------------------
# jax-free self-test (bin/hetusave --check)
# ---------------------------------------------------------------------------

def _fake_epoch(jobdir: str, epoch: int, step: int, n_servers: int = 1,
                commit: bool = True, torn: Optional[str] = None) -> dict:
    """A synthetic epoch for the manifest-selection tests: real files,
    no cluster. ``torn`` drops one referenced piece."""
    edir = os.path.join(jobdir, epoch_dir_name(epoch))
    servers = []
    for r in range(n_servers):
        name = f"snap_s{r}_v{epoch}"
        d = os.path.join(edir, name)
        os.makedirs(d, exist_ok=True)
        _write_ps_manifest(os.path.join(d, "manifest.bin"), 10 * epoch, 1)
        _write_pointer(edir, r, name)
        servers.append({"rank": r, "snapshot": name, "version": epoch,
                        "counter": 10 * epoch, "updates": 10 * epoch})
    wfile = "worker_0.pkl"
    with open(os.path.join(edir, wfile), "wb") as f:
        pickle.dump({"step": step}, f)
    m = {"format": MANIFEST_FORMAT, "epoch": epoch, "step": step,
         "world": {"n_workers": 1, "n_servers": n_servers,
                   "world_version": 1},
         "servers": servers,
         "total_updates": sum(s["counter"] for s in servers),
         "workers": [{"rank": 0, "state_file": wfile}], "eras": []}
    if torn == "manifest.bin":
        os.remove(os.path.join(edir, servers[0]["snapshot"],
                               "manifest.bin"))
    elif torn == "worker":
        os.remove(os.path.join(edir, wfile))
    elif torn == "pointer":
        os.remove(os.path.join(edir, "LATEST_s0"))
    if commit:
        commit_manifest(jobdir, m)
    elif torn == "tmp_manifest":
        # a commit that died mid-write: .tmp exists, manifest does not
        with open(manifest_path(jobdir, epoch) + ".tmp", "w") as f:
            f.write(json.dumps(m)[: len(json.dumps(m)) // 2])
    return m


def self_check(out=None) -> int:
    """CI smoke with no cluster and no jax: manifest commit atomicity +
    newest-committed-only selection (torn epochs of every shape
    skipped), epoch numbering, re-split bit-equality + counter algebra,
    the per-server manifest writer's binary layout, phase validation,
    and the job_kill spec-grammar round trip. Returns 0 on success."""
    import struct
    import tempfile
    out = out or sys.stdout

    with tempfile.TemporaryDirectory(prefix="hetusave_check_") as td:
        # -- manifest selection: newest COMMITTED only ---------------------
        assert latest_committed_manifest(td) is None
        _fake_epoch(td, 1, step=4)
        got = latest_committed_manifest(td)
        assert got is not None and got[0]["epoch"] == 1
        # epoch 2 torn in each shape: never selected over committed 1
        for torn in ("tmp_manifest", "manifest.bin", "worker", "pointer"):
            shutil.rmtree(os.path.join(td, epoch_dir_name(2)),
                          ignore_errors=True)
            for leftover in (manifest_path(td, 2),
                             manifest_path(td, 2) + ".tmp"):
                if os.path.exists(leftover):
                    os.remove(leftover)
            _fake_epoch(td, 2, step=8, commit=torn != "tmp_manifest",
                        torn=torn)
            got = latest_committed_manifest(td)
            assert got is not None and got[0]["epoch"] == 1, torn
        # unparseable JSON: skipped, not fatal
        with open(manifest_path(td, 3), "w") as f:
            f.write("{not json")
        assert latest_committed_manifest(td)[0]["epoch"] == 1
        os.remove(manifest_path(td, 3))
        # a COMMITTED epoch 2 wins
        shutil.rmtree(os.path.join(td, epoch_dir_name(2)))
        os.remove(manifest_path(td, 2))
        _fake_epoch(td, 2, step=8)
        assert latest_committed_manifest(td)[0]["epoch"] == 2
        # next_epoch never collides with torn leftovers
        assert next_epoch(td) == 3
        rows = list_epochs(td)
        assert [r["status"] for r in rows] == ["committed", "committed"]

    # -- re-split: bit-equality + counter algebra --------------------------
    from .elastic import read_v2_shard, write_v2_shard, _range_split
    with tempfile.TemporaryDirectory(prefix="hetusave_check_") as td:
        edir = os.path.join(td, epoch_dir_name(1))
        rng = np.random.RandomState(7)
        rows, width = 10, 3
        full = {
            "data": rng.randn(rows * width).astype(np.float32),
            "accum": rng.randn(rows * width).astype(np.float32),
            "accum2": rng.randn(rows * width).astype(np.float32),
            "versions": np.arange(rows, dtype=np.int64)}
        servers = []
        for r, (lo, hi) in enumerate(_range_split(rows, 2)):
            name = f"snap_s{r}_v1"
            d = os.path.join(edir, name)
            os.makedirs(d)
            sl = slice(lo * width, hi * width)
            write_v2_shard(
                os.path.join(d, f"param_5_shard{r}.bin"),
                {"kind": 1, "rows": hi - lo, "len": (hi - lo) * width,
                 "width": width, "otype": 4, "step": 9,
                 "lrs": np.asarray([0.1], np.float32),
                 "data": full["data"][sl], "accum": full["accum"][sl],
                 "accum2": full["accum2"][sl],
                 "versions": full["versions"][lo:hi]})
            _write_ps_manifest(os.path.join(d, "manifest.bin"), 21, 1)
            _write_pointer(edir, r, name)
            servers.append({"rank": r, "snapshot": name, "version": 1,
                            "counter": 21, "updates": 21})
        m = {"format": 1, "epoch": 1, "step": 9,
             "world": {"n_workers": 1, "n_servers": 2, "world_version": 1},
             "servers": servers, "total_updates": 42,
             "workers": [], "eras": []}
        for new_ns in (1, 3):
            dst = os.path.join(td, f"re{new_ns}")
            rep = resplit_epoch(edir, dst, new_ns, m)
            assert rep["n_params"] == 1
            assert sum(rep["counters"]) == 42  # sum-preserving
            news = [read_v2_shard(os.path.join(
                dst, f"snap_s{r}_v1", f"param_5_shard{r}.bin"))
                for r in range(new_ns)]
            for sect in ("data", "accum", "accum2", "versions"):
                cat = np.concatenate([s[sect] for s in news])
                assert cat.tobytes() == full[sect].tobytes(), sect
            # native-manifest layout: magic + {version, counter, n, 0}
            with open(os.path.join(dst, "snap_s0_v1", "manifest.bin"),
                      "rb") as f:
                raw = f.read()
            magic, = struct.unpack("<q", raw[:8])
            version, counter, n_params, n_clients = struct.unpack(
                "<4Q", raw[8:40])
            assert magic == _PS_MANIFEST_MAGIC and version == 1
            assert counter == rep["counters"][0]
            assert n_params == 1 and n_clients == 0
            # pointer files name existing dirs (atomic flip contract)
            for r in range(new_ns):
                with open(os.path.join(dst, f"LATEST_s{r}")) as f:
                    assert os.path.isdir(os.path.join(dst,
                                                      f.read().strip()))
        # counter-algebra gate: accept exact, refuse drift
        verify_restored_job(m, [{"restored_updates": 21},
                                {"restored_updates": 21}])
        try:
            verify_restored_job(m, [{"restored_updates": 21},
                                    {"restored_updates": 20}])
            raise AssertionError("counter drift not caught")
        except RecoveryError:
            pass
        try:
            prepare_restore(os.path.join(td, "nowhere"))
            raise AssertionError("missing jobdir not caught")
        except RecoveryError:
            pass

    # -- phases + the job_kill spec grammar --------------------------------
    assert PHASES == ("pre_barrier", "server_write", "pre_commit",
                      "post_commit")
    try:
        arm_job_kill("mid_flight")
        raise AssertionError("bad phase accepted")
    except ValueError:
        pass
    arm_job_kill("pre_commit")
    assert armed_kill_phase() == "pre_commit"
    _armed_kill["phase"] = None
    from .resilience import FaultInjector
    fi = FaultInjector("job_kill@3:server_write")
    assert fi.entries[0]["arg"] == "server_write"
    assert FaultInjector("job_kill@2").entries[0]["arg"] is None
    for bad in ("job_kill@2:mid_flight", "job_murder@2"):
        try:
            FaultInjector(bad)
            raise AssertionError(f"{bad!r} accepted")
        except ValueError as e:
            # rejections must NAME the legal vocabulary
            assert ("pre_barrier" in str(e)) or ("nan_grads" in str(e))

    print("hetusave --check: manifest atomicity + newest-committed "
          "selection, re-split bit-equality, counter algebra, and the "
          "job_kill grammar OK", file=out)
    return 0


# ---------------------------------------------------------------------------
# CLI (bin/hetusave)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    """``hetusave --seed S``: live whole-job-kill soak (twin + killed
    life + restore, every invariant checked). ``--seeds`` rotates the
    kill through every snapshot phase; ``--resize N`` restores the last
    seed into N servers; ``--check`` is the jax-free CI self-test;
    ``--list DIR`` inventories a job's epochs; ``--restore-prep DIR``
    resolves (and, with --servers, re-splits) the newest committed
    epoch without starting a job. Exit 0 = green."""
    import argparse
    import json as _json

    ap = argparse.ArgumentParser(
        prog="hetusave",
        description="coordinated job-wide consistent checkpoints + "
                    "whole-job crash recovery (docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--check", action="store_true",
                    help="jax-free self-test (CI smoke); exit 0/1")
    ap.add_argument("--list", metavar="DIR", default=None,
                    help="inventory a job dir's epochs (committed/torn)")
    ap.add_argument("--restore-prep", metavar="DIR", default=None,
                    help="resolve the newest committed epoch (with "
                         "--servers N: build the re-split) and print it")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", type=str, default=None,
                    help="comma-separated seed list (overrides --seed); "
                         "kill phase rotates per seed")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--phase", choices=PHASES, default=None,
                    help="kill phase (default: rotate through all)")
    ap.add_argument("--resize", type=int, default=None,
                    help="restore the LAST seed into this many servers "
                         "(world-size-changed recovery)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable per-seed reports on stdout")
    args = ap.parse_args(argv)

    if args.check:
        return self_check()
    if args.list is not None:
        for row in list_epochs(args.list):
            print(_json.dumps(row, sort_keys=True))
        return 0
    if args.restore_prep is not None:
        prep = prepare_restore(args.restore_prep, args.servers
                               if args.resize is None else args.resize)
        print(_json.dumps(
            {"epoch": prep["manifest"]["epoch"],
             "step": prep["manifest"]["step"],
             "server_restore_dir": prep["server_restore_dir"],
             "n_servers": prep["n_servers"],
             "resplit": prep["resplit"]}, sort_keys=True))
        return 0

    os.environ.setdefault("HETU_TEST_MODE", "1")
    seeds = ([int(s) for s in args.seeds.split(",")]
             if args.seeds else [args.seed])
    rc = 0
    for i, seed in enumerate(seeds):
        phase = args.phase or PHASES[i % len(PHASES)]
        resize = (args.resize if args.resize is not None
                  and i == len(seeds) - 1 else None)
        try:
            report = run_soak(seed, steps=args.steps,
                              n_servers=args.servers, kill_phase=phase,
                              restore_n_servers=resize)
        except Exception as e:  # noqa: BLE001 — report and fail the seed
            print(f"seed {seed} [{phase}]: FAIL — {e}", file=sys.stderr)
            rc = 1
            continue
        if args.json:
            print(_json.dumps(report, default=str, sort_keys=True))
        else:
            print(f"seed {seed} [{phase}"
                  f"{f' -> {resize} servers' if resize else ''}]: "
                  f"restored step {report['restored_step']}, "
                  f"{len(report['checks'])} checks green, final loss "
                  f"{report['final_loss']:.6f}")
        if not report["ok"]:
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
