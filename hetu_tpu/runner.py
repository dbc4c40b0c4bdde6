"""``heturun`` — cluster launcher (reference ``python/runner.py`` +
``bin/heturun``).

Usage: ``heturun -c cluster.yml python train.py [args...]``

The yaml lists nodes with host/servers/workers/chief (reference
runner.py:158-184). On a single machine, PS roles run as local processes and
workers as subprocesses with WORKER_ID env. Across machines, remote roles are
started over ``ssh`` (the reference uses paramiko + mpirun; TPU pods use one
process per host, so workers get ``jax.distributed`` coordinator env vars
instead of an MPI world).

One process per chip: a process that has initialised a jax backend holds its
chips, and a second one that needs them fails or hangs. So this parent never
touches a jax backend (importing the package loads the jax module, no more),
and several local workers are each bound to one chip of the host through the
environment libtpu reads (``plan_local_chips``); more local workers than
chips is refused before anything starts.
"""
from __future__ import annotations

import argparse
import glob
import multiprocessing
import os
import shlex
import signal
import socket
import subprocess
import sys
import time

import yaml

# mirrored from hetu_tpu.resilience (EXIT_PREEMPTED/EXIT_WATCHDOG) without
# importing the package here: the launcher parent must stay jax-free
EXIT_PREEMPTED = 75
EXIT_WATCHDOG = 85

_procs: list = []
_shells: list = []
_tel_dir: str = ""   # --telemetry-dir (run summary written at every exit)

# what libtpu reads to bind a process to one chip of its host
_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "{chip}",
             "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
             "TPU_PROCESS_BOUNDS": "1,1,1"}


def local_tpu_chips() -> int:
    """How many TPU chips this host has, counted from the device nodes the
    driver exposes — no jax, so the launcher parent stays off the chips."""
    return len(glob.glob("/dev/vfio/[0-9]*")
               or glob.glob("/dev/accel[0-9]*"))


def plan_local_chips(n_workers: int, env: dict):
    """How many chips the local workers share out, one each (worker ``w``
    gets chip ``w``) — or None when there is nothing to assign: workers
    pinned to the CPU need no chip, and a single worker keeps the whole
    host (it is the one process). More workers than chips raises
    ``SystemExit`` with one line."""
    if n_workers <= 1 or \
            env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    chips = local_tpu_chips()
    if n_workers > chips:
        raise SystemExit(
            f"heturun: {n_workers} local workers but {chips} TPU chip(s) "
            "on this host — one process per chip (set JAX_PLATFORMS=cpu to "
            "run the workers on the CPU on purpose)")
    return chips


def worker_chip_env(chips, w: int) -> dict:
    """Environment binding local worker ``w`` to chip ``w`` ({} when
    ``plan_local_chips`` assigned none)."""
    if chips is None:
        return {}
    if w >= chips:   # an elastic grow past the host's chips
        raise RuntimeError(f"worker {w} has no chip: this host has {chips}")
    return {k: v.format(chip=w) for k, v in _CHIP_ENV.items()}


def _story_mod():
    """The shared ledger reader (hetu_tpu/telemetry/story.py), loaded by
    file path: the launcher parent must stay jax-free, and importing the
    hetu_tpu package would pay the jax import (story.py is stdlib-only)."""
    mod = (sys.modules.get("hetu_tpu.telemetry.story")
           or sys.modules.get("_hetustory"))
    if mod is not None:
        return mod
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "telemetry", "story.py")
    spec = importlib.util.spec_from_file_location("_hetustory", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_hetustory"] = mod
    spec.loader.exec_module(mod)
    return mod


def _scan_rank_jsonl(tel_dir):
    """Per-rank final step + the elastic world/resize history from the
    rank JSONL files (via the shared hetustory reader, which orders each
    file's rotated ``.1`` backup before its live generation): the
    post-mortem of an elastic run should start from run_summary.json, not
    from re-deriving the membership timeline by hand."""
    story = _story_mod()
    final_steps = {}
    resizes = []
    world_versions = set()
    plan = None
    for path in story.ledger_files("metrics", tel_dir):
        # iterate, never slurp: an uncapped (HETU_TELEMETRY_MAX_MB unset)
        # long-run rank file can be huge, and this runs in the launcher
        for row in story.iter_rows(path):
            rec = row.rec
            rank = rec.get("rank")
            if rec.get("kind") == "step" and "step" in rec:
                key = str(rank if rank is not None else "?")
                final_steps[key] = max(final_steps.get(key, -1),
                                       int(rec["step"]))
            elif rec.get("kind") == "event" and \
                    str(rec.get("name", "")).startswith("resize"):
                ev = {k: rec.get(k) for k in
                      ("ts", "name", "rank", "step", "world_version",
                       "n_workers", "n_servers", "duration_ms")
                      if rec.get(k) is not None}
                resizes.append(ev)
                if rec.get("world_version") is not None:
                    world_versions.add(int(rec["world_version"]))
            elif rec.get("kind") == "plan" and plan is None:
                # the hetuwatch plan stamp (docs/OBSERVABILITY.md
                # pillar 6): the adopted layout, per-param comm
                # decisions and predicted step — rank 0 stamps first;
                # every rank adopts the same plan, so first wins
                plan = {k: rec.get(k) for k in
                        ("mesh", "comm_mode", "comm_quant", "zero1",
                         "remat", "predicted_step_ms",
                         "predicted_legs", "params")
                        if rec.get(k) is not None}
    resizes.sort(key=lambda e: e.get("ts", 0))
    return final_steps, resizes, sorted(world_versions), plan


def _write_telemetry_summary(rc, preempted, num_workers):
    """Aggregate the run's per-rank telemetry files into one manifest
    (run_summary.json) in the shared directory — ranks already write
    metrics-r<N>.jsonl / trace-r<N>.json side by side (WORKER_ID keys the
    file names), so the launcher's job is the closing inventory + outcome,
    per-rank final steps, and the elastic resize/world-version history."""
    if not _tel_dir:
        return
    import glob
    import json
    final_steps, resizes, world_versions, plan = _scan_rank_jsonl(_tel_dir)
    summary = {
        "workers": num_workers,
        "exit_code": rc,
        "preempted": bool(preempted),
        "final_steps": final_steps,
        "files": sorted(os.path.basename(p) for p in
                        glob.glob(os.path.join(_tel_dir, "*"))
                        if not p.endswith(".tmp")
                        and os.path.basename(p) != "run_summary.json"),
    }
    if resizes:
        summary["resizes"] = resizes
        summary["world_versions"] = world_versions
    if plan:
        summary["plan"] = plan
    # hetupilot actuation history (docs/FAULT_TOLERANCE.md "Self-tuning
    # with guardrails"): the era ledger rolls up next to the plan it tuned
    try:
        from hetu_tpu.pilot import summarize_dir
        pilot = summarize_dir(os.path.join(_tel_dir, "pilot")) \
            or summarize_dir(_tel_dir)
        if pilot is not None:
            summary["pilot"] = pilot
    except Exception as e:  # noqa: BLE001 — the summary must still land
        print(f"# heturun: pilot summary skipped ({e})", file=sys.stderr)
    try:
        with open(os.path.join(_tel_dir, "run_summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    except OSError as e:
        print(f"# heturun: telemetry summary skipped ({e})",
              file=sys.stderr)


def _signal_handler(sig, frame):
    """Preemption-aware teardown: forward the signal to the WORKERS first so
    their resilience.PreemptionHandler can take the emergency checkpoint,
    give them a grace window, then tear down the PS roles. Exits with
    EXIT_PREEMPTED on SIGTERM (the cluster-level 'preempted cleanly' code)
    and the conventional 130 on SIGINT."""
    for p in _shells:
        if p.poll() is None:
            try:
                p.send_signal(sig)
            except OSError:
                pass
    grace = float(os.environ.get("HETU_PREEMPT_GRACE_S", "30"))
    deadline = time.time() + grace
    for p in _shells:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            # SIGKILL: it already had the SIGTERM + grace window — a wedged
            # worker must not outlive the launcher as an orphan
            p.kill()
    for p in _procs:
        p.terminate()
    rc = EXIT_PREEMPTED if sig == signal.SIGTERM else 130
    _write_telemetry_summary(rc, sig == signal.SIGTERM, len(_shells))
    sys.exit(rc)


def _get_available_port(addr: str) -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((addr, 0))
        return s.getsockname()[1]


def parse_cluster(path):
    settings = yaml.safe_load(open(path).read())
    attributes = {"host", "servers", "workers", "chief"}
    hosts, servers, workers = [], {}, {}
    chief = None
    for node in settings["nodes"]:
        assert set(node.keys()) <= attributes, \
            f"invalid node attributes: {set(node.keys())} / {attributes}"
        hosts.append(node["host"])
        if node.get("servers", 0):
            servers[node["host"]] = int(node["servers"])
        if node.get("workers", 0):
            workers[node["host"]] = int(node["workers"])
        if node.get("chief", False):
            assert chief is None, "there should be only one chief"
            chief = node["host"]
    assert chief, "there should be one chief"
    return hosts, servers, workers, chief


def _sched_entry(env):
    from hetu_tpu.launcher import start_sched
    start_sched(env)


def _server_entry(server_id, env):
    from hetu_tpu.launcher import start_server
    start_server(server_id, env)


def main(argv=None):
    signal.signal(signal.SIGINT, _signal_handler)
    signal.signal(signal.SIGTERM, _signal_handler)
    parser = argparse.ArgumentParser(prog="heturun")
    parser.add_argument("-c", "--config", required=True,
                        help="cluster yaml (nodes: host/servers/workers/chief)")
    parser.add_argument("-i", "--identify", default="",
                        help="SSH identity file for multi-machine launch")
    parser.add_argument("-r", "--max-restarts", type=int, default=0,
                        help="restart a worker that exits with a recoverable "
                             "(nonzero, non-preempted) code up to N times "
                             "total, with exponential backoff — workers "
                             "resume from their checkpointer (single-host "
                             "mode; see docs/FAULT_TOLERANCE.md)")
    parser.add_argument("--ps-max-respawns", type=int, default=0,
                        help="PS high availability (single-host mode): "
                             "servers write continuous shard snapshots "
                             "(DMLC_PS_SNAPSHOT_DIR/_MS) and a supervisor "
                             "respawns a dead server from the freshest "
                             "snapshot up to N times total; workers get a "
                             "failover deadline (DMLC_PS_FAILOVER_DEADLINE_"
                             "MS) so in-flight requests re-issue instead of "
                             "failing (see docs/FAULT_TOLERANCE.md)")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic membership (single-host PS mode): a "
                             "worker that exits abnormally becomes a "
                             "planned DEPARTURE (the launcher proposes a "
                             "world shrink via the scheduler's two-phase "
                             "resize instead of restarting it); SIGUSR1 "
                             "grows the world by one worker, SIGUSR2 by "
                             "one PS server (key ranges migrate live). "
                             "Workers run with HETU_ELASTIC=1 and drain/"
                             "commit at step boundaries (see 'Elastic "
                             "membership' in docs/FAULT_TOLERANCE.md)")
    parser.add_argument("--restore", metavar="JOBDIR", default="",
                        help="reconstruct the whole job from the newest "
                             "COMMITTED coordinated snapshot epoch under "
                             "JOBDIR (written by hetusave / "
                             "resilience.JobCheckpointer): servers restore "
                             "the epoch's pinned shard snapshots "
                             "(DMLC_PS_RESTORE_DIR), workers re-impose "
                             "params/optimizer/dataloader/RNG state and "
                             "verify the update-counter algebra before "
                             "step one. The epoch may be restored into a "
                             "DIFFERENT world size — key ranges re-split "
                             "offline, optimizer state rides bit-for-bit "
                             "(single-host PS mode; see "
                             "docs/FAULT_TOLERANCE.md 'Coordinated job "
                             "snapshots')")
    parser.add_argument("--telemetry-dir", default="",
                        help="shared telemetry directory: workers run with "
                             "HETU_TELEMETRY_DIR set (HETU_TELEMETRY "
                             "defaults to 'metrics' unless already set), "
                             "each rank writes metrics-r<N>.jsonl / "
                             "trace-r<N>.json there, the PS supervisor "
                             "appends ps_supervisor.jsonl, and the launcher "
                             "writes run_summary.json on exit; inspect with "
                             "bin/hetutop (docs/OBSERVABILITY.md)")
    parser.add_argument("--pilot", action="store_true",
                        help="bounded self-tuning (single-host PS mode): "
                             "workers run with HETU_PILOT=1 (HETU_WATCH "
                             "defaults on) so the hetupilot controller acts "
                             "on hetuwatch's plan-divergence/SLO "
                             "recommendations — each actuation is an era "
                             "through the elastic two-phase protocol, "
                             "measured for K windows and rolled back on "
                             "regression. The actuation ledger "
                             "(pilot.jsonl) lands under the telemetry dir "
                             "and is folded into run_summary.json; inspect "
                             "with bin/hetupilot (docs/FAULT_TOLERANCE.md "
                             "'Self-tuning with guardrails')")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="worker command, e.g. python train.py")
    args = parser.parse_args(argv)
    hosts, servers, workers, chief = parse_cluster(args.config)
    num_servers = sum(servers.values())
    num_workers = sum(workers.values())
    enable_ps = num_servers > 0
    chief_address = (socket.gethostbyname(socket.gethostname())
                     if len(hosts) > 1 else "127.0.0.1")
    port = _get_available_port(chief_address)
    print(f"Cluster: {{ chief: {chief}, servers({num_servers}): {servers}, "
          f"workers({num_workers}): {workers} }}")

    env = dict(os.environ)
    # one process per chip; refused here, before any role is started
    chips = plan_local_chips(num_workers, env) if len(hosts) == 1 else None
    # workers share one persistent compile cache, handed over by name
    from hetu_tpu.utils import compile_cache_path
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_path())
    # Run identity (docs/OBSERVABILITY.md pillar 7): every JSONL row,
    # pilot ledger line and flight ring this job writes carries
    # (run_id, inc). A fresh launch mints the id; a relaunch that inherited
    # HETU_RUN_ID (an outer supervisor / k8s restart) keeps it and bumps
    # the incarnation — so a reused telemetry dir disambiguates runs
    # instead of silently interleaving them.
    if env.get("HETU_RUN_ID"):
        try:
            run_inc = int(env.get("HETU_RUN_INCARNATION", "-1")) + 1
        except ValueError:
            run_inc = 1
    else:
        env["HETU_RUN_ID"] = (time.strftime("%Y%m%d-%H%M%S")
                              + f"-{os.getpid()}")
        run_inc = 0
    env["HETU_RUN_INCARNATION"] = str(run_inc)
    os.environ["HETU_RUN_ID"] = env["HETU_RUN_ID"]
    os.environ["HETU_RUN_INCARNATION"] = env["HETU_RUN_INCARNATION"]
    if args.telemetry_dir:
        global _tel_dir
        _tel_dir = os.path.abspath(args.telemetry_dir)
        os.makedirs(_tel_dir, exist_ok=True)
        env["HETU_TELEMETRY_DIR"] = _tel_dir
        env.setdefault("HETU_TELEMETRY", "metrics")
        # the PS supervisor runs in THIS process and reads the env directly
        os.environ["HETU_TELEMETRY_DIR"] = _tel_dir
        # hetutrail (docs/OBSERVABILITY.md pillar 5): HETU_TRAIL=1 arms the
        # PS-wire span rings for EVERY role, flushing next to the metrics
        # files so hetutrail joins them from one directory
        if os.environ.get("HETU_TRAIL", "").strip().lower() in (
                "1", "true", "yes", "on"):
            env.setdefault("HETU_TRAIL_DIR", _tel_dir)
            os.environ.setdefault("HETU_TRAIL_DIR", _tel_dir)
    pilot_on = args.pilot and enable_ps and len(hosts) == 1
    if args.pilot and not pilot_on:
        # never let an operator believe self-tuning is armed when it is not
        print("# heturun: --pilot requires single-host PS mode; the "
              "self-tuning controller is OFF for this cluster",
              file=sys.stderr)
    if pilot_on:
        env["HETU_PILOT"] = "1"
        # the controller consumes the sentinel's stream: watching defaults
        # on (explicit HETU_WATCH=0 still wins and disables both)
        env.setdefault("HETU_WATCH", "1")
        if _tel_dir:
            env.setdefault("HETU_PILOT_DIR", os.path.join(_tel_dir, "pilot"))
    ps_ha = enable_ps and args.ps_max_respawns > 0 and len(hosts) == 1
    if enable_ps and args.ps_max_respawns > 0 and len(hosts) > 1:
        # don't let an operator believe HA is armed when it is not: the
        # supervisor only drives local children (remote respawn needs a
        # per-host agent), so multi-host runs get no self-healing yet
        print("# heturun: --ps-max-respawns is single-host only; PS "
              "high availability is OFF for this multi-host cluster",
              file=sys.stderr)
    if enable_ps:
        env.update({
            "DMLC_PS_ROOT_URI": chief_address,
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_SERVER": str(num_servers),
            "DMLC_NUM_WORKER": str(num_workers),
        })
    ps_snap_created = None
    if ps_ha:
        # PS high availability: snapshots + supervised respawn + worker
        # failover. Explicit env wins over the defaults.
        from hetu_tpu.ps.supervisor import apply_ha_env_defaults
        ps_snap_created = apply_ha_env_defaults(env)
    if args.restore:
        if not (enable_ps and len(hosts) == 1):
            print("# heturun: --restore requires single-host PS mode",
                  file=sys.stderr)
            return 2
        # resolve (and, on a world-size change, re-split) BEFORE any role
        # spawns: a job must never half-start against an unrestorable dir
        from hetu_tpu.recovery import RecoveryError, prepare_restore
        try:
            prep = prepare_restore(os.path.abspath(args.restore),
                                   num_servers)
        except RecoveryError as e:
            print(f"# heturun: --restore failed: {e}", file=sys.stderr)
            return 2
        m = prep["manifest"]
        env["DMLC_PS_RESTORE_DIR"] = prep["server_restore_dir"]
        # workers: Executor re-imposes this rank's state from the job dir
        # and verifies the counter algebra (recovery.restore_executor_from_env)
        env["HETU_RESTORE_DIR"] = os.path.abspath(args.restore)
        # restored workers are JOINERS: InitTensor must not push fresh
        # values over the restored tables, and init barriers are moot
        env["HETU_ELASTIC_JOIN"] = "1"
        rs = prep["resplit"]
        print(f"# heturun --restore: epoch {m['epoch']} (step {m['step']}, "
              f"{m['total_updates']} updates) from {args.restore}"
              + (f"; re-split {rs['old_n_servers']} -> "
                 f"{rs['new_n_servers']} servers" if rs else ""))
    elastic_on = args.elastic and enable_ps and len(hosts) == 1
    elastic_dir = None
    if args.elastic and not elastic_on:
        # never let an operator believe elasticity is armed when it is not
        print("# heturun: --elastic requires single-host PS mode; elastic "
              "membership is OFF for this cluster", file=sys.stderr)
    if elastic_on:
        import tempfile
        elastic_dir = tempfile.mkdtemp(prefix="hetu_elastic_")
        env["HETU_ELASTIC"] = "1"
        env["HETU_ELASTIC_DIR"] = elastic_dir

    ctx = multiprocessing.get_context("spawn")
    ps_sup = None
    if len(hosts) == 1:
        server_procs = {}
        if enable_ps:
            _procs.append(ctx.Process(target=_sched_entry, args=(env,)))
            for i in range(num_servers):
                server_procs[i] = ctx.Process(target=_server_entry,
                                              args=(i, env))
                _procs.append(server_procs[i])
            for p in _procs:
                p.start()
            if ps_ha:
                from hetu_tpu.ps.supervisor import start_mp_supervisor
                ps_sup = start_mp_supervisor(
                    ctx, _server_entry, env, server_procs, _procs.append,
                    max_respawns=args.ps_max_respawns)
        def spawn_worker(w, join=False, incarnation=0):
            wenv = dict(env)
            wenv["WORKER_ID"] = str(w)
            if incarnation:
                # an auto-resume respawn is a new incarnation of the same
                # run: its telemetry rows must not be indistinguishable
                # from its dead predecessor's
                try:
                    base_inc = int(env.get("HETU_RUN_INCARNATION", "0"))
                except ValueError:
                    base_inc = 0
                wenv["HETU_RUN_INCARNATION"] = str(base_inc + incarnation)
            if enable_ps:
                wenv["DMLC_ROLE"] = "worker"
            if join:
                # late joiner: skip init pushes/barriers, bootstrap step +
                # data partition from the scheduler's world log
                wenv["HETU_ELASTIC_JOIN"] = "1"
            # multi-chip single host: each worker is one jax process on
            # one chip of its own
            wenv["HETU_NUM_WORKER"] = str(num_workers)
            wenv.update(worker_chip_env(chips, w))
            p = subprocess.Popen(args.command, env=wenv)
            _shells.append(p)   # visible to the signal handler
            return p

        # -- elastic membership (docs/FAULT_TOLERANCE.md) -------------------
        # The launcher parent IS the resize coordinator: worker deaths
        # propose shrinks, SIGUSR1/SIGUSR2 (or the supervisor's scale
        # policy) propose grows. All resizes run inline in the reap loop —
        # the drain completes when the survivors reach their next step
        # boundary, bounded by HETU_ELASTIC_DRAIN_TIMEOUT_S.
        usr_grow = {"worker": 0, "server": 0}
        if elastic_on:
            signal.signal(signal.SIGUSR1,
                          lambda *_: usr_grow.__setitem__(
                              "worker", usr_grow["worker"] + 1))
            signal.signal(signal.SIGUSR2,
                          lambda *_: usr_grow.__setitem__(
                              "server", usr_grow["server"] + 1))
        # supervisor-thread grow requests ride their own queue: usr_grow's
        # read-modify-write is only safe from the signal handlers (which
        # run on the main thread); a cross-thread += would race the main
        # loop's decrement and duplicate or drop a grow. list.append/pop
        # are atomic under the GIL.
        scale_requests: list = []
        if elastic_on and ps_sup is not None:
            # telemetry-driven scale policy: the supervisor feeds raw
            # kServerStats rows each poll; a grow recommendation takes the
            # same path as an operator SIGUSR2
            from hetu_tpu.elastic import ScalePolicy
            ps_sup.scale_policy = ScalePolicy(max_servers=int(os.environ.get(
                "HETU_ELASTIC_MAX_SERVERS", str(num_servers + 2))))
            ps_sup.on_scale = lambda d: scale_requests.append(d)

        def elastic_coord():
            from hetu_tpu.elastic import ElasticCoordinator
            return ElasticCoordinator(
                env.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
                int(env.get("DMLC_PS_ROOT_PORT", "13200")),
                drain_timeout_s=float(os.environ.get(
                    "HETU_ELASTIC_DRAIN_TIMEOUT_S", "60")))

        def elastic_world():
            from hetu_tpu.elastic import resize_state
            return resize_state(env.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
                                int(env.get("DMLC_PS_ROOT_PORT", "13200")))

        # ranks that left the world but are not yet removed from the
        # scheduler's member set: abnormal exits resize immediately; clean
        # (rc=0) completions defer to the next resize — their partitions
        # are fully consumed, and resizing on every natural completion
        # would stall teardown when the whole fleet finishes together
        pending_departed: dict = {}

        def note_departure(w):
            step = -1
            try:
                with open(os.path.join(elastic_dir,
                                       f"progress_r{w}")) as f:
                    step = int(f.read().strip())
            except (OSError, ValueError):
                pass  # unknown progress: the scheduler falls back
            pending_departed[w] = step

        def elastic_resize(d_workers=0, d_servers=0):
            """One membership change folding in every pending departure.
            ``d_workers``/``d_servers`` grow the world by that many."""
            st = elastic_world()
            removed = [r for r in pending_departed if r in st["members"]]
            steps = [pending_departed[r] for r in removed]
            new_nw = len(st["members"]) - len(removed) + d_workers
            new_ns = st["n_servers"] + d_servers
            if new_nw < 1:
                return None  # the last worker left: nothing to resize for

            spawned_sids: list = []

            def spawn_srv(sid):
                p = ctx.Process(target=_server_entry, args=(sid, env))
                p.start()
                _procs.append(p)
                server_procs[sid] = p
                spawned_sids.append(sid)
                if ps_sup is not None:
                    ps_sup.watch_server(sid, p)

            try:
                report = elastic_coord().resize(
                    new_nw, new_ns, removed=removed, removed_steps=steps,
                    spawn_server=spawn_srv if d_servers else None,
                    spawn_worker=(lambda r: running.__setitem__(
                        r, spawn_worker(r, join=True)))
                    if d_workers else None)
            except Exception:
                # an aborted grow must not leave the joining server as an
                # orphan: it never became part of the committed world, so
                # reap it and drop it from supervision (its death must not
                # burn respawn budget)
                for sid in spawned_sids:
                    p = server_procs.pop(sid, None)
                    if p is not None:
                        p.terminate()
                        p.join(timeout=10)
                    if ps_sup is not None:
                        ps_sup.unwatch_server(sid)
                raise
            for r in removed:
                pending_departed.pop(r, None)
            return report

        # hetutrail straggler watch (docs/OBSERVABILITY.md pillar 5): tail
        # the rank JSONLs for cross-rank step skew; K-consecutive straggler
        # events land in trail-events.jsonl and — under --elastic — reach
        # the supervisor's ScalePolicy like any other pressure signal.
        skew_mon = None
        skew_next_poll = 0.0
        if _tel_dir and num_workers > 1:
            try:
                from hetu_tpu.telemetry.trail import SkewMonitor

                def _on_straggler(ev):
                    print(f"# heturun: straggler rank {ev.get('rank')} @ "
                          f"step {ev.get('step')}: {ev.get('step_ms')}ms vs "
                          f"median {ev.get('median_ms')}ms",
                          file=sys.stderr, flush=True)
                    if ps_sup is not None and \
                            getattr(ps_sup, "scale_policy", None) is not None:
                        rec = ps_sup.scale_policy.note_straggler(ev)
                        if rec is not None:
                            scale_requests.append(rec)

                skew_mon = SkewMonitor(_tel_dir, on_event=_on_straggler)
            except Exception as e:  # noqa: BLE001 — watch is best-effort
                print(f"# heturun: straggler watch off ({e!r})",
                      file=sys.stderr)

        running = {w: spawn_worker(w) for w in range(num_workers)}
        respawn_at = {}   # worker id -> monotonic deadline (backoff pending)
        worker_respawns = {}   # worker id -> incarnation bump count
        restarts, delay = 0, 2.0
        rc_final, preempted = 0, False
        teardown_at = None
        while running or respawn_at:
            for w, p in list(running.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del running[w]
                if elastic_on and running:
                    # elastic: every exit is a membership event. Clean
                    # completions defer (their partition is consumed);
                    # abnormal exits — crash, SIGKILL, preemption — are
                    # DEPARTURES: shrink the world so survivors
                    # re-partition, instead of restarting
                    note_departure(w)
                    if rc == 0:
                        continue
                    if rc == EXIT_PREEMPTED:
                        preempted = True
                    print(f"# heturun: worker {w} exited rc={rc}; elastic: "
                          "proposing shrink", file=sys.stderr, flush=True)
                    try:
                        elastic_resize()
                        continue
                    except Exception as e:  # noqa: BLE001
                        # falling back to RESTART means this rank is not
                        # departed after all — a stale pending_departed
                        # entry would decommission the respawned worker at
                        # the next resize and double-consume its samples
                        pending_departed.pop(w, None)
                        print(f"# heturun: elastic shrink failed ({e!r}); "
                              "falling back to restart/fail handling",
                              file=sys.stderr, flush=True)
                if rc == 0:
                    continue
                if rc == EXIT_PREEMPTED:
                    # clean preemption: emergency checkpoint written; never
                    # counted against the restart budget
                    preempted = True
                    continue
                if restarts < args.max_restarts:
                    restarts += 1
                    print(f"# heturun: worker {w} exited rc={rc}; auto-"
                          f"resume restart {restarts}/{args.max_restarts} "
                          f"in {delay:.0f}s", file=sys.stderr, flush=True)
                    # deadline, not an inline sleep: other workers' exits
                    # (preemption!) must keep being reaped during backoff
                    respawn_at[w] = time.monotonic() + delay
                    delay *= 2
                elif not rc_final:
                    # first failure wins: survivors killed by the teardown
                    # below exit -15, which must not mask the real code
                    rc_final = rc
            while elastic_on and usr_grow["worker"] > 0 and running:
                usr_grow["worker"] -= 1
                try:
                    print("# heturun: elastic: growing by one worker",
                          file=sys.stderr, flush=True)
                    elastic_resize(d_workers=1)
                except Exception as e:  # noqa: BLE001
                    print(f"# heturun: elastic worker grow failed ({e!r})",
                          file=sys.stderr, flush=True)
            while elastic_on and scale_requests and running:
                scale_requests.pop()
                usr_grow["server"] += 1  # main thread: safe to merge here
            while elastic_on and usr_grow["server"] > 0 and running:
                usr_grow["server"] -= 1
                try:
                    print("# heturun: elastic: growing by one PS server",
                          file=sys.stderr, flush=True)
                    elastic_resize(d_servers=1)
                except Exception as e:  # noqa: BLE001
                    print(f"# heturun: elastic server grow failed ({e!r})",
                          file=sys.stderr, flush=True)
            now = time.monotonic()
            if ps_sup is not None and ps_sup.fatal and not rc_final:
                # the PS tier is permanently down (respawn budget exhausted
                # or a respawn failed): fail the run now instead of letting
                # every worker grind through its failover deadline. A worker
                # failure that already landed keeps its code (first failure
                # wins, the PR 1 convention).
                print(f"# heturun: PS supervisor fatal: {ps_sup.fatal}",
                      file=sys.stderr, flush=True)
                rc_final = 1
            if rc_final:
                # a permanently failed worker strands the survivors in
                # dead-rank collectives — preempt them (SIGTERM so they can
                # emergency-checkpoint, then terminate after the grace
                # window) instead of polling forever
                respawn_at.clear()
                if teardown_at is None:
                    print(f"# heturun: worker failed rc={rc_final} with no "
                          "restart budget; preempting remaining workers",
                          file=sys.stderr, flush=True)
                    for p in running.values():
                        if p.poll() is None:
                            try:
                                p.send_signal(signal.SIGTERM)
                            except OSError:
                                pass
                    teardown_at = now + float(
                        os.environ.get("HETU_PREEMPT_GRACE_S", "30"))
                elif now >= teardown_at:
                    for p in running.values():
                        if p.poll() is None:
                            # SIGKILL, not terminate(): a worker wedged in a
                            # hung collective already ignored the SIGTERM
                            p.kill()
            for w, when in list(respawn_at.items()):
                if now >= when:
                    del respawn_at[w]
                    worker_respawns[w] = worker_respawns.get(w, 0) + 1
                    running[w] = spawn_worker(
                        w, incarnation=worker_respawns[w])
            if skew_mon is not None and now >= skew_next_poll:
                skew_next_poll = now + 2.0
                try:
                    skew_mon.poll()
                except Exception:  # noqa: BLE001 — watch is best-effort
                    pass
            if running or respawn_at:
                time.sleep(0.2)
        if ps_sup is not None:
            ps_sup.stop()  # before terminate(): teardown is not a death
        for p in _procs:
            p.terminate()
            p.join(timeout=10)
        if ps_snap_created:
            from hetu_tpu.ps.supervisor import cleanup_snapshot_root
            cleanup_snapshot_root(ps_snap_created)
        if elastic_dir:
            import shutil
            shutil.rmtree(elastic_dir, ignore_errors=True)
        rc = rc_final if rc_final else (EXIT_PREEMPTED if preempted else 0)
        _write_telemetry_summary(rc, preempted, num_workers)
        sys.exit(rc)
    else:
        # multi-machine: ssh remote roles; workers get jax.distributed
        # coordinator env (reference: paramiko remote PS + mpirun -host)
        ssh_opts = ["-o", "StrictHostKeyChecking=no"]
        if args.identify:
            ssh_opts += ["-i", args.identify]
        coord = f"{chief_address}:{_get_available_port(chief_address)}"
        # forward the PS config AND the telemetry toggles: --telemetry-dir
        # promises every rank writes to the (shared) dir, so the ssh'd
        # ranks need the env too, not just the chief-host children.
        # Values are shell-quoted — the telemetry dir is a user-supplied
        # path that may carry spaces/metacharacters into the remote line
        env_exports = " ".join(
            f"{k}={shlex.quote(str(v))}" for k, v in env.items()
            if k.startswith("DMLC_") or k.startswith("HETU_TELEMETRY"))
        sid = 0
        if enable_ps:
            _procs.append(ctx.Process(target=_sched_entry, args=(env,)))
            for p in _procs:
                p.start()
        pidx = 0
        total_procs = sum(workers.values())
        for host in hosts:
            for _ in range(servers.get(host, 0)):
                cmd = (f"{env_exports} SERVER_ID={sid} DMLC_ROLE=server "
                       f"python -m hetu_tpu.launcher_remote_server")
                _shells.append(subprocess.Popen(
                    ["ssh", *ssh_opts, host, cmd]))
                sid += 1
            for _ in range(workers.get(host, 0)):
                wcmd = (f"{env_exports} WORKER_ID={pidx} DMLC_ROLE=worker "
                        f"HETU_NUM_WORKER={num_workers} "
                        f"JAX_COORDINATOR_ADDRESS={coord} "
                        f"JAX_NUM_PROCESSES={total_procs} "
                        f"JAX_PROCESS_ID={pidx} " + " ".join(args.command))
                if host == chief:
                    _shells.append(subprocess.Popen(
                        args.command, env={**env, "WORKER_ID": str(pidx),
                                           "DMLC_ROLE": "worker",
                                           "HETU_NUM_WORKER": str(num_workers),
                                           "JAX_COORDINATOR_ADDRESS": coord,
                                           "JAX_NUM_PROCESSES": str(total_procs),
                                           "JAX_PROCESS_ID": str(pidx)}))
                else:
                    _shells.append(subprocess.Popen(
                        ["ssh", *ssh_opts, host, wcmd]))
                pidx += 1
        rc = 0
        for p in _shells:
            rc |= p.wait()
        for p in _procs:
            p.terminate()
        # multi-host: only this host's files are visible unless the dir is
        # on a shared filesystem — the summary still inventories what's here
        _write_telemetry_summary(rc, False, num_workers)
        sys.exit(rc)


if __name__ == "__main__":
    main()
