"""Yaml-driven local PS-cluster launcher.

Capability parity with the reference's ``python/hetu/launcher.py``: a yaml
file carries the shared DMLC_* env block plus a ``launch`` section with
scheduler/server/worker counts; roles run as local processes
(``python -m hetu_tpu.launcher cfg.yml -n 2 --sched`` starts PS roles only,
``launch(target, args)`` also forks workers running ``target``).

Uses the ``spawn`` start method: worker targets import JAX, and forking a
JAX-threaded parent deadlocks.
"""
from __future__ import annotations

import argparse
import multiprocessing
import os
import signal
import sys

import yaml

_procs: list = []


def _signal_handler(sig, frame):
    print("SIGINT caught, stopping cluster")
    for proc in _procs:
        proc.terminate()
    sys.exit(0)


def _apply_shared_env(settings):
    for k, v in settings.get("shared", {}).items():
        os.environ[k] = str(v)


def start_sched(env=None):
    os.environ.update(env or {})
    os.environ["DMLC_ROLE"] = "scheduler"
    from hetu_tpu.ps import server as srv
    srv.start_scheduler_from_env()
    try:
        srv.scheduler_wait()
    except RuntimeError as e:
        # bounded teardown wait timed out: print the diagnostic naming the
        # ranks that never checked out, still Finalize, and exit nonzero
        # (same contract as ps/_light_main.py's scheduler body)
        print(f"[hetu ps scheduler] {e}", file=sys.stderr)
        srv.stop_scheduler()
        sys.exit(1)
    srv.stop_scheduler()


def start_server(server_id=0, env=None):
    os.environ.update(env or {})
    os.environ["DMLC_ROLE"] = "server"
    os.environ.setdefault("SERVER_ID", str(server_id))
    # no DMLC_PS_SERVER_PORT -> the native server binds an OS-assigned port
    # itself (race-free) and registers the actual number with the scheduler
    import signal as _signal
    import threading
    from hetu_tpu.ps import server as srv
    srv.start_server_from_env()
    stop = threading.Event()
    _signal.signal(_signal.SIGTERM, lambda *_: stop.set())
    _signal.signal(_signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    srv.stop_server()


def start_worker(target, args, worker_id=0, env=None):
    os.environ.update(env or {})
    os.environ["DMLC_ROLE"] = "worker"
    os.environ.setdefault("WORKER_ID", str(worker_id))
    import hetu_tpu as ht
    ht.worker_init()
    try:
        target(args)
    finally:
        ht.worker_finish()


def launch(target, args):
    """Launch the yaml-described local cluster and run ``target(args)`` in
    every worker process (reference launcher.py:18-38).

    PS high availability: a ``ps_max_respawns`` count in the yaml's
    ``launch`` section (or env ``HETU_PS_MAX_RESPAWNS``) turns on continuous
    server snapshots + supervised auto-respawn + worker failover, with the
    same env knobs as ``heturun --ps-max-respawns`` (docs/FAULT_TOLERANCE.md).
    """
    settings = yaml.safe_load(open(args.config).read())
    _apply_shared_env(settings)
    n_servers = int(settings["launch"]["server"])
    max_respawns = int(settings["launch"].get(
        "ps_max_respawns", os.environ.get("HETU_PS_MAX_RESPAWNS", 0)))
    ps_ha = n_servers > 0 and max_respawns > 0
    env = dict(os.environ)
    ps_snap_created = None
    if ps_ha:
        # defaults land in the CHILD env only — the launcher parent's
        # environment is left alone
        from hetu_tpu.ps.supervisor import apply_ha_env_defaults
        ps_snap_created = apply_ha_env_defaults(env)
    ctx = multiprocessing.get_context("spawn")
    n_workers = int(settings["launch"]["worker"])
    args.num_local_worker = n_workers
    # one process per chip, exactly as heturun does it (refused here when
    # there are more workers than chips); start_worker applies its env
    # before the child's first jax backend touch
    from hetu_tpu.runner import plan_local_chips, worker_chip_env
    chips = plan_local_chips(n_workers, env)
    if settings["launch"].get("scheduler", 0):
        _procs.append(ctx.Process(target=start_sched, args=(env,)))
    server_procs = {}
    for i in range(n_servers):
        server_procs[i] = ctx.Process(target=start_server, args=(i, env))
        _procs.append(server_procs[i])
    workers = []
    for i in range(n_workers):
        p = ctx.Process(target=start_worker, args=(
            target, args, i, {**env, **worker_chip_env(chips, i)}))
        _procs.append(p)
        workers.append(p)
    signal.signal(signal.SIGINT, _signal_handler)
    for proc in _procs:
        proc.start()
    sup = None
    if ps_ha:
        from hetu_tpu.ps.supervisor import start_mp_supervisor
        sup = start_mp_supervisor(ctx, start_server, env, server_procs,
                                  _procs.append, max_respawns=max_respawns)
    fatal_reported = False
    for proc in workers:
        while True:
            proc.join(timeout=0.5 if sup is not None else None)
            if not proc.is_alive():
                break
            if sup is not None and sup.fatal and not fatal_reported:
                # PS tier permanently down: fail fast instead of letting
                # every worker grind through its failover deadline
                fatal_reported = True
                print(f"# hetu launcher: PS supervisor fatal: {sup.fatal}; "
                      "terminating workers", file=sys.stderr)
                for w in workers:
                    if w.is_alive():
                        w.terminate()
    # workers done: tear down PS roles
    if sup is not None:
        sup.stop()  # before terminate(): teardown is not a death
    for proc in _procs:
        if proc not in workers:
            proc.terminate()
            proc.join(timeout=10)
    if ps_snap_created:
        from hetu_tpu.ps.supervisor import cleanup_snapshot_root
        cleanup_snapshot_root(ps_snap_created)
    if fatal_reported:
        # workers were killed because the PS tier was permanently down —
        # a caller (or CI) must not see this run as a success
        raise RuntimeError(f"PS supervisor fatal: {sup.fatal}")


def main():
    signal.signal(signal.SIGINT, _signal_handler)
    parser = argparse.ArgumentParser(
        description="launch PS roles (scheduler/servers) from a yaml config")
    parser.add_argument("config")
    parser.add_argument("-n", type=int, default=1, help="number of servers")
    parser.add_argument("--sched", action="store_true",
                        help="also launch the scheduler")
    args = parser.parse_args()
    settings = yaml.safe_load(open(args.config).read())
    _apply_shared_env(settings)
    env = dict(os.environ)
    ctx = multiprocessing.get_context("spawn")
    if args.sched:
        _procs.append(ctx.Process(target=start_sched, args=(env,)))
    for i in range(args.n):
        _procs.append(ctx.Process(target=start_server, args=(i, env)))
    for proc in _procs:
        proc.start()
    for proc in _procs:
        proc.join()


if __name__ == "__main__":
    main()
