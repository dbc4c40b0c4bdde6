"""Self-provisioned local PS cluster (scheduler + N servers as spawned
processes, the calling process becomes worker 0).

One shared implementation of the bootstrap that ``chip_smoke.py``, the
benchmark's ``wdl-criteo`` adapter and the examples need when run standalone — outside a ``heturun`` launch (reference: the
``tests/*.sh`` scripts' local mpirun clusters). The test suite's
``tests/test_ps.run_cluster`` stays separate: it additionally runs worker
BODIES in subprocesses and collects per-worker results, which this helper
deliberately does not (the caller IS the worker).
"""
from __future__ import annotations

import contextlib
import os
import socket
import shutil
import subprocess
import sys
import tempfile
import time

_LIGHT_MAIN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "_light_main.py")

# The live cluster registry: the `ps_kill` fault-injection kind
# (resilience.FaultInjector) and tests resolve the CURRENT server process
# for a given id here. Only one local_cluster is live per process.
_LIVE: dict = {}


def _ps_env(port: int, n_workers: int, n_servers: int) -> dict:
    return {"DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_NUM_SERVER": str(n_servers)}


def spawn_light_role(role: str, env_extra: dict) -> subprocess.Popen:
    """Launch a scheduler/server as a LIGHT process: ``_light_main.py``
    executed by file path needs only ctypes + the prebuilt lib — no
    hetu_tpu/jax import (seconds per process saved at every cluster
    bootstrap). Shared by this module and tests/test_ps.run_cluster."""
    from ..csrc.build import build
    env = os.environ.copy()
    env.update(env_extra)
    env["DMLC_ROLE"] = role
    env["HETU_PS_LIB"] = build("libhetu_ps.so")
    return subprocess.Popen([sys.executable, _LIGHT_MAIN], env=env)


def spawn_light_server(idx: int, base_env: dict, stopfile: str,
                       port: str = "0") -> subprocess.Popen:
    """Server-role wrapper over ``spawn_light_role`` carrying the full
    bootstrap contract in ONE place (``_light_main.py`` hard-fails on a
    missing key). ``port="0"``: bind an OS-assigned port, registered with
    the scheduler (race-free)."""
    return spawn_light_role("server", {**base_env, "SERVER_ID": str(idx),
                                       "DMLC_PS_SERVER_URI": "127.0.0.1",
                                       "DMLC_PS_SERVER_PORT": port,
                                       "HETU_PS_STOPFILE": stopfile})


def reap_light_procs(procs, timeout: float = 15.0):
    """Wait for light children; SIGKILL stragglers AND reap them (a kill
    without a wait leaves a zombie for the rest of the session).

    ``timeout`` is ONE shared deadline across all children, not a per-child
    budget: a wedged cluster of N processes tears down in bounded total
    time instead of N x timeout."""
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            p.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def resolve_test_kill_index(n_servers: int):
    """The ``HETU_PS_TEST_KILL_SERVER`` fault hook's gate + bounds check.

    Follows the resilience fault-injection convention (HETU_FAULT_SPEC):
    destructive test hooks are INERT unless ``HETU_TEST_MODE`` is explicitly
    truthy, so an env var leaked from a test session cannot SIGKILL a real
    server. In test mode an out-of-range index is a hard error — silently
    killing the wrong process (or IndexError-ing into the scheduler slot)
    would make the fault test meaningless."""
    from ..resilience import test_mode_enabled
    raw = os.environ.get("HETU_PS_TEST_KILL_SERVER")
    if raw is None or not test_mode_enabled():
        return None
    idx = int(raw)
    if not 0 <= idx < n_servers:
        raise ValueError(
            f"HETU_PS_TEST_KILL_SERVER={raw} out of range for "
            f"{n_servers} servers")
    return idx


def kill_live_server(idx: int):
    """SIGKILL the CURRENT process serving server id ``idx`` of the live
    ``local_cluster`` — the executor of the ``ps_kill@step[:idx]`` fault
    kind. Test-gating lives in the FaultInjector (HETU_TEST_MODE); here the
    index is bounds-checked like ``resolve_test_kill_index`` so the fault
    can never land on the scheduler or a random child."""
    if not _LIVE:
        raise RuntimeError("ps_kill: no live local_cluster in this process")
    n = _LIVE["n_servers"]
    if not 0 <= idx < n:
        raise ValueError(f"ps_kill server index {idx} out of range for "
                         f"{n} servers")
    victim = _LIVE["servers"][idx]
    victim.kill()
    victim.wait()


def get_live_cluster() -> dict:
    """The live cluster registry (empty when none): n_servers, servers
    (id -> current Popen), supervisor (PSSupervisor or None), snapshot_dir,
    port."""
    return _LIVE


@contextlib.contextmanager
def local_cluster(n_servers: int = 1, n_workers: int = 1, port: int = None,
                  *, ha: bool = False, snapshot_ms: int = 1000,
                  max_respawns: int = 3, snapshot_dir: str = None,
                  failover_ms: int = 30000):
    """Spawn scheduler + servers, set THIS process up as worker 0, yield.
    On exit, signal the servers to stop and reap every process.

    ``ha=True`` turns on the full high-availability stack: servers write
    continuous shard snapshots (``snapshot_ms``), a :class:`PSSupervisor`
    respawns dead servers from the freshest snapshot (at most
    ``max_respawns`` times), and this worker blocks-with-deadline through a
    server death instead of raising (``failover_ms`` →
    DMLC_PS_FAILOVER_DEADLINE_MS, set only if not already in the env).
    """
    if port is None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    # signal-by-creation file inside a fresh private dir (mktemp is
    # race-prone: the generated name can be claimed by another process)
    stopdir = tempfile.mkdtemp(prefix="hetu_ps_stop_")
    stopfile = os.path.join(stopdir, "stop")
    base = _ps_env(port, n_workers, n_servers)
    snapdir = None
    saved_env: dict = {}
    if ha:
        snapdir = snapshot_dir or tempfile.mkdtemp(prefix="hetu_ps_snap_")
        base.update({"DMLC_PS_SNAPSHOT_DIR": snapdir,
                     "DMLC_PS_SNAPSHOT_MS": str(int(snapshot_ms))})
        # these ride into os.environ below (os.environ.update(base)); a
        # leaked snapshot knob would make a LATER non-HA cluster's servers
        # snapshot into a deleted tempdir forever — remember the caller's
        # values (or their absence) to undo on exit
        saved_env = {k: os.environ.get(k)
                     for k in ("DMLC_PS_SNAPSHOT_DIR",
                               "DMLC_PS_SNAPSHOT_MS")}
    procs = []
    servers_by_id: dict = {}
    sup = None
    failover_env_set = False
    try:
        # spawn INSIDE the try: if a later spawn fails, the finally still
        # signals and reaps the children already running
        procs.append(spawn_light_role("scheduler", base))
        for i in range(n_servers):
            servers_by_id[i] = spawn_light_server(i, base, stopfile)
            procs.append(servers_by_id[i])
        # fault-injection hook: SIGKILL server <idx> right after spawn, so
        # the caller's RPCs face a cluster that can never complete
        # registration. Gated on HETU_TEST_MODE + bounds-checked
        # (resolve_test_kill_index; tests/test_resilience.py).
        kill_idx = resolve_test_kill_index(n_servers)
        if kill_idx is not None:
            victim = servers_by_id[kill_idx]
            victim.kill()
            victim.wait()
        if ha:
            from .supervisor import PSSupervisor

            def _respawn(i):
                p = spawn_light_server(
                    i, {**base, "DMLC_PS_RESTORE_DIR": snapdir}, stopfile)
                servers_by_id[i] = p
                procs.append(p)  # teardown reaps replacements too
                return p

            # procs is held by reference: ps_kill's victim and the
            # supervisor's wedged-process check stay in sync
            sup = PSSupervisor("127.0.0.1", port, n_servers, _respawn,
                               procs=servers_by_id,
                               max_respawns=max_respawns)
            sup.start()
            if "DMLC_PS_FAILOVER_DEADLINE_MS" not in os.environ:
                # this worker opts into failover for THIS cluster only — a
                # leaked deadline would turn a later non-HA cluster's fast
                # server-death error into a silent block-with-deadline
                os.environ["DMLC_PS_FAILOVER_DEADLINE_MS"] = \
                    str(int(failover_ms))
                failover_env_set = True
        os.environ.update(base)
        os.environ.update({"DMLC_ROLE": "worker", "WORKER_ID": "0"})
        _LIVE.clear()
        _LIVE.update({"n_servers": n_servers, "servers": servers_by_id,
                      "supervisor": sup, "snapshot_dir": snapdir,
                      "port": port,
                      # hetu-elastic (elastic.grow_local_cluster_server):
                      # enough to spawn a JOINING server into this world
                      # and have teardown reap it
                      "base_env": dict(base), "stopfile": stopfile,
                      "procs": procs})
        yield port
    finally:
        _LIVE.clear()
        if failover_env_set:
            os.environ.pop("DMLC_PS_FAILOVER_DEADLINE_MS", None)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if sup is not None:
            sup.stop()  # before the stopfile: clean exits are not "deaths"
        with open(stopfile, "w") as f:
            f.write("stop")
        reap_light_procs(procs)
        shutil.rmtree(stopdir, ignore_errors=True)
        if ha and snapshot_dir is None and snapdir:
            shutil.rmtree(snapdir, ignore_errors=True)
