"""Training supervision layer — what keeps a long-running job alive ABOVE
the parameter-server fault tolerance (ps-lite resender/heartbeats,
``hetu_tpu/ps/``): NaN'd steps, preempted TPU workers, hung collectives, and
crashed loops that would otherwise restart from step 0.

Four cooperating pieces, each usable alone:

- **Anomaly detection** — the executor's in-trace finite-check
  (``HetuConfig(anomaly_guard=True)``) gates the parameter/optimizer-state
  commit on every float output, updated parameter and slot being finite; a
  NaN/Inf step leaves params bit-identical to pre-step. :class:`AnomalyPolicy`
  turns the per-step verdict into skip / loss-scale backoff / rollback-to-
  checkpoint decisions.
- **Preemption handling** — :class:`PreemptionHandler` installs
  SIGTERM/SIGINT handlers that only set a flag; at the next step boundary the
  :class:`Supervisor` takes a coordinated emergency checkpoint
  (``TrainCheckpointer.save_step(..., force=True)``, all hosts — orbax writes
  are already multi-process-coordinated) and raises :class:`Preempted`, which
  ``supervise()`` converts into a clean exit with :data:`EXIT_PREEMPTED`.
- **Hang watchdog** — :class:`Watchdog` is a monitor thread fed by
  ``beat()`` at step boundaries (and around multihost barriers,
  ``multihost.barrier(deadline_s=...)``); when a step exceeds its deadline it
  dumps every live thread's Python stack plus the last-known phase/step to
  stderr and aborts with :data:`EXIT_WATCHDOG` instead of hanging forever —
  a wedged collective cannot be unwound by an exception, so
  abort-then-auto-resume is the recovery path.
- **Auto-resume** — :func:`supervise` restores the latest checkpoint
  (params, optimizer slots, op state, dataloader cursors/RNG — see
  :func:`capture_executor_state`) and re-enters the loop on recoverable
  failure, with bounded restarts and exponential backoff. ``heturun
  --max-restarts N`` applies the same policy one level up, at worker-process
  granularity.

Deterministic fault injection (``HETU_FAULT_SPEC``, inert unless
``HETU_TEST_MODE`` is set) makes every path testable on CPU: NaN grads,
step stalls, signals, crashes. See docs/FAULT_TOLERANCE.md.
"""
from __future__ import annotations

import os
import signal as _signal
import sys
import threading
import time
import traceback
from typing import Any, Callable, Optional

import numpy as np

from . import faults

# Distinct exit codes so a process supervisor (heturun, k8s, the operator)
# can tell the exits apart without parsing logs:
#   EXIT_PREEMPTED — clean preemption: emergency checkpoint written, do NOT
#     count against restart budgets (BSD EX_TEMPFAIL: "try again later").
#   EXIT_WATCHDOG — hang watchdog abort: stacks were dumped to stderr; a
#     restart resumes from the latest checkpoint.
EXIT_PREEMPTED = 75
EXIT_WATCHDOG = 85

_TRUTHY = ("1", "true", "yes", "on")


def env_truthy(name: str) -> bool:
    """The one spelling of 'is this env knob on': explicitly truthy values
    only, so ``FOO=false`` and ``FOO=0`` mean OFF."""
    return os.environ.get(name, "").strip().lower() in _TRUTHY


def test_mode_enabled() -> bool:
    """The single gate for every destructive test hook (fault injection,
    the PS kill-server hook): ``HETU_TEST_MODE`` must be explicitly truthy.
    A fault spec or kill index leaked into a production environment is
    inert without it."""
    return env_truthy("HETU_TEST_MODE")


def _tel_event(name: str, flush: bool = False, **fields) -> None:
    """Typed resilience event into the telemetry JSONL (no-op when telemetry
    is off). ``flush=True`` on the abort/exit paths — the record must be on
    disk before ``os._exit``/``Preempted`` ends the process. Never raises:
    observability must not take the recovery path down with it. Event names
    map to metrics as documented in docs/OBSERVABILITY.md."""
    from . import telemetry as _telemetry
    tel = _telemetry.get()
    if tel is None:
        return
    try:
        tel.event(name, **fields)
        if flush:
            tel.flush()
    except Exception:  # noqa: BLE001
        pass


def _flight_flush(reason: str) -> None:
    """Flush any armed hetuscope flight recorder (telemetry/scope.py) on an
    abort path — the ring of recent step records must be on disk before the
    process dies. No-op when introspection is off; never raises."""
    try:
        from .telemetry import scope as _scope
        _scope.flush_flight(reason)
    except Exception:  # noqa: BLE001
        pass


def _incident(reason: str, step=None, **extra) -> None:
    """Freeze a hetustory incident report (telemetry/story.py): the ±K-step
    window from EVERY ledger family in the telemetry dir, one JSON doc,
    rendered offline by ``hetustory --incident``. Called AFTER the event /
    flight flush of the same abort path so the window includes them. Gated
    by HETU_STORY_INCIDENT (default on); no-op when telemetry is off; never
    raises — post-mortem capture must not take the abort path down."""
    try:
        from . import telemetry as _telemetry
        from .telemetry import story as _story
        tel = _telemetry.get()
        if tel is None or not _story.incident_enabled():
            return
        # the snapshot reads the ledgers from disk: push any buffered rows
        # (the triggering event itself) out first
        try:
            tel.sink.flush()
        except Exception:  # noqa: BLE001
            pass
        _story.write_incident(tel.dir, reason, step=step, rank=tel.rank,
                              extra=extra or None)
    except Exception:  # noqa: BLE001
        pass


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------

class FaultInjected(RuntimeError):
    """Raised by the ``crash`` fault kind (a stand-in for an arbitrary
    training-loop exception in auto-resume tests)."""


class FaultInjector:
    """Deterministic fault schedule: ``HETU_FAULT_SPEC="kind@step[:arg],..."``.

    Kinds (each entry fires at most once, at its step's boundary):

    - ``nan_grads@S`` — the executor poisons that step's parameter update
      with NaN inside the trace (exercises the anomaly guard end to end).
    - ``nan_op@S[:OPNAME]`` — the executor NaN-poisons one op's OUTPUT
      inside the trace at step S (``OPNAME`` is the op's named_scope
      identity, ``/``/whitespace replaced by ``_``; default: the first
      computing op in topological order) — the deterministic seed the
      hetuscope NaN/Inf provenance pass must localize.
    - ``stall@S:SECONDS`` — sleep at the step boundary (trips the watchdog).
    - ``sigterm@S`` / ``sigint@S`` — deliver the signal to this process
      (exercises preemption handling).
    - ``crash@S`` — raise :class:`FaultInjected` (exercises auto-resume).
    - ``ps_kill@S[:IDX]`` — SIGKILL live PS server ``IDX`` (default 0) of
      this process's ``ps.local_cluster`` (exercises the PS
      snapshot/respawn/failover stack end to end; bounds-checked in
      ``local_cluster.kill_live_server`` like ``resolve_test_kill_index``).
    - ``quant_corrupt@S[:NODE]`` — flip the scale bytes of the next
      quantized PS message this worker sends (``NODE`` = tensor id filter,
      default any; requires ``HetuConfig(comm_quant=...)`` traffic) — the
      server's length/scale validation must reject the malformed payload
      as an error response instead of applying garbage
      (docs/COMM_QUANT.md; the C++ hook is additionally gated on
      HETU_TEST_MODE in capi.cc).
    - ``worker_lost@S[:RANK]`` — this process SIGKILLs ITSELF at step S
      when its WORKER_ID matches RANK (default: any rank) — the
      deterministic elastic scale-down trigger: under ``heturun
      --elastic`` the launcher observes the death and proposes a world
      shrink (docs/FAULT_TOLERANCE.md "Elastic membership").
    - ``ps_join@S`` — grow this process's live ``ps.local_cluster`` by one
      PS server at step S (spawns the server + runs the resize
      coordinator in a daemon thread; the executor's ElasticAgent
      drains/commits at the same boundary and the key ranges migrate
      live).
    - ``ps_slow@S[:MS]`` — delay one PS server's NEXT optimizer apply by
      MS milliseconds (default 100) at step S — the deterministic lever
      the hetutrail critical-path and straggler tests drive
      (docs/OBSERVABILITY.md pillar 5). The target server is
      ``HETU_PS_SLOW_SERVER`` (default 0); the server-side hook
      (``kTestSlowApply``) is additionally HETU_TEST_MODE-gated in capi
      AND on the server.
    - ``plan_flap@S[:PERIOD]`` — from step S onward, alternate the
      injected ``ps_slow`` delay on/off every PERIOD steps (default 8;
      delay ``HETU_PLAN_FLAP_MS`` ms, default 40, re-armed at every
      boundary of an "on" half-period since the server hook is one-shot
      per arming). The ONLY persistent entry in the schedule — it never
      burns out — and it is deliberately adversarial: the period is
      chosen to entice a naive controller into oscillating (slow →
      actuate → fault pauses → "improvement" → commit → fault returns →
      actuate back...). The hetupilot governor's anti-flap regression
      test drives it (docs/FAULT_TOLERANCE.md "Self-tuning with
      guardrails"); a huge PERIOD degenerates to a sustained slow
      server, the pilot's genuine-improvement fixture.
    - ``ps_partition@S[:SERVER]`` — arm a transient directed partition
      between this worker and PS server ``SERVER`` (default 0) at step S
      via the hetuchaos engine: the next ``HETU_PS_PARTITION_ATTEMPTS``
      (default 2) RPC attempts *per wire channel* (bulk push + fast pull
      — up to 2x that many attempts total) to that server fail,
      exercising the retry-with-backoff path (a window past the
      per-channel retry budget escalates to the failover/departure path
      instead — docs/FAULT_TOLERANCE.md "Chaos testing & transport
      hardening"). For full seeded schedules use ``HETU_CHAOS_SPEC`` /
      ``bin/hetuchaos`` directly.
    - ``job_kill@S[:PHASE]`` — whole-job death (hetusave,
      docs/FAULT_TOLERANCE.md "Coordinated job snapshots"). With no
      PHASE: at step S every live local-cluster PS process is SIGKILLed
      and then this worker SIGKILLs itself — the power-loss/pool-sweep
      shape only a committed job epoch recovers from. With PHASE (one of
      ``pre_barrier|server_write|pre_commit|post_commit``): arms the
      crash window INSIDE the next coordinated snapshot at step >= S,
      consumed by ``recovery.take_job_snapshot`` at exactly that phase —
      how the soak proves torn epochs are never restore-eligible.

    The full injector catalogue (args, gating, which subsystem each kind
    exercises, plus the native ``HETU_PS_TEST_EXIT_AFTER_UPDATES`` and
    ``HETU_CHAOS_SPEC`` hooks) lives in docs/FAULT_TOLERANCE.md
    "Fault-kind catalogue".

    ``from_env()`` (the only path wired into the executor by default) returns
    None unless :func:`test_mode_enabled` — direct construction is itself an
    explicit opt-in for tests.
    """

    # the shared registry (hetu_tpu.faults) owns the catalogue; kept as a
    # class attribute for the tests and docs that enumerate kinds here
    KINDS = faults.STEP_FAULT_NAMES

    def __init__(self, spec: str):
        self.entries: list[dict] = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            # nan_op's arg is an OP NAME, job_kill's a snapshot PHASE,
            # every other kind's a number — faults.parse_step_entry
            # rejects unknown kinds/phases with the shared catalogue
            entry = faults.parse_step_entry(part)
            entry["fired"] = False
            self.entries.append(entry)

    @classmethod
    def from_env(cls) -> Optional["FaultInjector"]:
        spec = os.environ.get("HETU_FAULT_SPEC")
        if not spec or not test_mode_enabled():
            return None
        return cls(spec)

    def take(self, kind: str, step: int) -> Optional[dict]:
        """Consume (mark fired) the first unfired entry matching
        (kind, step); None when nothing matches."""
        for e in self.entries:
            if e["kind"] == kind and e["step"] == int(step) and not e["fired"]:
                e["fired"] = True
                return e
        return None

    def fires(self, kind: str, step: int) -> bool:
        return self.take(kind, step) is not None

    def inject_host(self, step: int, ex=None) -> None:
        """Host-side faults for this step boundary (stall / signals /
        crash). ``nan_grads`` is NOT handled here — it rides into the jitted
        step as a scalar argument (see SubExecutor). ``ex`` (when the
        Supervisor passes it) lets elastic faults reach the executor's
        membership agent."""
        e = self.take("stall", step)
        if e is not None:
            time.sleep(e["arg"] if e["arg"] is not None else 3600.0)
        e = self.take("ps_kill", step)
        if e is not None:
            from .ps.local_cluster import kill_live_server
            kill_live_server(0 if e["arg"] is None else int(e["arg"]))
        e = self.take("quant_corrupt", step)
        if e is not None:
            from . import ps as ps_pkg
            comm = ps_pkg.get_worker_communicate()
            comm.TestCorruptNextQuant(-1 if e["arg"] is None
                                      else int(e["arg"]))
        e = self.take("worker_lost", step)
        if e is not None:
            my_rank = int(os.environ.get("WORKER_ID", "0"))
            if e["arg"] is None or int(e["arg"]) == my_rank:
                # die like a preempted host: no checkout, no cleanup — the
                # elastic launcher must absorb it as a planned departure.
                # Progress flushes first (a real preemption's SIGTERM grace
                # window gives the same guarantee), so the departed tail is
                # redistributed exactly: `step` boundaries completed =
                # `step` batches consumed.
                ela = getattr(ex, "elastic", None) if ex is not None else None
                if ela is not None:
                    ela.write_progress(step)
                print(f"# hetu fault: worker_lost — rank {my_rank} "
                      f"SIGKILLing itself at step {step}", file=sys.stderr,
                      flush=True)
                os.kill(os.getpid(), _signal.SIGKILL)
        e = self.take("ps_join", step)
        if e is not None:
            from .elastic import grow_local_cluster_server
            grow_local_cluster_server()
        e = self.take("ps_slow", step)
        if e is not None:
            from . import ps as ps_pkg
            comm = ps_pkg.get_worker_communicate()
            comm.TestSlowApply(
                server=int(os.environ.get("HETU_PS_SLOW_SERVER", "0")),
                ms=100 if e["arg"] is None else int(e["arg"]))
        # plan_flap is the one persistent kind: it re-arms the one-shot
        # server delay at every boundary of an "on" half-period and never
        # marks itself fired — take() is deliberately bypassed
        for e in self.entries:
            if e["kind"] != "plan_flap" or int(step) < e["step"]:
                continue
            period = max(1, int(e["arg"])) if e["arg"] else 8
            if ((int(step) - e["step"]) // period) % 2 == 0:
                from . import ps as ps_pkg
                comm = ps_pkg.get_worker_communicate()
                comm.TestSlowApply(
                    server=int(os.environ.get("HETU_PS_SLOW_SERVER", "0")),
                    ms=int(os.environ.get("HETU_PLAN_FLAP_MS", "40")))
        e = self.take("ps_partition", step)
        if e is not None:
            from . import ps as ps_pkg
            comm = ps_pkg.get_worker_communicate()
            srv = 0 if e["arg"] is None else int(e["arg"])
            n = int(os.environ.get("HETU_PS_PARTITION_ATTEMPTS", "2"))
            # chaos-engine partition window over the next n attempts to
            # srv (SetChaos is HETU_TEST_MODE-gated like this injector)
            comm.SetChaos(f"seed={step},partition={srv}:0:{n}")
        e = self.take("job_kill", step)
        if e is not None:
            from . import recovery
            if e["arg"] is None:
                # whole-job death at a step boundary: every PS process dies
                # with the worker, no grace, no cleanup — only a committed
                # hetusave epoch can bring the job back
                recovery.kill_whole_job(step)
            else:
                # phase-targeted: arm the crash window inside the NEXT
                # coordinated snapshot (consumed by take_job_snapshot)
                recovery.arm_job_kill(e["arg"])
        if self.take("sigterm", step) is not None:
            os.kill(os.getpid(), _signal.SIGTERM)
        if self.take("sigint", step) is not None:
            os.kill(os.getpid(), _signal.SIGINT)
        if self.take("crash", step) is not None:
            raise FaultInjected(f"injected crash at step {step}")


# ---------------------------------------------------------------------------
# Hang watchdog
# ---------------------------------------------------------------------------

class Watchdog:
    """Monitor thread: fires when no ``beat()`` arrives within
    ``deadline_s``. On fire it writes the last-known phase/step and every
    live thread's Python stack to ``stream`` (default stderr), then calls
    ``on_timeout()`` if given, else ``os._exit(exit_code)`` — a hung device
    call or collective sits in C and cannot be interrupted by an exception,
    so the only useful outputs are the diagnosis and a restartable corpse.
    """

    def __init__(self, deadline_s: float, on_timeout: Optional[Callable] = None,
                 stream=None, exit_code: int = EXIT_WATCHDOG,
                 poll_s: Optional[float] = None):
        self.deadline_s = float(deadline_s)
        self.on_timeout = on_timeout
        self.stream = stream
        self.exit_code = exit_code
        self.poll_s = poll_s if poll_s is not None else min(
            1.0, self.deadline_s / 4)
        self.fired = False
        self._lock = threading.Lock()
        self._last = time.monotonic()
        self._phase = "start"
        self._step: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self, phase: str = "step", step: Optional[int] = None) -> None:
        with self._lock:
            self._last = time.monotonic()
            self._phase = phase
            self._step = step

    def start(self) -> "Watchdog":
        if self._thread is None:
            self.beat("start")
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._watch, name="hetu-watchdog", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_s):
            with self._lock:
                elapsed = time.monotonic() - self._last
                phase, step = self._phase, self._step
            if elapsed > self.deadline_s:
                self._fire(elapsed, phase, step)
                return

    def dump_stacks(self, stream=None) -> None:
        """Every live thread's Python stack (pure-Python, works with any
        stream — a thread blocked in a C call still shows its Python frames,
        which is exactly the 'where is it stuck' answer)."""
        stream = stream or self.stream or sys.stderr
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            print(f"--- Thread {names.get(ident, '?')} (ident {ident}) ---",
                  file=stream)
            traceback.print_stack(frame, file=stream)

    def _fire(self, elapsed: float, phase: str, step) -> None:
        self.fired = True
        stream = self.stream or sys.stderr
        print(f"hetu watchdog: no progress for {elapsed:.1f}s "
              f"(deadline {self.deadline_s:.1f}s); last phase={phase!r} "
              f"step={step}; dumping thread stacks and aborting "
              f"(exit {self.exit_code})", file=stream)
        try:
            self.dump_stacks(stream)
        finally:
            _tel_event("watchdog_fire", flush=True, phase=phase, step=step,
                       elapsed_s=round(elapsed, 1))
            _flight_flush("watchdog")
            _incident("watchdog", step=step, phase=phase,
                      elapsed_s=round(elapsed, 1))
            try:
                stream.flush()
            except Exception:  # noqa: BLE001 — never let flush mask the abort
                pass
            if self.on_timeout is not None:
                self.on_timeout()
            else:
                os._exit(self.exit_code)


# ---------------------------------------------------------------------------
# Preemption
# ---------------------------------------------------------------------------

class Preempted(BaseException):
    """Control-flow, not an error (like KeyboardInterrupt — deliberately NOT
    an Exception subclass, so broad ``except Exception`` recovery paths and
    ``supervise()``'s restart logic cannot swallow it). Raised at a step
    boundary after any emergency checkpoint is durable. ``step`` is the
    last COMPLETED step; the latest durable checkpoint may be earlier (no
    checkpointer attached, or the same boundary rolled back) — resume from
    the checkpointer's ``latest_step()``, as ``supervise()`` does, not from
    ``step``."""

    def __init__(self, step: int):
        super().__init__(f"preempted after step {step}")
        self.step = step


class PreemptionHandler:
    """SIGTERM/SIGINT → a flag checked at step boundaries; the signal
    context itself does nothing else (async-signal-safe by construction).

    ``should_stop()`` is the COORDINATED check: under a multi-process world
    it is True on every host once any host got the signal, so the emergency
    checkpoint (a collective orbax write) starts on all hosts at the same
    step instead of deadlocking on the one host that was told to die.
    """

    def __init__(self, signals=(_signal.SIGTERM, _signal.SIGINT)):
        self.signals = tuple(signals)
        self.installed = False
        self._flag = False
        self.signum: Optional[int] = None
        self._prev: dict = {}

    def _handler(self, signum, frame):
        self._flag = True
        self.signum = signum

    def install(self) -> "PreemptionHandler":
        if not self.installed:
            for s in self.signals:
                self._prev[s] = _signal.signal(s, self._handler)
            self.installed = True
        return self

    def uninstall(self) -> None:
        if self.installed:
            for s, prev in self._prev.items():
                _signal.signal(s, prev)
            self._prev.clear()
            self.installed = False

    def __enter__(self) -> "PreemptionHandler":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    @property
    def requested(self) -> bool:
        """This process's local flag (no collective)."""
        return self._flag

    def should_stop(self) -> bool:
        from .parallel import multihost
        return multihost.any_process_flag(self._flag)


# ---------------------------------------------------------------------------
# Anomaly policy + loss scaling
# ---------------------------------------------------------------------------

class LossScaler:
    """Dynamic loss scale with backoff-on-anomaly / growth-on-streak (the
    standard mixed-precision recipe). The executor path does not scale losses
    itself (its guard skips the whole update); flagship loops multiply
    ``scaler.scale`` into the loss, divide it out of grads (``unscale``), and
    call ``update(finite)`` each step — the :class:`AnomalyPolicy` does the
    ``update`` call when it owns one."""

    def __init__(self, init_scale: float = 2.0 ** 15, backoff: float = 0.5,
                 growth: float = 2.0, growth_interval: int = 200,
                 min_scale: float = 1.0, max_scale: float = 2.0 ** 24):
        self.scale = float(init_scale)
        self.backoff = float(backoff)
        self.growth = float(growth)
        self.growth_interval = int(growth_interval)
        self.min_scale = float(min_scale)
        self.max_scale = float(max_scale)
        self._good_steps = 0

    def scale_loss(self, loss):
        return loss * self.scale

    def unscale(self, grads):
        import jax
        inv = 1.0 / self.scale
        return jax.tree.map(lambda g: g * inv, grads)

    def update(self, finite: bool) -> None:
        if not finite:
            self.scale = max(self.scale * self.backoff, self.min_scale)
            self._good_steps = 0
        else:
            self._good_steps += 1
            if self._good_steps >= self.growth_interval:
                self.scale = min(self.scale * self.growth, self.max_scale)
                self._good_steps = 0


class AnomalyPolicy:
    """Turns per-step finite verdicts into actions: ``"ok"`` (finite),
    ``"skip"`` (anomalous — the in-trace guard already kept params
    unchanged), or ``"rollback"`` (``max_consecutive`` anomalies in a row —
    restore the latest checkpoint; a stretch of skipped steps that long
    means the divergence is in surviving state, not the batch)."""

    def __init__(self, max_consecutive: int = 3, max_rollbacks: int = 3,
                 loss_scaler: Optional[LossScaler] = None):
        if max_consecutive < 1:
            raise ValueError(f"max_consecutive must be >= 1, "
                             f"got {max_consecutive}")
        self.max_consecutive = int(max_consecutive)
        # restore is deterministic (params AND dataloader position), so a
        # NaN with a deterministic cause replays identically after every
        # rollback — without a bound that is a silent livelock, not
        # recovery. Exceeding it raises out of the loop instead.
        self.max_rollbacks = int(max_rollbacks)
        self.loss_scaler = loss_scaler
        self.streak = 0
        self.total = 0
        self.rollbacks = 0

    def note(self, finite: bool) -> str:
        if self.loss_scaler is not None:
            self.loss_scaler.update(finite)
        if finite:
            self.streak = 0
            return "ok"
        self.streak += 1
        self.total += 1
        if self.streak >= self.max_consecutive:
            self.streak = 0
            self.rollbacks += 1
            return "rollback"
        return "skip"


# ---------------------------------------------------------------------------
# Executor state capture/restore (what a supervision checkpoint holds)
# ---------------------------------------------------------------------------

def capture_executor_state(ex) -> dict:
    """Everything a resume needs, as a numpy pytree TrainCheckpointer can
    save: params (by stable file name), optimizer slots, op state, the step
    counter (which also positions every per-step RNG fold), host dataloader
    cursors/RNG/peeked batch, and device-resident dataset cursors.

    ``Executor.save/load`` (directory-of-.npy) remains the graph-API
    surface; this pytree form is what the Supervisor/supervise() path
    feeds through TrainCheckpointer's atomic, retained, multi-host-
    coordinated step checkpoints."""
    import jax

    def host_np(x):
        """Host value of a possibly-sharded leaf: np.asarray raises on
        arrays spanning non-addressable devices (multi-host meshes — the
        exact world the coordinated preemption save exists for), so those
        go through the allgather path."""
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from .parallel.multihost import fetch_replicated
            return fetch_replicated(x)
        return np.asarray(x)

    names = ex._param_file_names()
    state: dict[str, Any] = {
        "step": np.asarray(ex.state["step"], np.int64),
        "params": {name: host_np(ex.state["params"][id(n)])
                   for name, n in zip(names, ex.param_nodes)},
    }
    slots = {str(i): jax.tree.map(host_np, ex.state["slots"][id(n)])
             for i, n in enumerate(ex._opt_nodes())}
    if slots:
        state["slots"] = slots
    op_state = {str(i): jax.tree.map(host_np, ex.state["op_state"][id(n)])
                for i, n in enumerate(ex._stateful_nodes())}
    if op_state:
        state["op_state"] = op_state
    dls: dict[str, Any] = {}
    res: dict[str, Any] = {}
    for sub_name, sub in ex.subexecutors.items():
        per = {}
        for j, node in enumerate(getattr(sub, "dataloader_nodes", [])):
            sd = (node.state_dict(sub_name)
                  if hasattr(node, "state_dict") else None)
            if sd:
                per[str(j)] = sd
        if per:
            dls[sub_name] = per
        cursors = getattr(sub, "_dl_cursor", None)
        if cursors:
            res[sub_name] = {
                str(j): np.asarray(cursors[id(n)], np.int64)
                for j, n in enumerate(sub.res_dl_nodes) if id(n) in cursors}
    if dls:
        state["dataloaders"] = dls
    if res:
        state["resident_cursors"] = res
    return state


def load_executor_state(ex, state: dict) -> None:
    """Inverse of :func:`capture_executor_state` onto a live Executor (same
    graph; values may come from TrainCheckpointer's raw-numpy restore)."""
    import jax
    import jax.numpy as jnp

    def like_current(current, restored):
        """Re-impose the LIVE state's tree structure on restored leaves:
        orbax's raw restore returns tuples as lists, and the jitted step's
        pytrees must keep their exact treedef across a rollback."""
        leaves = [jnp.asarray(l) for l in jax.tree.leaves(restored)]
        return jax.tree.unflatten(jax.tree.structure(current), leaves)

    names = ex._param_file_names()
    params = state.get("params", {})
    for name, node in zip(names, ex.param_nodes):
        if name in params:
            ex.state["params"][id(node)] = ex._place_param(node, params[name])
    for i, n in enumerate(ex._opt_nodes()):
        if str(i) in state.get("slots", {}):
            ex.state["slots"][id(n)] = like_current(
                ex.state["slots"][id(n)], state["slots"][str(i)])
    for i, n in enumerate(ex._stateful_nodes()):
        if str(i) in state.get("op_state", {}):
            ex.state["op_state"][id(n)] = like_current(
                ex.state["op_state"][id(n)], state["op_state"][str(i)])
    ex.state["step"] = int(state["step"])
    ex.state["anomaly_streak"] = 0
    for sub_name, sub in ex.subexecutors.items():
        per = state.get("dataloaders", {}).get(sub_name, {})
        for j, node in enumerate(getattr(sub, "dataloader_nodes", [])):
            if str(j) in per and hasattr(node, "load_state_dict"):
                node.load_state_dict(sub_name, per[str(j)])
        # stale device-side prefetches were issued from pre-restore cursors
        if hasattr(sub, "_dev_prefetch"):
            sub._dev_prefetch.clear()
        cursors = state.get("resident_cursors", {}).get(sub_name, {})
        for j, node in enumerate(getattr(sub, "res_dl_nodes", [])):
            if str(j) in cursors:
                sub._dl_cursor[id(node)] = int(cursors[str(j)])


# ---------------------------------------------------------------------------
# The Supervisor: step-boundary hook object for Executor training loops
# ---------------------------------------------------------------------------

class Supervisor:
    """Ties the four pieces together for the graph-API path. Attach with
    ``executor.attach_supervisor(sup)``; ``SubExecutor.run`` then calls
    ``pre_step`` (watchdog beat + host fault injection) before dispatch and
    ``post_step`` (anomaly policy incl. rollback, periodic checkpoint,
    preemption check → emergency save + :class:`Preempted`) after the state
    commit. Use as a context manager (or call start/stop) so the watchdog
    thread and signal handlers are installed/removed deterministically.

    The gpipe/flagship loops drive the same pieces directly (beat/
    should_stop/AnomalyPolicy.note) — only plain SubExecutor gets the
    automatic wiring.
    """

    def __init__(self, ckptr=None, ckpt_every: Optional[int] = None,
                 anomaly: Optional[AnomalyPolicy] = None,
                 watchdog: Optional[Watchdog] = None,
                 preemption: Optional[PreemptionHandler] = None,
                 fault_injector: Any = "env", job_ckptr=None):
        # job_ckptr: a recovery.JobCheckpointer — when attached (the job
        # runs under a live hetusave coordinator), the SIGTERM grace window
        # upgrades from a worker-local emergency save to a COORDINATED job
        # snapshot, so the preemption leaves a globally consistent epoch
        # (worker + PS shards + cursors) instead of worker state alone.
        self.job_ckptr = job_ckptr
        self.ckptr = ckptr
        self.ckpt_every = ckpt_every
        self.anomaly = anomaly if anomaly is not None else AnomalyPolicy()
        self.watchdog = watchdog
        self.preemption = preemption
        self.fault_injector = (FaultInjector.from_env()
                               if fault_injector == "env" else fault_injector)
        self.last_saved_step: Optional[int] = None

    def start(self) -> "Supervisor":
        if self.watchdog is not None:
            self.watchdog.start()
        if self.preemption is not None:
            self.preemption.install()
        return self

    def stop(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.preemption is not None:
            self.preemption.uninstall()

    def __enter__(self) -> "Supervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- hooks called by SubExecutor.run -----------------------------------
    def pre_step(self, ex, sub, step: int) -> None:
        if self.watchdog is not None:
            self.watchdog.beat(phase=f"{sub.name}:pre_step", step=step)
        if self.fault_injector is not None:
            self.fault_injector.inject_host(step, ex=ex)

    def inject_nan(self, step: int) -> bool:
        """Whether this step's in-trace update should be NaN-poisoned
        (consumes the fault entry)."""
        fi = self.fault_injector
        return fi is not None and fi.fires("nan_grads", step)

    def poison_op(self, step: int) -> Optional[str]:
        """The op whose output this step's trace should NaN-poison
        (consumes the ``nan_op`` fault entry): None = no poison, ``""`` =
        the executor's default first op, else the op's scope name."""
        fi = self.fault_injector
        if fi is None:
            return None
        e = fi.take("nan_op", step)
        if e is None:
            return None
        return e["arg"] or ""

    def post_step(self, ex, sub, step: int, finite: bool = True,
                  loss=None, grad_norm=None) -> None:
        """``loss``/``grad_norm`` are the at-trip headline numbers the
        executor passes on a non-finite step (loss is NaN/Inf by
        construction — that IS the headline; grad_norm arrives when the
        hetuscope provenance pass ran) — recorded in the anomaly event so
        post-mortems need not open the flight recorder for them."""
        if self.watchdog is not None:
            self.watchdog.beat(phase=f"{sub.name}:post_step", step=step)
        action = self.anomaly.note(bool(finite))
        if not finite:
            from .telemetry.scope import json_num
            extra = {}
            if loss is not None:
                # non-finite (the usual case at a trip) serializes as the
                # string "NaN"/"Infinity" — the JSONL must stay strict JSON
                extra["loss"] = json_num(loss)
            if grad_norm is not None:
                extra["grad_norm"] = json_num(grad_norm)
            _tel_event("anomaly", step=step, action=action,
                       streak=self.anomaly.streak, **extra)
        if action == "rollback":
            # freeze the incident BEFORE rolling back: the window must show
            # the poisoned steps, not the restored state overwriting them
            _incident("anomaly", step=step,
                      streak=self.anomaly.streak)
            self._rollback(ex)
        elif action == "ok" and self.ckptr is not None and self.ckpt_every \
                and (step + 1) % self.ckpt_every == 0:
            self.save(ex, step)
        if self.preemption is not None and self.preemption.should_stop():
            # Skip the emergency save when (a) the periodic cadence just
            # wrote this exact step (that save IS the emergency checkpoint)
            # or (b) this call rolled back — the executor now holds the
            # already-durable checkpoint's state, and writing it under id
            # ``step`` would break the 'checkpoint id = last completed
            # step' invariant resume arithmetic relies on.
            coordinated = False
            if self.job_ckptr is not None and action != "rollback":
                # coordinated upgrade: quiesce the whole job and commit one
                # consistent epoch inside the grace window. Best-effort —
                # a failed coordination (e.g. scheduler already gone) falls
                # back to the worker-local emergency save below.
                # save_preempt bounds the drain barrier by the grace
                # budget (JobCheckpointer grace_s / HETU_PREEMPT_GRACE_S,
                # minus headroom) so a hung barrier fails with time LEFT
                # in the window — otherwise the SIGKILL would land
                # mid-coordination and cost the worker-local save too.
                try:
                    self.job_ckptr.save_preempt(ex, step)
                    coordinated = True
                    self.last_saved_step = step
                    _tel_event("emergency_save", step=step,
                               coordinated=True)
                except Exception as je:  # noqa: BLE001 — grace window:
                    # any failure must not cost the worker-local save
                    print(f"# hetu supervisor: coordinated snapshot failed "
                          f"({je!r}); falling back to worker-local save",
                          file=sys.stderr)
            if not coordinated and self.ckptr is not None \
                    and self.last_saved_step != step \
                    and action != "rollback":
                self.save(ex, step)
                _tel_event("emergency_save", step=step)
            durable = (f"durable coordinated epoch: step "
                       f"{self.last_saved_step} (heturun --restore)"
                       if coordinated else
                       "no checkpointer attached — resume will cold-start"
                       if self.ckptr is None else
                       f"durable checkpoint: step {self.last_saved_step}")
            print(f"# hetu supervisor: preemption signal "
                  f"({self.preemption.signum}) at step {step}; {durable}; "
                  f"exiting", file=sys.stderr)
            _tel_event("preempted", flush=True, step=step,
                       signum=self.preemption.signum,
                       durable_step=self.last_saved_step)
            _flight_flush("preempted")
            _incident("preempted", step=step,
                      durable_step=self.last_saved_step)
            raise Preempted(step)

    # -- checkpoint plumbing ------------------------------------------------
    def save(self, ex, step: int) -> None:
        """Checkpoint id = last COMPLETED step; the state inside carries
        ``step+1`` (the next step to run), so resume needs no arithmetic.
        force=True lets an emergency save land on a step the periodic
        cadence already wrote."""
        t0 = time.perf_counter()
        self.ckptr.save_step(step, capture_executor_state(ex), force=True)
        self.last_saved_step = step
        from . import telemetry as _telemetry
        tel = _telemetry.get()
        if tel is not None:
            tel.metrics.histogram("hetu_checkpoint_save_ms").observe(
                (time.perf_counter() - t0) * 1e3)

    def _rollback(self, ex) -> None:
        if self.ckptr is None:
            raise RuntimeError(
                f"{self.anomaly.max_consecutive} consecutive non-finite "
                "steps and no checkpointer to roll back to")
        if self.anomaly.rollbacks > self.anomaly.max_rollbacks:
            raise RuntimeError(
                f"anomaly rollback requested {self.anomaly.rollbacks} times "
                f"(max_rollbacks={self.anomaly.max_rollbacks}); the "
                "divergence survives restore — a deterministic NaN source, "
                "not a transient")
        state, ck_step = self.ckptr.restore_latest()
        if state is None:
            raise RuntimeError(
                f"{self.anomaly.max_consecutive} consecutive non-finite "
                "steps and no checkpoint exists yet to roll back to")
        load_executor_state(ex, state)
        _tel_event("rollback", ckpt_step=int(ck_step),
                   rollbacks=self.anomaly.rollbacks)
        print(f"# hetu supervisor: anomaly streak hit "
              f"{self.anomaly.max_consecutive}; rolled back to checkpoint "
              f"step {ck_step}", file=sys.stderr)


# ---------------------------------------------------------------------------
# Auto-resume driver
# ---------------------------------------------------------------------------

def supervise(loop_fn, ckptr=None, *, max_restarts: int = 3,
              backoff_s: float = 0.5, backoff_factor: float = 2.0,
              recoverable=(Exception,), like=None, mesh=None, specs=None,
              on_preempt: str = "exit", sleep=time.sleep):
    """Run ``loop_fn(state, start_step)`` under restart supervision.

    Before each attempt the latest checkpoint is restored (``state`` is its
    pytree, None on cold start) and ``start_step`` is the first step to run
    — checkpoints are numbered by last COMPLETED step, so
    ``start_step = latest + 1``. On a ``recoverable`` exception the attempt
    counts against ``max_restarts`` and the next one starts after an
    exponentially growing backoff; anything else (and exhaustion) propagates.

    :class:`Preempted` is never retried: with ``on_preempt="exit"`` (the
    default, for __main__ scripts under heturun/k8s) it becomes
    ``SystemExit(EXIT_PREEMPTED)``; ``on_preempt="raise"`` hands it to an
    embedding caller.

    ``like``/``mesh``/``specs`` pass through to
    ``TrainCheckpointer.restore_latest`` for sharded (flagship-path)
    states; the graph-API path restores raw numpy and feeds it to
    :func:`load_executor_state` inside ``loop_fn``.
    """
    if on_preempt not in ("exit", "raise"):
        raise ValueError(f"on_preempt must be 'exit' or 'raise', "
                         f"got {on_preempt!r}")
    restarts = 0
    delay = float(backoff_s)
    while True:
        state, ck_step = (None, None)
        if ckptr is not None:
            state, ck_step = ckptr.restore_latest(like=like, mesh=mesh,
                                                  specs=specs)
        start_step = 0 if ck_step is None else int(ck_step) + 1
        try:
            return loop_fn(state, start_step)
        except Preempted as e:
            if on_preempt == "raise":
                raise
            print(f"# hetu supervise: preempted after step {e.step}; "
                  f"exiting {EXIT_PREEMPTED}", file=sys.stderr)
            raise SystemExit(EXIT_PREEMPTED)
        except recoverable as e:
            _flight_flush("crash")
            restarts += 1
            if restarts > max_restarts:
                _incident("crash", error=type(e).__name__,
                          restarts=restarts - 1)
                raise
            _tel_event("restart", flush=True, attempt=restarts,
                       max_restarts=max_restarts, error=type(e).__name__)
            _incident("crash", error=type(e).__name__, attempt=restarts)
            print(f"# hetu supervise: {type(e).__name__}: {e} — restart "
                  f"{restarts}/{max_restarts} after {delay:.1f}s backoff",
                  file=sys.stderr)
            sleep(delay)
            delay *= backoff_factor
