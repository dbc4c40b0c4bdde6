"""Runtime telemetry: metrics registry + structured tracing + JSONL sinks.

Pillars (docs/OBSERVABILITY.md; numeric health lives in :mod:`.scope` —
hetuscope introspection, NaN/Inf provenance, flight recorder — and is
armed separately via ``HetuConfig(introspect=...)``):

- **Metrics** — process-wide counters/gauges/histograms
  (:mod:`.registry`), snapshotted into a per-step JSONL record and exported
  as a Prometheus textfile.
- **Tracing** — Chrome-trace spans for step phases (:mod:`.tracing`),
  Perfetto-loadable, merged across ranks by ``bin/hetutrace``.
- **Dashboards** — ``bin/hetutop`` tails the JSONL live;
  ``--check`` modes on both CLIs validate the schemas for CI.
- **Distributed tracing** — hetutrail (:mod:`.trail`, pillar 5): PS-wire
  client/server span rings joined by (client_id, req_id), per-step
  critical-path attribution, straggler detection; armed separately by
  ``HETU_TRAIL_DIR`` (``bin/hetutrail`` analyzes/validates).

Activation contract (the zero-overhead-when-off design):

- :func:`get` returns the process's active :class:`Telemetry` or **None**.
  Every instrumented call site does ``tel = telemetry.get()`` followed by an
  ``if tel is None`` early-out — when telemetry is off, the per-step cost is
  that None check and nothing else (no allocations, no syscalls; asserted by
  ``tests/test_telemetry.py``).
- :func:`activate` creates the singleton (first call wins; later calls may
  only *upgrade* ``metrics`` → ``trace``). ``HetuConfig(telemetry=...)``
  calls it from the Executor; standalone components (dataloaders, the PS
  supervisor) only ever :func:`get`.
- Config surface: ``HetuConfig(telemetry="off"|"metrics"|"trace")`` or env
  ``HETU_TELEMETRY`` (same values); output lands in ``HETU_TELEMETRY_DIR``
  (default ``./hetu_telemetry``), one ``metrics-r<rank>.jsonl`` +
  ``trace-r<rank>.json`` + ``metrics-r<rank>.prom`` per rank.

This package is stdlib-only: the heturun launcher parent and the PS
supervisor import it jax-free.
"""
from __future__ import annotations

import atexit
import contextlib
import os
import threading
from typing import Optional

from .registry import (Counter, Gauge, Histogram, MetricsRegistry,  # noqa: F401
                       JsonlSink, DEFAULT_BUCKETS_MS)
from .tracing import Tracer, XlaTraceWindow  # noqa: F401
from . import tracing as _tracing

MODES = ("off", "metrics", "trace")

_lock = threading.Lock()
_active: Optional["Telemetry"] = None


def resolve_mode(mode: Optional[str]) -> str:
    """One spelling of the mode resolution: explicit value wins, env
    ``HETU_TELEMETRY`` fills the default, anything falsy is off."""
    if mode is None:
        mode = os.environ.get("HETU_TELEMETRY", "off") or "off"
    mode = str(mode).strip().lower()
    if mode in ("0", "false", "no", ""):
        mode = "off"
    if mode == "1":  # HETU_TELEMETRY=1 == metrics (the common toggle)
        mode = "metrics"
    if mode not in MODES:
        raise ValueError(f"telemetry must be one of {MODES}, got {mode!r}")
    return mode


def default_rank() -> int:
    """Rank identity for file names: the launcher's WORKER_ID (set by
    heturun/launcher for every worker) — resolvable before jax initializes."""
    try:
        return int(os.environ.get("WORKER_ID", "0"))
    except ValueError:
        return 0


def run_identity():
    """``(run_id, incarnation)`` for this process, or ``(None, 0)``.

    heturun mints ``HETU_RUN_ID`` and every role inherits it; a process
    started outside heturun (tests, notebooks) simply has no run identity —
    nothing is fabricated, so rows stay byte-stable for such runs. The
    incarnation counts supervisor restarts (heturun bumps it per respawned
    worker and per inherited relaunch)."""
    run_id = os.environ.get("HETU_RUN_ID") or None
    inc = 0
    if run_id:
        try:
            inc = int(os.environ.get("HETU_RUN_INCARNATION", "0"))
        except ValueError:
            inc = 0
    return run_id, inc


class Telemetry:
    """One per process: registry + sinks + (in trace mode) the tracer."""

    def __init__(self, mode: str, out_dir: str, rank: int):
        self.mode = mode
        self.dir = out_dir
        self.rank = int(rank)
        self.metrics = MetricsRegistry()
        base_fields = {"rank": self.rank, "pid": os.getpid()}
        run_id, inc = run_identity()
        if run_id:
            # preserialized with the rest of the base fields: the hot-path
            # step record pays zero extra serialization for run identity
            base_fields["run_id"] = run_id
            base_fields["inc"] = inc
        self.sink = JsonlSink(
            os.path.join(out_dir, f"metrics-r{self.rank}.jsonl"),
            base_fields=base_fields)
        self.tracer: Optional[Tracer] = (
            Tracer(os.path.join(out_dir, f"trace-r{self.rank}.json"),
                   rank=self.rank) if mode == "trace" else None)
        self.xla_window = XlaTraceWindow.from_env()
        if self.xla_window is not None:
            # advertise the deep-dive window in the JSONL so hetuprof can
            # locate the XLA trace dir and normalize per-op times per step
            # without re-reading the caller's environment
            self.sink.write({"kind": "xla_trace",
                             "dir": self.xla_window.dir,
                             "start_step": self.xla_window.start_step,
                             "n_steps": self.xla_window.n_steps})
        self._prom_path = os.path.join(out_dir,
                                       f"metrics-r{self.rank}.prom")
        # full registry snapshots ride only every Nth step record: the
        # snapshot sorts each histogram's recent window for percentiles,
        # which would dominate sub-ms steps if taken per step (measured:
        # ~0.4 ms vs ~15 µs for the plain record). hetutop reads the
        # latest record that HAS metrics; every step still records
        # step/step_ms/phases.
        self._snapshot_every = max(1, int(os.environ.get(
            "HETU_TELEMETRY_SNAPSHOT_EVERY", "20")))
        # programs compiled before telemetry came on are not written
        self._compile_seq = _tracing.compile_count()
        self._closed = False

    # -- tracing -----------------------------------------------------------
    def span(self, name: str, cat: str = "step",
             args: Optional[dict] = None):
        """Span context manager; a no-op context in metrics mode so call
        sites need not branch on the mode."""
        if self.tracer is not None:
            return self.tracer.span(name, cat, args)
        return contextlib.nullcontext()

    # -- events ------------------------------------------------------------
    def event(self, name: str, **fields) -> None:
        """Typed event: one JSONL record + a labeled counter + (trace mode)
        an instant marker on the timeline."""
        self.metrics.counter("hetu_events_total", {"event": name}).inc()
        self.sink.write({"kind": "event", "name": name, **fields})
        if self.tracer is not None:
            self.tracer.instant(name, args=fields or None)

    # -- per-step record ---------------------------------------------------
    def step_record(self, sub: str, step: int, step_ms: float,
                    phases: Optional[dict] = None, **extra) -> None:
        if extra or step % self._snapshot_every == 0 \
                or not sub.isidentifier():
            rec = {"kind": "step", "sub": sub, "step": int(step),
                   "step_ms": round(float(step_ms), 4)}
            if phases:
                rec["phases"] = {k: round(float(v), 4)
                                 for k, v in phases.items()}
            if extra:
                rec.update(extra)
            if step % self._snapshot_every == 0:
                rec["metrics"] = self.metrics.snapshot()
            self.sink.write(rec)
            return
        # hot path (every non-snapshot step): direct string formatting —
        # json.dumps over the merged dict measured ~4x the cost; phase keys
        # are fixed identifiers and values finite floats, so the fragment
        # is valid JSON by construction
        body = (f'"kind":"step","sub":"{sub}","step":{int(step)},'
                f'"step_ms":{float(step_ms):.4f}')
        if phases:
            body += (',"phases":{'
                     + ",".join(f'"{k}":{float(v):.4f}'
                                for k, v in phases.items()) + "}")
        self.sink.write_fields(body)

    def record(self, kind: str, **fields) -> None:
        """Free-form record (``ps_server`` health rows etc.)."""
        self.sink.write({"kind": kind, **fields})

    def record_compiles(self) -> None:
        """One ``compile`` record a program the process has compiled since
        the last call, whichever Executor's step (or none) compiled it: the
        compile log's fields. An int compare where nothing compiled."""
        seen, self._compile_seq = self._compile_seq, _tracing.compile_count()
        if seen == self._compile_seq:
            return
        for r in _tracing.compile_log():
            if r["seq"] > seen:
                self.sink.write({"kind": "compile", **{
                    k: r[k] for k in ("fun_name", "trace_s", "lower_s",
                                      "backend_s", "cache", "cache_read_s",
                                      "thread", *_tracing.COMPILE_PARTS)}})

    # -- lifecycle ---------------------------------------------------------
    def flush(self) -> None:
        """Crash-durability point: resilience abort paths call this before
        ``os._exit``; also runs at interpreter exit via atexit. Writes a
        closing ``final`` record so the JSONL tail always carries current
        counter values even between snapshot-cadence steps."""
        try:
            self.sink.write({"kind": "final",
                             "metrics": self.metrics.snapshot()})
        except Exception:  # noqa: BLE001
            pass
        self.sink.flush()
        if self.tracer is not None:
            self.tracer.flush()
        if self.xla_window is not None:
            # a run that ends (or aborts) inside the HETU_XLA_TRACE window
            # must still stop_trace, or jax discards the buffered profile —
            # exactly the short/crashing runs the window is for
            try:
                self.xla_window.stop()
            except Exception:  # noqa: BLE001
                pass
        try:
            self.metrics.write_prometheus(self._prom_path)
        except OSError:
            pass  # a full/readonly disk must not take the abort path down

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.flush()
        self.sink.close()


def get() -> Optional[Telemetry]:
    """The active telemetry, or None when off — the per-call-site gate."""
    return _active


def record_model_info(**fields) -> None:
    """Advertise model geometry (``n_layers``, ``d_model``, ``seq_len``,
    ``causal``, optionally ``n_params``) to the dashboards: hetutop uses it
    to report MFU under the attention-inclusive denominator next to 6ND
    (docs/ROOFLINE.md). No-op when telemetry is off — trainers call this
    unconditionally after building their model."""
    t = get()
    if t is not None:
        t.record("model_info", **fields)


def activate(mode: Optional[str] = None, out_dir: Optional[str] = None,
             rank: Optional[int] = None) -> Optional[Telemetry]:
    """Create (or return) the process singleton. ``mode`` resolves via
    :func:`resolve_mode`; "off" returns None without touching an existing
    active instance (a metrics-enabled trainer is not disarmed by a later
    eval Executor constructed with defaults). A later ``trace`` request
    upgrades a ``metrics`` instance in place (same registry, tracer added)."""
    global _active
    mode = resolve_mode(mode)
    if mode == "off":
        return None
    with _lock:
        if _active is not None:
            if mode == "trace" and _active.tracer is None:
                _active.mode = "trace"
                _active.tracer = Tracer(
                    os.path.join(_active.dir,
                                 f"trace-r{_active.rank}.json"),
                    rank=_active.rank)
            return _active
        out_dir = out_dir or os.environ.get("HETU_TELEMETRY_DIR",
                                            "hetu_telemetry")
        rank = default_rank() if rank is None else int(rank)
        _active = Telemetry(mode, out_dir, rank)
        atexit.register(_shutdown_atexit)
        return _active


def _shutdown_atexit() -> None:
    t = _active
    if t is not None:
        t.close()


def shutdown() -> None:
    """Close and detach the singleton (tests; also lets a long-lived process
    rotate output directories by re-activating)."""
    global _active
    with _lock:
        t, _active = _active, None
    if t is not None:
        t.close()
