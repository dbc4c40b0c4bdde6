"""hetuprof — op-level performance attribution, roofline analysis, HBM
memory observability, and the perf-regression gate (docs/PROFILING.md).

Three pillars on top of the telemetry bus:

1. **Op attribution** — the executor lowers every Op under
   ``jax.named_scope(op.name)``, so the optimized HLO's ``op_name`` metadata
   carries graph-op identity per instruction. This module joins a bounded
   ``HETU_XLA_TRACE`` profiler window (Chrome-trace ``*.trace.json.gz``)
   against that metadata: device-lane event durations land on the graph op
   that generated them (backward work resolves through the ``jvp(...)`` /
   ``transpose(...)`` wrappers to its forward op), collectives land in a
   ``<collective>`` bucket, and the per-step compute / collective-comm /
   PS-RPC / host breakdown falls out of the join with the step-record phases.
2. **Roofline** — per-op analytic flops/bytes from the abstract shape
   inference (hetulint's substrate) classify each op family compute- vs
   HBM-bound against the assumed peaks; measured times from pillar 1 turn
   the prediction into a residual — the calibration data the cost-model
   planner (ROADMAP item 3) consumes.
3. **Perf-regression gate** — ``gate()`` diffs two bench/telemetry summaries
   cell-by-cell with a tolerance, and distinguishes *regressed* from *could
   not measure*: exit 0 clean, 1 regressed, 2 current run incomplete,
   3 baseline unusable — a partial run (a round killed rc=124) can
   never read as a win or a loss.

Import contract: module-level imports are **stdlib only**, and there are no
package-relative imports — ``bin/hetuprof`` loads this file directly via
``importlib.util.spec_from_file_location`` (importing the ``hetu_tpu``
package would pull jax). Anything that needs the graph/executor imports it
lazily inside the function that uses it.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import math
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# Published per-chip peaks, keyed by the ``device_kind`` jax reports, each
# with its source: the table the executor's run_info and hetutop read
# (benchmark/reduce/peaks.py holds the benchmark's copy, and
# tests/test_profiler.py keeps the two equal). A kind that is not here
# has no peak — its MFU and roofline figures are None and run_info says
# ``peak: unknown``, never another chip's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "tflops": 197.0, "gbs": 819.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16 and 819 GB/s HBM per chip"},
}


def device_peaks(device_kind) -> Optional[dict]:
    """``{"tflops", "gbs", "source"}`` for a known ``device_kind``, else
    None."""
    return DEVICE_PEAKS.get(device_kind)


def mfu(flops_per_step, step_s, device_kind) -> Optional[float]:
    """Model FLOP/s utilization against the table's peak for this device,
    or None when any of the three is unknown."""
    peaks = device_peaks(device_kind)
    if not flops_per_step or not step_s or peaks is None:
        return None
    return flops_per_step / step_s / (peaks["tflops"] * 1e12)


# The analytic tools (roofline prediction, the planner's cost model) price
# a graph for a NAMED target, not for whatever device happens to be found:
# their defaults are the v5e row, overridable per call.
DEFAULT_PEAK_TFLOPS = DEVICE_PEAKS["TPU v5 lite"]["tflops"]
DEFAULT_PEAK_GBS = DEVICE_PEAKS["TPU v5 lite"]["gbs"]

# gate exit codes — the contract CI scripts key on
GATE_OK = 0
GATE_REGRESSED = 1
GATE_INCOMPLETE_CURRENT = 2
GATE_INCOMPLETE_BASELINE = 3


def attn_flops(batch, seq, n_layers, d_model, causal):
    """Attention-score matmul FLOPs per training step (fwd+bwd), which the
    6ND rule EXCLUDES (they scale with T^2, not with N): per layer the
    forward QK^T and PV matmuls cost 2*2*B*T^2*d; backward doubles it ->
    12*B*T^2*d*L for a bidirectional encoder. A causal decoder only
    computes the lower triangle (the flash kernel skips upper blocks), so
    half. Reporting MFU against 6ND alone OVERSTATES utilization at long
    seq — report both denominators (hetutop does)."""
    full = 12.0 * batch * seq * seq * d_model * n_layers
    return full / 2.0 if causal else full


# ---------------------------------------------------------------------------
# pillar 1 — Chrome-trace parsing and op attribution
# ---------------------------------------------------------------------------

def load_trace_events(path: str) -> List[dict]:
    """Events of one Chrome-trace file (.json or .json.gz; the jax profiler
    writes the object form, our own Tracer too; a bare event list also
    loads)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        return doc.get("traceEvents", [])
    return doc if isinstance(doc, list) else []


def find_xla_traces(root: str) -> List[str]:
    """All profiler trace files under a ``jax.profiler`` output dir (the
    layout is ``<dir>/plugins/profile/<run>/<host>.trace.json.gz``)."""
    out = []
    for base, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".trace.json.gz") or fn.endswith(".trace.json"):
                out.append(os.path.join(base, fn))
    return sorted(out)


_HLO_META = re.compile(r"%([\w.\-]+)\s*=\s*[^\n]*?op_name=\"([^\"]+)\"")
_HLO_CALL = re.compile(
    r"%(call[\w.\-]*)\s*=\s*[^\n]*?to_apply=%parallel_([\w.\-]+)")


def hlo_op_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` metadata path, parsed from the
    optimized-HLO text (``SubExecutor.dump_hlo(stage="optimized")``). The
    trace's device events are named after these instructions — this map is
    the join key back to graph ops.

    Second pass: the CPU backend wraps parallelized fusions in metadata-less
    ``%call.N = call(...), to_apply=%parallel_<fusion>`` instructions whose
    trace events would otherwise be unattributable — they inherit the
    wrapped fusion's path."""
    out = {m.group(1): m.group(2) for m in _HLO_META.finditer(hlo_text)}
    for m in _HLO_CALL.finditer(hlo_text):
        call_name, fused = m.group(1), m.group(2)
        if call_name in out:
            continue
        for cand in (fused, fused + ".clone",
                     re.sub(r"\.\d+$", "", fused),
                     re.sub(r"\.\d+$", "", fused) + ".clone"):
            if cand in out:
                out[call_name] = out[cand]
                break
    return out


_WRAPPER = re.compile(r"^(?:jvp|vjp|transpose|remat|checkpoint)\((.+)\)$")
_OPNAME_GUESS = re.compile(r"^[\w().\-]+_\d+$")


def scope_of(op_path: str, known_ops=None) -> Tuple[Optional[str], bool]:
    """Graph-op identity of one HLO ``op_name`` path.

    Returns ``(op, is_backward)``. The INNERMOST known-op segment wins:
    ``Gradient(w)/transpose(Gradient(w))/jvp(MatMul_3)/transpose`` is
    backward work OF ``MatMul_3``, not of the Gradient node. Without a
    ``known_ops`` set, segments shaped like hetu op names (``Name_<id>``)
    are accepted."""
    best = None
    bwd = False
    for seg in op_path.split("/"):
        if seg.startswith("jit("):
            continue
        if seg.startswith("transpose("):
            bwd = True
        inner = seg
        while True:
            m = _WRAPPER.match(inner)
            if m is None:
                break
            inner = m.group(1)
        if known_ops is not None:
            if inner in known_ops:
                best = inner
        elif _OPNAME_GUESS.match(inner):
            best = inner
    return best, bwd


# collective bases as they appear in device-lane event / HLO names
COLLECTIVE_BASES = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute",
                    "collective-broadcast", "send", "recv", "send-done",
                    "recv-done")

# host-side profiler noise that must never be attributed as device time
_NOISE_PREFIXES = ("ThreadpoolListener", "Thunk", "TaskDispatcher",
                   "H2D ", "D2H ", "$", "Tfrt", "DevicePut", "copy_",
                   "BufferFromHostBuffer",
                   # the CPU thunk executor's completion markers ("end:
                   # dot.3" closes the async "dot.3" it follows) and waits
                   "end: ", "SlinkyThreadPool")


def _base_name(event_name: str) -> str:
    """``dot.9`` -> ``dot``; ``broadcast_maximum_fusion.clone`` ->
    ``broadcast_maximum_fusion``."""
    return event_name.split(".", 1)[0]


def _union_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered microseconds of possibly-overlapping [t0, t1) spans —
    the wall-clock footprint of an op whose slices ran on several worker
    threads/cores in parallel (summing durations would overcount)."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur0, cur1 = intervals[0]
    for t0, t1 in intervals[1:]:
        if t0 > cur1:
            total += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    return total + (cur1 - cur0)


def op_family(op: str) -> str:
    """``MatMul_3`` -> ``MatMul``; ``Gradient(w)`` -> ``Gradient``."""
    m = re.match(r"^(.*?)_\d+$", op)
    base = m.group(1) if m else op
    return re.sub(r"\(.*\)$", "", base) or base


@dataclass
class OpRow:
    op: str
    family: str
    count: int = 0
    total_us: float = 0.0      # summed slice durations (CPU/core time)
    bwd_us: float = 0.0        # share attributed through jvp/transpose
    wall_us: float = 0.0       # interval union (parallel slices merged)
    intervals: list = field(default_factory=list)

    def finish(self):
        self.wall_us = _union_us(self.intervals)
        self.intervals = []
        return self


class Attribution:
    """Per-op time table for one profiler window."""

    def __init__(self, rows: Dict[str, OpRow], steps: int,
                 span_us: float = 0.0):
        self.rows = rows
        self.steps = max(1, int(steps))
        # global interval union over every device event: the wall-clock
        # footprint of the window's device work (parallel slices and
        # parent/child call spans collapse) — the number to hold against
        # the executor's measured compute span
        self.span_us = span_us

    @property
    def device_wall_us(self) -> float:
        return sum(r.wall_us for r in self.rows.values())

    @property
    def unattributed_us(self) -> float:
        """Device time visible in the trace but not resolvable to a graph
        op (sub-computation instructions, renamed fusion clones)."""
        return sum(r.wall_us for r in self.rows.values()
                   if r.op.startswith("<") and r.family != "<collective>")

    @property
    def attributed_fraction(self) -> float:
        wall = self.device_wall_us
        return (wall - self.unattributed_us) / wall if wall else 0.0

    @property
    def collective_wall_us(self) -> float:
        return sum(r.wall_us for r in self.rows.values()
                   if r.family == "<collective>")

    def families(self) -> Dict[str, dict]:
        fams: Dict[str, dict] = {}
        for r in self.rows.values():
            f = fams.setdefault(r.family, {"family": r.family, "n_ops": 0,
                                           "count": 0, "total_us": 0.0,
                                           "wall_us": 0.0, "bwd_us": 0.0})
            f["n_ops"] += 1
            f["count"] += r.count
            f["total_us"] += r.total_us
            f["wall_us"] += r.wall_us
            f["bwd_us"] += r.bwd_us
        return fams

    def table(self, top: Optional[int] = None) -> str:
        rows = sorted(self.rows.values(), key=lambda r: -r.wall_us)
        if top:
            rows = rows[:top]
        wall = self.device_wall_us or 1.0
        lines = [f"{'op':<40} {'family':<18} {'count':>7} "
                 f"{'us/step':>10} {'bwd%':>6} {'share%':>7}"]
        for r in rows:
            bwd = 100.0 * r.bwd_us / r.total_us if r.total_us else 0.0
            lines.append(
                f"{r.op[:40]:<40} {r.family[:18]:<18} {r.count:>7} "
                f"{r.wall_us / self.steps:>10.1f} {bwd:>6.1f} "
                f"{100.0 * r.wall_us / wall:>7.2f}")
        lines.append(
            f"{'TOTAL (device busy)':<40} {'':<18} "
            f"{sum(r.count for r in self.rows.values()):>7} "
            f"{self.device_wall_us / self.steps:>10.1f} {'':>6} {100.0:>7.2f}")
        lines.append(
            f"# device wall span {self.span_us / self.steps:.1f} us/step "
            f"over {self.steps} step(s); "
            f"{100.0 * self.attributed_fraction:.1f}% of busy time "
            "attributed to graph ops")
        return "\n".join(lines)

    def as_dict(self) -> dict:
        return {
            "steps": self.steps,
            "device_busy_us_per_step": self.device_wall_us / self.steps,
            "device_span_us_per_step": self.span_us / self.steps,
            "attributed_fraction": round(self.attributed_fraction, 4),
            "collective_us_per_step":
                self.collective_wall_us / self.steps,
            "unattributed_us_per_step": self.unattributed_us / self.steps,
            "ops": [{"op": r.op, "family": r.family, "count": r.count,
                     "total_us": round(r.total_us, 1),
                     "bwd_us": round(r.bwd_us, 1),
                     "wall_us": round(r.wall_us, 1),
                     "us_per_step": round(r.wall_us / self.steps, 2)}
                    for r in sorted(self.rows.values(),
                                    key=lambda r: -r.wall_us)],
        }


def device_lanes(events: List[dict]) -> Optional[set]:
    """(pid, tid) lanes that carry DEVICE work, from the trace's own
    metadata: XLA executor/client threads are named ``tf_*`` on the CPU
    backend, and TPU device timelines live under processes named
    ``/device:...``. None when the trace carries no lane metadata (our
    synthetic test traces) — callers fall back to name-shape filtering."""
    tf_tids = set()
    dev_pids = set()
    saw_meta = False
    for ev in events:
        if ev.get("ph") != "M":
            continue
        name = (ev.get("args") or {}).get("name", "")
        if ev.get("name") == "thread_name":
            saw_meta = True
            if name.startswith("tf_"):
                tf_tids.add((ev.get("pid"), ev.get("tid")))
        elif ev.get("name") == "process_name":
            saw_meta = True
            if "/device:" in name:
                dev_pids.add(ev.get("pid"))
    if not saw_meta:
        return None
    return {(p, t) for (p, t) in tf_tids} | {(p, None) for p in dev_pids}


def attribute(events: List[dict], op_map: Optional[Dict[str, str]] = None,
              known_ops=None, steps: Optional[int] = None) -> Attribution:
    """Attribute device-lane trace events to graph ops.

    ``op_map`` (HLO instruction -> op_name path, from :func:`hlo_op_map`)
    is the precise join; events on a device lane the map doesn't cover are
    bucketed per HLO base name (``<dot>``, ``<fusion>`` ...) so nothing is
    silently dropped. Host lanes (the python TraceMe firehose) are excluded
    via the trace's own lane metadata. ``steps`` defaults to the number of
    ``hetu_step`` StepTraceAnnotation events in the window (the executor
    opens one per step while a profiler trace is active)."""
    lanes = device_lanes(events)
    rows: Dict[str, OpRow] = {}
    all_intervals: List[Tuple[float, float]] = []
    n_steps = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if name.startswith("hetu_step"):
            n_steps += 1
            continue
        dur = float(ev.get("dur", 0.0) or 0.0)
        if dur <= 0 or any(name.startswith(p) for p in _NOISE_PREFIXES):
            continue
        if lanes is not None:
            lane_ok = (ev.get("pid"), ev.get("tid")) in lanes \
                or (ev.get("pid"), None) in lanes
            if not lane_ok:
                continue              # host lane: not device time
        elif not re.match(r"^[a-z][\w.\-]*$", name):
            continue                  # no metadata: keep HLO-shaped names
        base = _base_name(name)
        bwd = False
        mapped = None
        if op_map is not None:
            # event names and HLO instruction names drift by rename
            # suffixes (".clone", trailing ".N") — try the variants
            for cand in (name, name + ".clone", base, base + ".clone"):
                mapped = op_map.get(cand)
                if mapped is not None:
                    break
        if base in COLLECTIVE_BASES or name in COLLECTIVE_BASES:
            op, fam = name, "<collective>"
        elif mapped is not None:
            op, bwd = scope_of(mapped, known_ops)
            if op is None:
                op, fam = f"<{base}>", f"<{base}>"
            else:
                fam = op_family(op)
        else:
            # a device event the HLO map has no entry for (sub-computation
            # instruction, renamed clone): visible, not silently dropped
            op = f"<{base}>"
            fam = "<fusion>" if "fusion" in base else f"<{base}>"
        row = rows.get(op)
        if row is None:
            row = rows[op] = OpRow(op=op, family=fam)
        row.count += 1
        row.total_us += dur
        if bwd:
            row.bwd_us += dur
        t0 = float(ev.get("ts", 0.0))
        row.intervals.append((t0, t0 + dur))
        all_intervals.append((t0, t0 + dur))
    span = _union_us(all_intervals)
    for row in rows.values():
        row.finish()
    if steps is None:
        steps = n_steps or 1
    return Attribution(rows, steps, span_us=span)


# ---------------------------------------------------------------------------
# telemetry-dir readers (shared by the CLI and profile_dir)
# ---------------------------------------------------------------------------

def read_metrics_records(tel_dir: str) -> List[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(tel_dir,
                                              "metrics-r*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    recs.append(rec)
    return recs


def step_phase_means(records: List[dict]) -> dict:
    """Mean per-phase milliseconds over the run's step records, compile
    steps excluded (a compile step's dispatch carries the XLA compile and
    would poison the steady-state mean)."""
    sums: Dict[str, float] = {}
    n = 0
    for rec in records:
        if rec.get("kind") != "step":
            continue
        phases = rec.get("phases") or {}
        if "compile_ms" in phases:
            continue
        n += 1
        sums["step_ms"] = sums.get("step_ms", 0.0) + float(rec["step_ms"])
        for k, v in phases.items():
            sums[k] = sums.get(k, 0.0) + float(v)
    if n == 0:
        return {}
    return {k: v / n for k, v in sums.items()} | {"n_steps": n}


def last_metrics_snapshot(records: List[dict]) -> dict:
    snap: dict = {}
    for rec in records:
        if rec.get("kind") in ("step", "final") \
                and isinstance(rec.get("metrics"), dict):
            snap = rec["metrics"]
    return snap


def step_breakdown(phase_means: dict, attribution=None) -> dict:
    """Per-step compute / collective-comm / PS-RPC / host milliseconds.

    ``dispatch_ms`` is the on-device window (compute + in-program
    collectives); the device trace (when present) splits the collective
    share out of it. PS RPC time is the executor's critical-path stamp;
    host is everything else (feed staging, python, bookkeeping)."""
    if not phase_means:
        return {}
    step = phase_means.get("step_ms", 0.0)
    dispatch = phase_means.get("dispatch_ms", 0.0)
    ps_rpc = phase_means.get("ps_comm_ms", 0.0)
    coll = 0.0
    if attribution is not None and attribution.steps:
        coll = attribution.collective_wall_us / attribution.steps / 1e3
    out = {
        "step_ms": step,
        "compute_ms": max(0.0, dispatch - coll),
        "collective_ms": coll,
        "ps_rpc_ms": ps_rpc,
        "host_ms": max(0.0, step - dispatch - ps_rpc),
    }
    if step > 0:
        out["comm_fraction"] = min(1.0, (coll + ps_rpc) / step)
    # hetutrail critical path (trail.step_legs' decomposition, inlined so
    # this module stays loadable by file path): who-blocked-whom per mean
    # step, not just totals — the planner's calibration signal
    legs = cp_legs(phase_means)
    total = sum(legs.values())
    if total > 0:
        dom = max(legs, key=legs.get)
        out["cp_legs_ms"] = {k: round(v, 4) for k, v in legs.items()}
        out["cp_dominant"] = dom
        out["cp_fraction"] = round(legs[dom] / total, 4)
    return out


_TRAIL_MOD = None
_WATCH_MOD = None


def _trail_mod():
    """The hetutrail module, loadable BOTH ways this file is: as the
    package module (tests) and by file path (bin/hetuprof, which must not
    import the jax-bearing ``hetu_tpu`` package root) — the sibling
    trail.py is stdlib-only, so file-path loading it is always safe."""
    global _TRAIL_MOD
    if _TRAIL_MOD is None:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "trail.py")
        spec = importlib.util.spec_from_file_location("_hetuprof_trail",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_hetuprof_trail"] = mod
        spec.loader.exec_module(mod)
        _TRAIL_MOD = mod
    return _TRAIL_MOD


def cp_legs(phase_means: dict) -> dict:
    """The per-step blocking chain from mean phases — ONE definition,
    ``trail.step_legs`` (feed → PS pull wait → compute → PS push →
    poststep); zero-valued on runs that predate the ps_pull/ps_push phase
    split."""
    return _trail_mod().step_legs(phase_means)


def profile_dir(tel_dir: str, trace_dir: Optional[str] = None,
                hlo_path: Optional[str] = None, known_ops=None,
                steps: Optional[int] = None) -> dict:
    """One-stop offline report over a telemetry directory: reads the step
    records, locates the ``HETU_XLA_TRACE`` window (advertised by the
    ``xla_trace`` record), attributes the device trace, and assembles the
    breakdown + memory view. Every absence degrades a section to None
    instead of failing — a partial run yields a partial report that SAYS
    it is partial. The report is plain JSON (``json.dumps``-safe); use
    :func:`profile_dir_with_attribution` to also get the live
    :class:`Attribution` for table rendering."""
    report, _att = profile_dir_with_attribution(
        tel_dir, trace_dir=trace_dir, hlo_path=hlo_path,
        known_ops=known_ops, steps=steps)
    return report


def profile_dir_with_attribution(
        tel_dir: str, trace_dir: Optional[str] = None,
        hlo_path: Optional[str] = None, known_ops=None,
        steps: Optional[int] = None) -> Tuple[dict, Optional["Attribution"]]:
    records = read_metrics_records(tel_dir)
    phase_means = step_phase_means(records)
    snap = last_metrics_snapshot(records)
    window = next((r for r in records if r.get("kind") == "xla_trace"), None)
    if trace_dir is None and window is not None:
        trace_dir = window.get("dir")
    attribution = None
    trace_files: List[str] = []
    if trace_dir and os.path.isdir(trace_dir):
        trace_files = find_xla_traces(trace_dir)
        events: List[dict] = []
        for p in trace_files:
            events.extend(load_trace_events(p))
        op_map = None
        if hlo_path and os.path.exists(hlo_path):
            with open(hlo_path) as f:
                op_map = hlo_op_map(f.read())
        if events:
            attribution = attribute(events, op_map=op_map,
                                    known_ops=known_ops, steps=steps)
    report = {
        "telemetry_dir": tel_dir,
        "xla_trace_dir": trace_dir,
        "trace_files": len(trace_files),
        "phase_means_ms": phase_means or None,
        "breakdown": step_breakdown(phase_means, attribution) or None,
        "attribution": attribution.as_dict() if attribution else None,
        "memory": {k: v for k, v in snap.items()
                   if k.startswith("hetu_hbm_")} or None,
        "model_info": next((
            {k: v for k, v in r.items()
             if k not in ("kind", "ts", "rank", "pid")}
            for r in records if r.get("kind") == "model_info"), None),
        "incomplete": [],
    }
    if not phase_means:
        report["incomplete"].append("no step records")
    if attribution is None:
        report["incomplete"].append("no XLA trace window captured")
    return report, attribution


def profile_executor(executor, name: str = "train",
                     trace_dir: Optional[str] = None,
                     steps: Optional[int] = None) -> dict:
    """In-process attribution for a live Executor: uses the subexecutor's
    own optimized HLO (exact instruction->op join) plus its topo as the
    known-op set. ``trace_dir`` defaults to the active telemetry's
    ``HETU_XLA_TRACE`` window dir."""
    from hetu_tpu import telemetry as _tel
    from hetu_tpu.graph.executor import _op_scope
    sub = executor.subexecutors[name]
    known = {_op_scope(op) for op in sub.topo}
    hlo = sub.dump_hlo(stage="optimized")
    tel = _tel.get()
    if trace_dir is None and tel is not None and tel.xla_window is not None:
        trace_dir = tel.xla_window.dir
    events: List[dict] = []
    for p in find_xla_traces(trace_dir) if trace_dir else []:
        events.extend(load_trace_events(p))
    attribution = attribute(
        events, op_map=hlo_op_map(hlo) if hlo else None,
        known_ops=known, steps=steps)
    phases = sub.last_phases or {}
    return {
        "attribution": attribution,
        "hlo_ops": len(known),
        "last_phases": phases,
        "memory": sub.last_memory_analysis(),
        "cost": sub.last_cost_analysis(),
    }


# ---------------------------------------------------------------------------
# pillar 2 — roofline: predicted flops/bytes per op vs measured time
# ---------------------------------------------------------------------------

# op families whose flops scale with a contraction (fwd cost 2*out*K); the
# backward pass re-runs two such matmuls -> 3x under training
_MATMUL_FAMILIES = {"MatMul", "BatchMatMul", "Linear", "MatMulwithBias"}
_CONV_FAMILIES = {"Conv2d", "Conv2dAddBias"}
# elementwise-ish flop multipliers per output element (coarse by design:
# the roofline wants orders of magnitude, the residual column absorbs it)
_FLOPS_PER_ELEM = {"Softmax": 5.0, "SoftmaxCrossEntropy": 8.0,
                   "LayerNorm": 8.0, "BatchNorm": 8.0, "Gelu": 10.0,
                   "Relu": 1.0, "Dropout": 2.0}


def _nbytes(meta) -> int:
    try:
        n = 1
        for s in meta.shape:
            n *= int(s)
        return n * meta.dtype.itemsize
    except Exception:  # noqa: BLE001 — unknown meta contributes nothing
        return 0


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def op_cost_estimate(node, meta_of) -> Tuple[float, float]:
    """(flops, bytes) analytic estimate for one op's FORWARD evaluation.

    ``meta_of(node) -> ShapeDtypeStruct | None`` supplies abstract shapes.
    Bytes = inputs + output traffic (the HBM-side roofline axis); flops by
    family formula — exact for the matmul/conv heavy hitters, coarse
    multipliers elsewhere."""
    out_meta = meta_of(node)
    in_metas = [meta_of(i) for i in node.inputs]
    bytes_ = _nbytes(out_meta) + sum(_nbytes(m) for m in in_metas
                                     if m is not None)
    if out_meta is None or not hasattr(out_meta, "shape"):
        return 0.0, float(bytes_)
    out_elems = _prod(out_meta.shape)
    fam = op_family(node.name)
    if fam in _MATMUL_FAMILIES and in_metas and in_metas[0] is not None \
            and getattr(in_metas[0], "shape", None):
        k = int(in_metas[0].shape[-1])
        return 2.0 * out_elems * k, float(bytes_)
    if fam in _CONV_FAMILIES and len(in_metas) > 1 \
            and in_metas[1] is not None \
            and len(getattr(in_metas[1], "shape", ())) == 4:
        _o, i, kh, kw = in_metas[1].shape
        return 2.0 * out_elems * int(i) * int(kh) * int(kw), float(bytes_)
    # hetukern families (docs/KERNELS.md): the fused-embed-grad and
    # csr-spmm tiers are HBM-roof citizens — flops are the segment adds
    # (one per input element / two per nnz·feature), bytes dominate
    if fam == "EmbeddingLookUpGradient":
        in_elems = (_prod(in_metas[0].shape)
                    if in_metas and in_metas[0] is not None
                    and getattr(in_metas[0], "shape", None) else out_elems)
        return float(in_elems), float(bytes_)   # one add per grad element
    if fam in ("CSRMatMat", "CSRMatVec"):
        # nnz is runtime-fed (COO feed); 2·out_elems is the dense-output
        # floor — the residual column absorbs the per-graph density
        return 2.0 * out_elems, float(bytes_)
    if fam.startswith("Embedding"):
        return 0.0, float(bytes_)   # a gather: pure HBM traffic
    return _FLOPS_PER_ELEM.get(fam, 1.0) * out_elems, float(bytes_)


@dataclass
class RooflineRow:
    family: str
    n_ops: int
    flops: float
    bytes: float
    intensity: float            # flops per byte
    bound: str                  # "compute" | "memory"
    predicted_us: float
    measured_us: Optional[float] = None
    residual: Optional[float] = None   # measured / predicted
    # hetutrail: share of the step's measured blocking chain held by the
    # leg this family executes in (compute for on-device families, the PS
    # legs for boundary comm) — a 3x residual on a family at 90% of the
    # critical path is a planner problem; the same residual at 2% is not
    cp_fraction: Optional[float] = None


def roofline_rows(nodes, training: bool = True, target: Optional[str] = None,
                  peak_tflops: float = DEFAULT_PEAK_TFLOPS,
                  peak_gbs: float = DEFAULT_PEAK_GBS,
                  attribution: Optional[Attribution] = None,
                  cp: Optional[dict] = None) -> List[RooflineRow]:
    """Roofline classification per op family over a graph (eval-node list,
    topo, or Executor). Needs hetu_tpu — call sites that only gate/parse
    traces never reach here."""
    from hetu_tpu.graph.node import find_topo_sort
    from hetu_tpu.analysis.abstract import AbstractGraph

    if hasattr(nodes, "subexecutors"):          # an Executor
        subs = nodes.subexecutors
        sub = subs.get(target) or next(iter(subs.values()))
        topo = sub.topo
        training = sub.training
    elif nodes and hasattr(nodes[0], "inputs"):
        topo = find_topo_sort(list(nodes))
    else:
        topo = list(nodes)
    ag = AbstractGraph(topo, target=target).evaluate()

    def meta_of(n):
        return ag.meta.get(id(n))

    # training multiplier: matmul/conv backward re-runs two GEMMs (3x),
    # everything else roughly doubles (fwd + elementwise vjp)
    fams: Dict[str, dict] = {}
    # hetukern fused-optimizer family (docs/KERNELS.md): the apply runs
    # inside the step under its own named_scope, so the measured join works
    # — give it a predicted row too. Adam reads grad+m+v+param and writes
    # param+m+v (~10 flops and 7 f32 transfers per element); SGD reads
    # grad+param, writes param (2 flops, 3 transfers).
    # per-element (flops, f32 transfers) by update rule: Adam reads
    # grad+m+v+param / writes param+m+v; Momentum reads grad+v+param /
    # writes param+v; AdaGrad reads grad+accum+param / writes param+accum;
    # SGD reads grad+param / writes param
    _OPT_COST = {"AdamOptimizer": (10.0, 7.0), "AdamWOptimizer": (10.0, 7.0),
                 "MomentumOptimizer": (4.0, 5.0),
                 "AdaGradOptimizer": (6.0, 5.0),
                 "SGDOptimizer": (2.0, 3.0)}
    for node in topo:
        if not node.is_optimizer:
            continue
        opt_name = type(node.optimizer).__name__
        per_flops, per_moves = _OPT_COST.get(opt_name, (2.0, 3.0))
        elems = 0
        for var in getattr(node, "vars", ()):
            m = meta_of(var)
            shape = (getattr(m, "shape", None)
                     or getattr(var, "shape", None))
            if shape:
                elems += _prod(shape)
        if elems:
            fam = op_family(node.name)      # e.g. Optimizer_AdamOptimizer
            f = fams.setdefault(fam, {"n_ops": 0, "flops": 0.0,
                                      "bytes": 0.0})
            f["n_ops"] += 1
            f["flops"] += per_flops * elems
            f["bytes"] += per_moves * 4.0 * elems
    for node in topo:
        if node.is_placeholder or node.is_dataloader or node.is_optimizer \
                or node.is_gradient:
            continue
        flops, bytes_ = op_cost_estimate(node, meta_of)
        fam = op_family(node.name)
        if training:
            mult = 3.0 if (fam in _MATMUL_FAMILIES
                           or fam in _CONV_FAMILIES) else 2.0
            flops *= mult
            bytes_ *= mult
        f = fams.setdefault(fam, {"n_ops": 0, "flops": 0.0, "bytes": 0.0})
        f["n_ops"] += 1
        f["flops"] += flops
        f["bytes"] += bytes_

    measured: Dict[str, float] = {}
    if attribution is not None:
        for fam, agg in attribution.families().items():
            measured[fam] = agg["wall_us"] / attribution.steps

    # hetutrail cp column: `cp` is a blocking-chain legs dict (profiler
    # cp_legs / trail.step_legs output, typically from the measured run's
    # telemetry dir). Families that execute at the PS boundary get the PS
    # legs' share; everything else runs inside the dispatched program and
    # gets the compute leg's.
    cp_compute = cp_ps = None
    cp_total = sum(cp.values()) if cp else 0.0
    if cp and cp_total > 0:
        cp_compute = cp.get("compute", 0.0) / cp_total
        cp_ps = (cp.get("ps_pull", 0.0) + cp.get("ps_push", 0.0)) / cp_total
    ridge = (peak_tflops * 1e12) / (peak_gbs * 1e9)   # flops per byte
    rows = []
    for fam, f in fams.items():
        inten = f["flops"] / f["bytes"] if f["bytes"] else math.inf
        pred_us = max(f["flops"] / (peak_tflops * 1e12),
                      f["bytes"] / (peak_gbs * 1e9)) * 1e6
        m = measured.get(fam)
        cp_frac = None
        if cp_compute is not None:
            is_ps = any(t in fam.lower()
                        for t in ("embeddinglookup", "embedding_lookup",
                                  "parameterserver", "allreduce", "comm"))
            cp_frac = round(cp_ps if is_ps else cp_compute, 4)
        rows.append(RooflineRow(
            family=fam, n_ops=f["n_ops"], flops=f["flops"],
            bytes=f["bytes"], intensity=inten,
            bound="compute" if inten >= ridge else "memory",
            predicted_us=pred_us, measured_us=m,
            residual=(m / pred_us) if (m and pred_us > 0) else None,
            cp_fraction=cp_frac))
    rows.sort(key=lambda r: -r.predicted_us)
    return rows


def roofline_report(rows: List[RooflineRow],
                    peak_tflops: float = DEFAULT_PEAK_TFLOPS,
                    peak_gbs: float = DEFAULT_PEAK_GBS) -> dict:
    """The machine-readable residual table (``--roofline --json``): op
    family, predicted, measured, residual per row, with the assumed peaks
    the predictions were computed against (an MFU or residual without its
    peak is not a measurement — docs/ROOFLINE.md). This document is the
    calibration input ``hetulint --plan --calibrate`` consumes and the
    thing CI diffs run-over-run."""
    return {
        "kind": "roofline",
        "peak_tflops": peak_tflops,
        "peak_gbs": peak_gbs,
        "rows": [r.__dict__ for r in rows],
    }


def format_roofline(rows: List[RooflineRow],
                    peak_tflops: float = DEFAULT_PEAK_TFLOPS,
                    peak_gbs: float = DEFAULT_PEAK_GBS) -> str:
    ridge = (peak_tflops * 1e12) / (peak_gbs * 1e9)
    lines = [f"# assumed peaks: {peak_tflops:g} TFLOP/s, {peak_gbs:g} GB/s "
             f"-> ridge {ridge:.1f} flop/byte (docs/ROOFLINE.md: "
             "assumptions, not readings)",
             f"{'family':<22} {'ops':>4} {'GFLOP/step':>11} {'MB/step':>9} "
             f"{'flop/B':>8} {'bound':>8} {'pred us':>9} {'meas us':>9} "
             f"{'resid':>6}"
             + ("  cp_frac" if any(r.cp_fraction is not None
                                   for r in rows) else "")]
    for r in rows:
        lines.append(
            f"{r.family[:22]:<22} {r.n_ops:>4} {r.flops / 1e9:>11.3f} "
            f"{r.bytes / 1e6:>9.2f} "
            f"{min(r.intensity, 1e6):>8.1f} {r.bound:>8} "
            f"{r.predicted_us:>9.1f} "
            f"{r.measured_us if r.measured_us is not None else float('nan'):>9.1f} "
            f"{r.residual if r.residual is not None else float('nan'):>6.2f}"
            + (f"  {r.cp_fraction:>7.3f}" if r.cp_fraction is not None
               else ""))
    tf = sum(r.flops for r in rows)
    tb = sum(r.bytes for r in rows)
    tp = max(tf / (peak_tflops * 1e12), tb / (peak_gbs * 1e9)) * 1e6
    lines.append(f"{'TOTAL':<22} {sum(r.n_ops for r in rows):>4} "
                 f"{tf / 1e9:>11.3f} {tb / 1e6:>9.2f} {'':>8} {'':>8} "
                 f"{tp:>9.1f}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# pillar 3 — the perf-regression gate
# ---------------------------------------------------------------------------

def _watch_mod():
    """The hetuwatch module, loadable BOTH ways this file is (the
    ``_trail_mod`` pattern) — watch.py is stdlib-only."""
    global _WATCH_MOD
    if _WATCH_MOD is None:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "watch.py")
        spec = importlib.util.spec_from_file_location("_hetuprof_watch",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_hetuprof_watch"] = mod
        spec.loader.exec_module(mod)
        _WATCH_MOD = mod
    return _WATCH_MOD


def load_summary(path: str) -> Tuple[Dict[str, dict], dict]:
    """Normalize any of the bench artifacts into ``(cells, meta)``:

    - the bench final line (``{"metric", ..., "detail": {cell: {...}}}``),
    - a driver's wrapper around one (``{"rc", "parsed": <line>}``),
    - a cell ledger (``{"cells": {k: {"result": ...}}}``),
    - a bare ``{cell: {...}}`` mapping,
    - or a telemetry DIRECTORY carrying a live hetuwatch residual stream
      (``kind:"watch"`` rows -> a ``plan_watch`` cell whose ``divergence``
      / ``residual_*`` metrics gate lower-is-better — CI fails a PR that
      regresses plan fidelity).

    ``meta['incomplete']`` is True when the artifact itself says the run
    did not finish (rc != 0, ``error``/``incomplete_cells`` markers, or a
    null ``parsed``)."""
    if os.path.isdir(path):
        cells = _watch_mod().summary_cells(path)
        if not cells:
            return {}, {"incomplete": True,
                        "why": f"no hetuwatch rows under {path}"}
        return cells, {"incomplete": False, "why": None}
    with open(path) as f:
        data = json.load(f)
    return normalize_summary(data)


def normalize_summary(data) -> Tuple[Dict[str, dict], dict]:
    meta = {"incomplete": False, "why": None}
    if not isinstance(data, dict):
        return {}, {"incomplete": True, "why": "not a JSON object"}
    if "parsed" in data and ("rc" in data or "cmd" in data):
        if data.get("rc") not in (0, None):
            meta["incomplete"] = True
            meta["why"] = f"driver rc={data.get('rc')}"
        if data["parsed"] is None:
            return {}, {"incomplete": True,
                        "why": meta["why"] or "parsed is null"}
        cells, inner = normalize_summary(data["parsed"])
        inner["incomplete"] = inner["incomplete"] or meta["incomplete"]
        inner["why"] = inner["why"] or meta["why"]
        return cells, inner
    if isinstance(data.get("cells"), dict):       # ledger
        cells = {}
        for k, ent in data["cells"].items():
            if isinstance(ent, dict) and isinstance(ent.get("result"), dict):
                cells[k] = ent["result"]
        return cells, meta
    if isinstance(data.get("detail"), dict):      # bench final line
        cells = {k: v for k, v in data["detail"].items()
                 if isinstance(v, dict) and not k.startswith("_")}
        if data.get("error") or data.get("incomplete_cells"):
            meta["incomplete"] = True
            meta["why"] = data.get("error") or "incomplete_cells present"
        if data.get("value") is None:
            meta["incomplete"] = True
            meta["why"] = meta["why"] or "null headline value"
        return cells, meta
    cells = {k: v for k, v in data.items()
             if isinstance(v, dict) and not k.startswith("_")}
    return cells, meta


_HIGHER_HINTS = ("per_sec", "speedup", "samples_per", "tokens_per")
_LOWER_SUFFIXES = ("_ms", "_mib", "_bytes", "_us", "_s")
# hetuwatch plan-fidelity metrics: a residual ratio of 1.0 is on-plan and
# anything above is drift, so lower always wins (event COUNTS stay
# ungated — an extra recovered event is not a regression)
_LOWER_HINTS = ("residual", "divergence")


def metric_direction(key: str) -> Optional[int]:
    """+1 higher-is-better, -1 lower-is-better, None not gated."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf.endswith("_events") or leaf.endswith("_rows"):
        return None
    if leaf.startswith("mfu") or any(h in leaf for h in _HIGHER_HINTS):
        return 1
    if leaf.startswith("ms_") or leaf.endswith(_LOWER_SUFFIXES) \
            or any(h in leaf for h in _LOWER_HINTS):
        return -1
    return None


def _flatten_cell(cell: dict, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in cell.items():
        if k.startswith("_"):
            continue
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_cell(v, key + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                and math.isfinite(v):
            out[key] = float(v)
    return out


@dataclass
class GateResult:
    status: int
    regressions: list
    improvements: list
    incomplete: list            # baseline-measured cells missing/errored now
    skipped: list               # cells the baseline could not measure
    compared: int
    tolerance_pct: float
    baseline: str = ""
    current: str = ""
    notes: Tuple[str, ...] = ()   # provenance caveats (partial baseline...)

    def as_dict(self) -> dict:
        d = self.__dict__.copy()
        d["verdict"] = self.verdict
        return d

    @property
    def verdict(self) -> str:
        return {GATE_OK: "clean", GATE_REGRESSED: "regressed",
                GATE_INCOMPLETE_CURRENT: "incomplete-current",
                GATE_INCOMPLETE_BASELINE: "incomplete-baseline"}[self.status]

    def report(self) -> str:
        lines = [f"hetuprof gate: {self.verdict} (exit {self.status}) — "
                 f"{self.compared} metric(s) compared at "
                 f"±{self.tolerance_pct:g}% tolerance"]
        for r in self.regressions:
            lines.append(f"  REGRESSED {r['cell']}.{r['metric']}: "
                         f"{r['baseline']:g} -> {r['current']:g} "
                         f"({r['delta_pct']:+.1f}%)")
        for r in self.improvements[:5]:
            lines.append(f"  improved  {r['cell']}.{r['metric']}: "
                         f"{r['baseline']:g} -> {r['current']:g} "
                         f"({r['delta_pct']:+.1f}%)")
        if self.incomplete:
            lines.append("  could NOT measure (baseline had these, current "
                         "run did not): " + ", ".join(self.incomplete))
        if self.skipped:
            lines.append("  baseline has no measurement (skipped): "
                         + ", ".join(self.skipped))
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def gate(baseline_cells: Dict[str, dict], current_cells: Dict[str, dict],
         tolerance_pct: float = 10.0,
         baseline_meta: Optional[dict] = None,
         current_meta: Optional[dict] = None) -> GateResult:
    """Cell-by-cell perf diff with could-not-measure semantics.

    A *regression* needs both sides measured and a directed metric moving
    the wrong way past the tolerance. A baseline cell the current run
    errored on (or never reached) is *incomplete*, never a win or a loss:
    status 2 keeps partial runs from polluting the trajectory — the
    failure mode this gate exists for. A PARTIAL baseline
    (``baseline_meta['incomplete']``) still gates its measured cells,
    flagged in ``notes``; only one with nothing measurable is status 3."""
    notes = []
    if (baseline_meta or {}).get("incomplete"):
        why = (baseline_meta or {}).get("why") or "marked incomplete"
        notes.append(f"baseline run was partial ({why}); gating only its "
                     "measured cells")
    measurable: Dict[str, Dict[str, float]] = {}
    for cell, data in baseline_cells.items():
        if not isinstance(data, dict) or "error" in data:
            continue
        flat = {k: v for k, v in _flatten_cell(data).items()
                if metric_direction(k) is not None}
        if flat:
            measurable[cell] = flat
    if not measurable:
        return GateResult(GATE_INCOMPLETE_BASELINE, [], [], [],
                          sorted(baseline_cells), 0, tolerance_pct,
                          notes=tuple(notes))

    regressions, improvements, incomplete = [], [], []
    compared = 0
    tol = tolerance_pct / 100.0
    for cell, base_flat in sorted(measurable.items()):
        cur = current_cells.get(cell)
        if not isinstance(cur, dict) or "error" in cur:
            incomplete.append(cell)
            continue
        cur_flat = _flatten_cell(cur)
        seen_any = False
        for metric, bval in base_flat.items():
            if metric not in cur_flat:
                continue
            direction = metric_direction(metric)
            cval = cur_flat[metric]
            seen_any = True
            compared += 1
            if bval == 0:
                continue
            delta = (cval - bval) / abs(bval)
            entry = {"cell": cell, "metric": metric, "baseline": bval,
                     "current": cval, "delta_pct": 100.0 * delta}
            if direction * delta < -tol:
                regressions.append(entry)
            elif direction * delta > tol:
                improvements.append(entry)
        if not seen_any:
            incomplete.append(cell)
    skipped = sorted(set(current_cells) - set(measurable))
    if (current_meta or {}).get("incomplete"):
        # the current artifact says it was cut short: any baseline cell it
        # did not reproduce is already in `incomplete` above; make sure a
        # formally-complete-looking diff still cannot claim a clean pass
        if not incomplete and compared == 0:
            incomplete = sorted(measurable)
    if regressions:
        status = GATE_REGRESSED
    elif incomplete:
        status = GATE_INCOMPLETE_CURRENT
    else:
        status = GATE_OK
    return GateResult(status, regressions, improvements, incomplete,
                      skipped, compared, tolerance_pct,
                      notes=tuple(notes))


def gate_files(baseline_path: str, current_path: Optional[str] = None,
               current_data=None, tolerance_pct: float = 10.0) -> GateResult:
    try:
        base_cells, base_meta = load_summary(baseline_path)
    except (OSError, ValueError) as e:
        return GateResult(GATE_INCOMPLETE_BASELINE, [], [], [], [], 0,
                          tolerance_pct, baseline=f"{baseline_path}: {e}")
    if current_data is not None:
        cur_cells, cur_meta = normalize_summary(current_data)
    else:
        try:
            cur_cells, cur_meta = load_summary(current_path)
        except (OSError, ValueError) as e:
            r = GateResult(GATE_INCOMPLETE_CURRENT, [], [],
                           sorted(base_cells), [], 0, tolerance_pct)
            r.current = f"{current_path}: {e}"
            return r
    res = gate(base_cells, cur_cells, tolerance_pct,
               baseline_meta=base_meta, current_meta=cur_meta)
    res.baseline = baseline_path
    res.current = current_path or "<inline>"
    return res


def gate_self_check(out=sys.stdout) -> int:
    """Tier-1-safe smoke: exercises all four gate verdicts on synthetic
    summaries and verifies the exit-code contract. Returns 0 when the
    contract holds (the verify-skill/CI hook)."""
    good = {"detail": {"cell_a": {"samples_per_sec": 100.0, "step_ms": 10.0},
                       "cell_b": {"mfu": 0.4}},
            "value": 100.0}
    slow = {"detail": {"cell_a": {"samples_per_sec": 50.0, "step_ms": 20.0},
                       "cell_b": {"mfu": 0.4}},
            "value": 50.0}
    partial = {"detail": {"cell_a": {"samples_per_sec": 100.0,
                                     "step_ms": 10.0},
                          "cell_b": {"error": "rc=124"}},
               "value": 100.0, "incomplete_cells": ["cell_b"]}
    empty = {"detail": {"cell_a": {"error": "skipped"}}, "value": None}
    cases = [
        ("clean", good, good, GATE_OK),
        ("regressed", good, slow, GATE_REGRESSED),
        ("incomplete-current", good, partial, GATE_INCOMPLETE_CURRENT),
        ("incomplete-baseline", empty, good, GATE_INCOMPLETE_BASELINE),
    ]
    ok = True
    for label, base, cur, want in cases:
        bc, bm = normalize_summary(base)
        cc, cm = normalize_summary(cur)
        got = gate(bc, cc, 10.0, baseline_meta=bm, current_meta=cm).status
        state = "ok" if got == want else f"FAIL (got {got})"
        if got != want:
            ok = False
        print(f"hetuprof --gate --check: {label} -> exit {want} {state}",
              file=out)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="hetuprof",
        description="op-level performance attribution, roofline analysis "
                    "and the perf-regression gate (docs/PROFILING.md)")
    ap.add_argument("target", nargs="?",
                    help="telemetry dir (attribution mode) or "
                         "MODULE:BUILDER (--roofline mode)")
    ap.add_argument("--roofline", action="store_true",
                    help="predicted roofline table for a graph builder "
                         "(hetulint's MODULE:BUILDER convention)")
    ap.add_argument("--gate", nargs="?", const="", metavar="BASELINE",
                    help="diff a bench/telemetry summary against BASELINE; "
                         "exit 0 clean / 1 regressed / 2 incomplete run / "
                         "3 unusable baseline")
    ap.add_argument("--current", metavar="SUMMARY",
                    help="current summary for --gate: a bench artifact, "
                         "or a telemetry dir carrying a hetuwatch "
                         "residual stream (gates plan fidelity — "
                         "hetu_plan_divergence / worst-leg residual)")
    ap.add_argument("--tolerance", type=float, default=10.0, metavar="PCT",
                    help="gate tolerance percent (default 10)")
    ap.add_argument("--check", action="store_true",
                    help="with --gate: self-check the exit-code contract "
                         "(CI smoke, no files needed)")
    ap.add_argument("--trace-dir", help="XLA profiler dir override")
    ap.add_argument("--cp-from", metavar="TEL_DIR",
                    help="with --roofline: telemetry dir whose measured "
                         "critical-path legs fill the cp_frac column "
                         "(hetutrail, docs/OBSERVABILITY.md pillar 5)")
    ap.add_argument("--hlo", help="optimized-HLO text file for the exact "
                                  "instruction->op join")
    ap.add_argument("--steps", type=int, help="steps in the trace window "
                    "(default: count of hetu_step annotations)")
    ap.add_argument("--top", type=int, default=25,
                    help="rows in the attribution table")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--peak-tflops", type=float, default=DEFAULT_PEAK_TFLOPS)
    ap.add_argument("--peak-gbs", type=float, default=DEFAULT_PEAK_GBS)
    args = ap.parse_args(argv)

    if args.gate is not None:
        if args.check:
            return gate_self_check()
        if not args.gate:
            print("hetuprof: --gate needs a BASELINE file (or --check)",
                  file=sys.stderr)
            return GATE_INCOMPLETE_BASELINE
        if not args.current:
            print("hetuprof: --gate needs --current SUMMARY",
                  file=sys.stderr)
            return GATE_INCOMPLETE_CURRENT
        res = gate_files(args.gate, args.current,
                         tolerance_pct=args.tolerance)
        print(json.dumps(res.as_dict(), indent=2) if args.as_json
              else res.report())
        return res.status

    if args.roofline:
        if not args.target:
            print("hetuprof: --roofline needs a MODULE:BUILDER target",
                  file=sys.stderr)
            return 2
        from hetu_tpu.analysis.cli import load_builder
        result = load_builder(args.target)()
        graph = result[0] if (isinstance(result, tuple)
                              and len(result) == 2) else result
        if isinstance(graph, dict):
            graph = [n for nodes in graph.values() for n in nodes]
        elif not isinstance(graph, (list, tuple)):
            graph = [graph]
        attribution = None
        if args.trace_dir:
            events: List[dict] = []
            for p in find_xla_traces(args.trace_dir):
                events.extend(load_trace_events(p))
            op_map = None
            if args.hlo:
                with open(args.hlo) as f:
                    op_map = hlo_op_map(f.read())
            if events:
                attribution = attribute(events, op_map=op_map,
                                        steps=args.steps)
        cp = None
        if args.cp_from:
            means = step_phase_means(read_metrics_records(args.cp_from))
            cp = cp_legs(means) if means else None
        rows = roofline_rows(list(graph), peak_tflops=args.peak_tflops,
                             peak_gbs=args.peak_gbs,
                             attribution=attribution, cp=cp)
        if args.as_json:
            # structured residual table — the hetulint --plan --calibrate
            # input; cost_model.load_calibration also accepts the bare
            # row-list form this replaced
            print(json.dumps(roofline_report(
                rows, args.peak_tflops, args.peak_gbs), indent=2))
        else:
            print(format_roofline(rows, args.peak_tflops, args.peak_gbs))
        return 0

    if not args.target:
        ap.print_usage(sys.stderr)
        return 2
    report, attribution = profile_dir_with_attribution(
        args.target, trace_dir=args.trace_dir, hlo_path=args.hlo,
        steps=args.steps)
    if args.as_json:
        print(json.dumps(report, indent=2))
        return 0
    if report["breakdown"]:
        b = report["breakdown"]
        print(f"per-step breakdown over {report['phase_means_ms']['n_steps']}"
              f" steady-state steps: step {b['step_ms']:.2f} ms = compute "
              f"{b['compute_ms']:.2f} + collectives {b['collective_ms']:.2f}"
              f" + ps-rpc {b['ps_rpc_ms']:.2f} + host {b['host_ms']:.2f}"
              + (f"  (comm fraction {b['comm_fraction']:.1%})"
                 if "comm_fraction" in b else ""))
        if "cp_dominant" in b:
            legs = "  ".join(f"{k}={v:.2f}" for k, v in
                             b["cp_legs_ms"].items())
            print(f"critical path (hetutrail): {legs} ms — dominant "
                  f"{b['cp_dominant']} at {b['cp_fraction']:.1%} of the "
                  "blocking chain")
    if report["memory"]:
        mem = report["memory"]
        parts = [f"{k.replace('hetu_hbm_', '').replace('_bytes', '')} "
                 f"{v / 2**20:.1f} MiB" for k, v in sorted(mem.items())]
        print("HBM (compiled program vs live): " + ", ".join(parts))
    if attribution is not None:
        print(attribution.table(top=args.top))
    for why in report["incomplete"]:
        print(f"# incomplete: {why}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
