"""``hetutop`` — live terminal dashboard over a telemetry directory, plus the
``--check`` schema validator CI uses (exit 0 valid / 1 invalid, mirroring the
``hetulint --json`` pattern).

Reads the per-rank ``metrics-r<N>.jsonl`` files a run writes (see
docs/OBSERVABILITY.md for the record schemas) and renders throughput, step-
time percentiles, MFU against the device's tabled peak (docs/ROOFLINE.md), PS-tier
health and cache hit rate. Stdlib-only and jax-free: it runs on a login node
against a shared filesystem while the job trains.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import math
import os
import sys
import time
from typing import Optional

from . import story as _story      # shared ledger readers (stdlib-only)
from .profiler import attn_flops   # stdlib-only module

# metrics snapshots ride only every Nth step record (plus every "final"
# record) — the per-step cost of percentile math is paid on a cadence
STEP_REQUIRED = ("sub", "step", "step_ms")
WINDOW = 200   # dashboard statistics run over the last N step records


def metrics_files(dir_path: str) -> list[str]:
    return sorted(glob.glob(os.path.join(dir_path, "metrics-r*.jsonl")))


def load_records(path: str, errors: Optional[list] = None,
                 rotated: bool = False) -> list[dict]:
    """One metrics file's records via the shared hetustory reader —
    --check stays strict (every classified line, torn tails included,
    formats into ``errors``); ``rotated=True`` prepends the ``.1`` backup
    so rotation can't hide records from the validator."""
    errs: Optional[list] = [] if errors is not None else None
    reader = _story.read_rows_rotated if rotated else _story.read_rows
    out = [r.rec for r in reader(path, errs)]
    if errors is not None:
        errors.extend(_story.format_error(e) for e in errs)
    return out


# ---------------------------------------------------------------------------
# --check: schema validation
# ---------------------------------------------------------------------------

def check_dir(dir_path: str, out=sys.stdout) -> int:
    """Validate every record in the directory; print a summary of what a
    dashboard would read. Returns a process exit code (0 ok, 1 invalid)."""
    files = metrics_files(dir_path)
    if not files:
        print(f"hetutop --check: no metrics-r*.jsonl under {dir_path}",
              file=out)
        return 1
    errors: list[str] = []
    n_steps = n_events = n_ps = n_scope = 0
    step_ms: list[float] = []
    last_metrics: Optional[dict] = None   # None = no snapshot seen at all
    ps_last: dict = {}
    for path in files:
        for rec in load_records(path, errors, rotated=True):
            kind = rec.get("kind")
            if kind == "step":
                missing = [k for k in STEP_REQUIRED if k not in rec]
                if missing:
                    errors.append(f"{path}: step record missing {missing}")
                    continue
                if "metrics" in rec and not isinstance(rec["metrics"], dict):
                    errors.append(f"{path}: step 'metrics' is not an object")
                    continue
                n_steps += 1
                step_ms.append(float(rec["step_ms"]))
                if isinstance(rec.get("metrics"), dict):
                    last_metrics = rec["metrics"]
            elif kind == "final":
                if not isinstance(rec.get("metrics"), dict):
                    errors.append(f"{path}: final record missing 'metrics'")
                    continue
                last_metrics = rec["metrics"]
            elif kind == "event":
                if "name" not in rec:
                    errors.append(f"{path}: event record missing 'name'")
                    continue
                n_events += 1
            elif kind == "ps_server":
                if "server" not in rec:
                    errors.append(f"{path}: ps_server record missing "
                                  "'server'")
                    continue
                n_ps += 1
                ps_last[rec["server"]] = rec
            elif kind == "scope":
                # hetuscope numeric-health row (cadence steps only)
                missing = [k for k in ("sub", "step") if k not in rec]
                if missing:
                    errors.append(f"{path}: scope record missing {missing}")
                    continue
                n_scope += 1
            elif kind is None:
                errors.append(f"{path}: record missing 'kind'")
    for msg in errors[:20]:
        print(f"hetutop --check: {msg}", file=out)
    if len(errors) > 20:
        print(f"hetutop --check: ... and {len(errors) - 20} more", file=out)
    if n_steps == 0:
        print("hetutop --check: no valid step records", file=out)
        return 1
    if last_metrics is None:
        print("hetutop --check: no metrics snapshot (step-with-metrics or "
              "final record) found", file=out)
        return 1
    # the summary below is the CI-readable proof of what the dashboard
    # reads: step time, recompile count, PS latency + snapshot age
    rec_count = last_metrics.get("hetu_recompiles_total")
    print(f"hetutop --check: {len(files)} rank file(s), {n_steps} step, "
          f"{n_events} event, {n_ps} ps_server, {n_scope} scope record(s); "
          f"step_ms p50={_pctl(step_ms, 50):.3f} "
          f"recompiles={rec_count if rec_count is not None else 'n/a'}",
          file=out)
    for sid in sorted(ps_last):
        r = ps_last[sid]
        print(f"hetutop --check: ps server {sid}: "
              f"updates={r.get('updates')} "
              f"snapshot_age_ms={r.get('snapshot_age_ms')} "
              f"rpc p50={last_metrics.get('hetu_ps_pull_ms_p50', 'n/a')}",
              file=out)
    return 1 if errors else 0


def _pctl(vals: list[float], p: float) -> float:
    if not vals:
        return float("nan")
    s = sorted(vals)
    k = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return s[k]


# ---------------------------------------------------------------------------
# dashboard
# ---------------------------------------------------------------------------

def gather(dir_path: str) -> dict:
    """One dashboard frame's worth of state from the directory (full
    parse — one-shot use: ``--once``, tests). The live loop uses
    :class:`Follower`, which tails incrementally."""
    return _aggregate({p: load_records(p, rotated=True)
                       for p in metrics_files(dir_path)})


class Follower:
    """Incremental reader for live mode: keeps a byte offset and a bounded
    record buffer per file, so each frame parses only appended lines —
    frame cost stays O(new data) instead of growing with run length."""

    # per-file history: enough for the WINDOW step stats plus the
    # interleaved snapshot/event/ps rows that ride between step records
    BUFFER = 4 * WINDOW

    def __init__(self, dir_path: str):
        self.dir = dir_path
        # shared rotation-aware tailer (hetustory): on rotation the old
        # generation's unread tail is drained from the .1 backup instead of
        # dropped, and an existing backup seeds the dashboard's history
        self._follow = _story.LedgerFollower(backlog=True)
        self._recs: dict = {}
        # once-per-run records (run_info/model_info) and slow-cadence rows
        # (ps_server, hetuscope scope) must survive eviction from the
        # bounded buffers
        self._sticky_run_info: dict = {}
        self._sticky_model: dict = {}
        self._sticky_ps: dict = {}
        self._sticky_scope: dict = {}

    def _poll_file(self, path: str):
        buf = self._recs.get(path)
        if buf is None:
            buf = self._recs[path] = collections.deque(
                maxlen=self.BUFFER)
        buf.extend(self._follow.poll(path))
        return buf

    def poll(self) -> dict:
        state = _aggregate({p: self._poll_file(p)
                            for p in metrics_files(self.dir)})
        self._sticky_run_info.update(state["run_info"])
        self._sticky_model.update(state["model"])
        self._sticky_ps.update(state["ps"])
        self._sticky_scope.update(state["scope"])
        state["run_info"] = dict(self._sticky_run_info)
        state["model"] = dict(self._sticky_model)
        state["ps"] = dict(self._sticky_ps)
        state["scope"] = dict(self._sticky_scope)
        return state


def _aggregate(recs_by_file: dict) -> dict:
    state: dict = {"ranks": {}, "events": [], "ps": {}, "run_info": {},
                   "model": {}, "scope": {}}
    for path, recs in recs_by_file.items():
        steps = [r for r in recs if r.get("kind") == "step"
                 and all(k in r for k in STEP_REQUIRED)]
        m = {}
        snaps = []   # (ts, metrics) of every snapshot-bearing record
        for r in recs:
            kind = r.get("kind")
            if kind == "event":
                state["events"].append(r)
            elif kind == "ps_server":
                state["ps"][r.get("server")] = r
            elif kind == "run_info":
                state["run_info"].update(r)
            elif kind == "model_info":
                # model geometry (telemetry.record_model_info) unlocks the
                # analytic attention-inclusive MFU denominator
                state["model"].update(r)
            elif kind == "scope":
                # latest hetuscope numeric-health row per rank
                state["scope"][r.get("rank", 0)] = r
            if kind in ("step", "final") and isinstance(
                    r.get("metrics"), dict):
                m = r["metrics"]   # latest snapshot wins
                if "ts" in r:
                    snaps.append((r["ts"], r["metrics"]))
        if not steps:
            continue
        rank = steps[-1].get("rank", 0)
        window = steps[-WINDOW:]
        t = [r["step_ms"] for r in window]
        span_s = (window[-1]["ts"] - window[0]["ts"]) if len(window) > 1 \
            else 0.0
        ex_rate = None
        if len(snaps) > 1 and snaps[-1][0] > snaps[0][0]:
            ex_rate = ((snaps[-1][1].get("hetu_examples_total", 0)
                        - snaps[0][1].get("hetu_examples_total", 0))
                       / (snaps[-1][0] - snaps[0][0]))
        state["ranks"][rank] = {
            "last_step": window[-1]["step"],
            "sub": window[-1]["sub"],
            "steps_per_s": (len(window) - 1) / span_s if span_s > 0 else None,
            "examples_per_s": ex_rate,
            "p50": _pctl(t, 50), "p90": _pctl(t, 90), "p99": _pctl(t, 99),
            "max": max(t),
            "metrics": m,
            "last_ts": window[-1]["ts"],
        }
    state["events"] = state["events"][-5:]
    return state


def _fmt(v, spec=".1f", na="  n/a") -> str:
    return na if v is None else format(v, spec)


def _defloat(v):
    """A recorded number back as a float — hetuscope serializes non-finite
    values as the strings "NaN"/"Infinity" to keep the JSONL strict JSON;
    float() parses them back. None on anything non-numeric."""
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _finite(v):
    f = _defloat(v)
    return f if f is not None and math.isfinite(f) else None


def _metric_children(m: dict, base: str, suffix: str):
    """Snapshot entries for one metric family: the unlabeled parent
    (``<base><suffix>``) and/or its labeled children
    (``base{k="v"}suffix`` -> child tag ``k=v``)."""
    out = []
    exact = base + suffix
    for k, v in m.items():
        if k == exact:
            out.append(("", v))
        elif k.startswith(base + "{") and k.endswith(suffix):
            labels = k[len(base) + 1:len(k) - len(suffix) - 1]
            out.append((labels.replace('"', ""), v))
    return sorted(out)


def _mfu_pair(m: dict, model: dict, p50_ms, peak_tflops):
    """MFU under BOTH denominators (docs/ROOFLINE.md: 6ND alone overstates
    utilization at long seq): 6ND from the executor's
    ``hetu_flops_per_step_6nd`` gauge; attention-inclusive as 6ND + the
    analytic attention add-on when model geometry is known
    (``telemetry.record_model_info``), else the measured XLA cost-analysis
    flops — which count the score matmuls by construction."""
    if not p50_ms or not peak_tflops:   # unknown device_kind: no MFU
        return None, None
    denom = (p50_ms / 1e3) * peak_tflops * 1e12
    f6 = m.get("hetu_flops_per_step_6nd")
    mfu6 = 100.0 * f6 / denom if f6 else None
    f_attn = None
    if f6 and all(k in model for k in ("n_layers", "d_model", "seq_len")):
        # invert tokens with the SAME N that produced the gauge
        # (hetu_params_total; the executor's count includes PS-resident
        # tables) — a user-supplied model n_params may count differently
        # and would scale the recovered token count by the ratio
        n = m.get("hetu_params_total") or model.get("n_params")
        if n:
            tokens = f6 / (6.0 * n)
            seq = float(model["seq_len"])
            f_attn = f6 + attn_flops(tokens / seq, seq,
                                     model["n_layers"], model["d_model"],
                                     bool(model.get("causal")))
    if f_attn is None:
        f_attn = m.get("hetu_flops_per_step")
    mfu_a = 100.0 * f_attn / denom if f_attn else None
    return mfu6, mfu_a


def render_frame(state: dict) -> str:
    lines = []
    info = state["run_info"]
    dev = info.get("device_kind", "?")
    # the run recorded its peak from profiler.DEVICE_PEAKS; a device_kind
    # that table does not know shows no MFU
    peak = info.get("peak_tflops")
    lines.append(f"hetutop — device {dev}, peak "
                 + (f"{peak:g} TFLOP/s" if peak else "unknown (no MFU)")
                 + " (see docs/ROOFLINE.md)")
    lines.append("rank  sub        step   steps/s    ex/s   p50ms   p90ms"
                 "   p99ms   maxms MFU6nd% MFUatt%  recompiles  anomalies")
    for rank in sorted(state["ranks"]):
        r = state["ranks"][rank]
        m = r["metrics"]
        mfu6, mfu_a = _mfu_pair(m, state.get("model", {}), r["p50"], peak)
        lines.append(
            f"{rank:>4}  {r['sub'][:9]:<9}{r['last_step']:>7}"
            f"{_fmt(r['steps_per_s'], '8.2f'):>9}"
            f"{_fmt(r['examples_per_s'], '8.0f'):>8}"
            f"{r['p50']:>8.2f}{r['p90']:>8.2f}{r['p99']:>8.2f}"
            f"{r['max']:>8.2f}"
            f"{_fmt(mfu6, '7.1f'):>8}"
            f"{_fmt(mfu_a, '7.1f'):>8}"
            f"{m.get('hetu_recompiles_total', 0):>11g}"
            f"{m.get('hetu_anomaly_trips_total', 0):>10g}")
        extras = []
        for base, suffix, label in (
                ("hetu_dataloader_wait_ms", "_p50", "dl wait p50"),
                ("hetu_ps_pull_ms", "_p50", "ps pull p50"),
                ("hetu_ps_push_ms", "_p50", "ps push p50"),
                ("hetu_cache_hit_rate", "", "cache hit"),
                ("hetu_comm_fraction", "", "comm frac"),
                ("hetu_comm_quant_ratio", "", "quant ratio")):
            unit = "" if base.endswith(("rate", "fraction", "ratio")) \
                else "ms"
            for child, v in _metric_children(m, base, suffix):
                tag = f"[{child}]" if child else ""
                extras.append(f"{label}{tag} {v:.3g}{unit}")
        hbm = m.get("hetu_hbm_peak_bytes")
        if hbm:
            live = m.get("hetu_hbm_live_bytes")
            extras.append(f"hbm compiled {hbm / 2**20:.0f}MiB"
                          + (f" live {live / 2**20:.0f}MiB" if live else ""))
        if extras:
            lines.append("      " + "  |  ".join(extras))
    if state.get("scope"):
        # hetuscope numeric health (docs/OBSERVABILITY.md): latest cadence
        # row per rank — global grad norm, worst layer, update ratio,
        # non-finite op count
        lines.append("numeric health (hetuscope):")
        for rank in sorted(state["scope"]):
            s = state["scope"][rank]
            params = s.get("params") or {}
            worst = max(params.items(),
                        key=lambda kv: _finite(kv[1].get("grad_norm"))
                        or 0.0,
                        default=None)
            # _finite filters None, NaN (zero-norm params) and the "NaN"
            # strings a trip row serializes
            ratios = [r for d in params.values()
                      if (r := _finite(d.get("update_ratio"))) is not None]
            ops = s.get("ops") or {}
            nonfin = [k for k, v in ops.items()
                      if (_defloat(v.get("nonfinite")) or 0.0) > 0]
            line = (f"  r{rank} step {s.get('step')}: "
                    f"loss {_fmt(_defloat(s.get('loss')), '.4g', 'n/a')} "
                    f"grad_norm "
                    f"{_fmt(_defloat(s.get('grad_norm')), '.4g', 'n/a')}")
            if worst is not None:
                line += (f"  worst layer {worst[0]} "
                         f"({_finite(worst[1].get('grad_norm')) or 0.0:.3g})")
            if ratios:
                line += f"  upd/param max {max(ratios):.3g}"
            line += (f"  NONFINITE: {', '.join(nonfin[:4])}" if nonfin
                     else "  nonfinite ops: 0")
            lines.append(line)
    # hetukern dispatch panel (docs/KERNELS.md): per-kernel pallas vs
    # fallback vs off tallies from hetu_kernel_dispatch_total — which tier
    # served each op family in the programs now compiled. Absent (no line)
    # when nothing ever dispatched (kernel tier untouched).
    kern: dict = {}
    for rk in state["ranks"].values():
        for child, v in _metric_children(
                rk["metrics"], "hetu_kernel_dispatch_total", ""):
            if not child:
                continue
            labels = dict(p.split("=", 1) for p in child.split(",")
                          if "=" in p)
            name = labels.get("kernel")
            path = labels.get("path")
            if name and path:
                ent = kern.setdefault(name, {})
                ent[path] = ent.get(path, 0) + (_defloat(v) or 0)
    if kern:
        parts = []
        for name in sorted(kern):
            ent = kern[name]
            parts.append(name + " " + "/".join(
                f"{p}:{int(ent[p])}" for p in ("pallas", "forced", "fallback", "off")
                if p in ent))
        lines.append("kernels: " + "  ".join(parts))
    # hetu-elastic membership (docs/FAULT_TOLERANCE.md): current world
    # version, live workers/servers, last resize cost — fed by the
    # ElasticAgent's gauges; absent (no line) for non-elastic runs
    wv = None
    memb = {}
    for rk in state["ranks"].values():
        m = rk["metrics"]
        v = _defloat(m.get("hetu_world_version"))
        if v is None:
            continue
        if wv is None or v > wv:
            wv, memb = v, {}
        if v == wv:
            # ranks at the same world merge per-key maxima: a fresh
            # JOINER reports resizes=0 next to a survivor's true count
            for k in ("hetu_world_workers", "hetu_world_servers",
                      "hetu_resizes_total", "hetu_resize_duration_ms"):
                x = _defloat(m.get(k))
                if x is not None and (memb.get(k) is None
                                      or x > memb[k]):
                    memb[k] = x
    if wv is not None:
        live_ranks = len(state["ranks"])
        line = (f"membership: world v{int(wv)}  "
                f"workers {_fmt(memb.get('hetu_world_workers'), '.0f')}"
                f" ({live_ranks} reporting)  "
                f"servers {_fmt(memb.get('hetu_world_servers'), '.0f')}  "
                f"resizes {_fmt(memb.get('hetu_resizes_total'), '.0f')}")
        if memb.get("hetu_resize_duration_ms") is not None:
            line += (f"  last resize "
                     f"{memb['hetu_resize_duration_ms']:.0f}ms")
        lines.append(line)
    # hetutrail (docs/OBSERVABILITY.md pillar 5): per-step blocking chain
    # from the hetu_critical_path_ms{leg=...} gauges (latest-reporting
    # rank) + cross-rank p50 skew straight from the rank table. Absent (no
    # line) when the executor never exported critical-path gauges.
    cp_rank = None
    for rk in sorted(state["ranks"].values(),
                     key=lambda r: r.get("last_ts") or 0):
        if any(k.startswith("hetu_critical_path_ms")
               for k in rk["metrics"]):
            cp_rank = rk
    if cp_rank is not None:
        m = cp_rank["metrics"]
        legs = {child.split("=", 1)[1]: _defloat(v) or 0.0
                for child, v in _metric_children(
                    m, "hetu_critical_path_ms", "") if "=" in child}
        parts = [f"{leg} {legs[leg]:.2f}" for leg in
                 ("feed", "ps_pull", "compute", "ps_push", "poststep")
                 if leg in legs]
        line = "trail: cp(ms) " + " | ".join(parts)
        frac = _defloat(m.get("hetu_cp_fraction"))
        if legs and frac is not None:
            line += (f"  dominant {max(legs, key=legs.get)} "
                     f"{100.0 * frac:.0f}%")
        if len(state["ranks"]) > 1:
            p50s = {r: rk["p50"] for r, rk in state["ranks"].items()}
            slowest = max(p50s, key=p50s.get)
            line += (f"  skew(p50) "
                     f"{max(p50s.values()) - min(p50s.values()):.2f}ms "
                     f"slowest r{slowest}")
        stragglers = 0.0
        for rk in state["ranks"].values():
            for child, v in _metric_children(
                    rk["metrics"], "hetu_events_total", ""):
                if child == "event=straggler":
                    stragglers += _defloat(v) or 0.0
        if stragglers:
            line += f"  stragglers {int(stragglers)}"
        lines.append(line)
    # hetuwatch plan-divergence sentinel (docs/OBSERVABILITY.md pillar 6):
    # per-leg measured/predicted residual EWMAs + the worst-leg divergence
    # gauge (1.0 = on plan) from the latest-reporting watched rank, plus
    # any latched divergence / SLO-breach event counts. Absent (no line)
    # when no rank armed the watch.
    w_rank = None
    for rk in sorted(state["ranks"].values(),
                     key=lambda r: r.get("last_ts") or 0):
        if any(k.startswith("hetu_plan_residual") for k in rk["metrics"]):
            w_rank = rk
    if w_rank is not None:
        m = w_rank["metrics"]
        resid = {child.split("=", 1)[1]: _defloat(v) or 0.0
                 for child, v in _metric_children(
                     m, "hetu_plan_residual", "") if "=" in child}
        parts = [f"{leg} {resid[leg]:.2f}x" for leg in
                 ("feed", "ps_pull", "compute", "ps_push", "poststep")
                 if leg in resid]
        line = "watch: residual " + " | ".join(parts)
        div = _defloat(m.get("hetu_plan_divergence"))
        if div is not None:
            line += f"  divergence {div:.2f}"
            if div > 1.5:
                line += " DIVERGED"
        div_evs = slo_evs = 0.0
        for rk in state["ranks"].values():
            for child, v in _metric_children(
                    rk["metrics"], "hetu_events_total", ""):
                if child == "event=plan_divergence":
                    div_evs += _defloat(v) or 0.0
                elif child == "event=slo_breach":
                    slo_evs += _defloat(v) or 0.0
        if div_evs:
            line += f"  divergence events {int(div_evs)}"
        if slo_evs:
            line += f"  slo breaches {int(slo_evs)}"
        lines.append(line)
    # hetupilot self-tuning controller (docs/FAULT_TOLERANCE.md
    # "Self-tuning with guardrails"): actuation/rollback era counts plus
    # whether a verdict is still measuring, from the controller's gauges.
    # Absent (no line) when no rank armed the pilot.
    p_state = p_act = p_rb = None
    for rk in state["ranks"].values():
        m = rk["metrics"]
        if "hetu_pilot_state" not in m:
            continue
        p_state = max(p_state or 0.0, _defloat(m.get("hetu_pilot_state"))
                      or 0.0)
        p_act = (p_act or 0.0) + (_defloat(
            m.get("hetu_pilot_actuations_total")) or 0.0)
        p_rb = (p_rb or 0.0) + (_defloat(
            m.get("hetu_pilot_rollbacks_total")) or 0.0)
    if p_state is not None:
        line = (f"pilot: actuations {int(p_act or 0)}  "
                f"rollbacks {int(p_rb or 0)}  "
                + ("MEASURING" if p_state >= 1.0 else "idle"))
        lines.append(line)
    # hetuchaos transport hardening (docs/FAULT_TOLERANCE.md "Chaos
    # testing & transport hardening"): retry/timeout/CRC health summed
    # across ranks, plus any injected-fault count when a chaos schedule
    # is armed (test runs only). Absent (no line) while every counter is
    # zero — the healthy-wire steady state.
    ch = {k: 0.0 for k in ("hetu_rpc_timeouts_total", "hetu_rpc_backoff_ms",
                           "hetu_crc_rejects_total",
                           "hetu_chaos_faults_total")}
    for rk in state["ranks"].values():
        m = rk["metrics"]
        for k in ch:
            ch[k] += _defloat(m.get(k)) or 0.0
    if any(ch.values()):
        line = (f"chaos: timeouts {int(ch['hetu_rpc_timeouts_total'])}  "
                f"backoff {ch['hetu_rpc_backoff_ms']:.0f}ms  "
                f"crc rejects {int(ch['hetu_crc_rejects_total'])}")
        if ch["hetu_chaos_faults_total"]:
            line += (f"  injected faults "
                     f"{int(ch['hetu_chaos_faults_total'])} (chaos armed)")
        lines.append(line)
    # hetusave coordinated job snapshots (docs/FAULT_TOLERANCE.md
    # "Coordinated job snapshots"): newest committed epoch + the wall
    # cost of taking it, from take_job_snapshot's gauges. Absent (no
    # line) for jobs that never committed a coordinated epoch.
    ep, ep_ms = None, None
    for rk in state["ranks"].values():
        m = rk["metrics"]
        v = _defloat(m.get("hetu_job_epoch"))
        if v is not None and (ep is None or v > ep):
            ep = v
            ep_ms = _defloat(m.get("hetu_snapshot_last_ms"))
    if ep is not None:
        line = f"snapshot: job epoch {int(ep)} committed"
        if ep_ms is not None:
            line += f"  last stall {ep_ms:.0f}ms"
        lines.append(line)
    if state["ps"]:
        lines.append("PS servers:")
        for sid in sorted(state["ps"]):
            r = state["ps"][sid]
            lines.append(
                f"  s{sid}: updates={r.get('updates')} "
                f"reqs={r.get('requests')} "
                f"apply_avg_ms={_fmt(r.get('apply_ms_avg'), '.3f')} "
                f"snap v{r.get('snapshot_version')} "
                f"age={_fmt(r.get('snapshot_age_ms'), '.0f')}ms "
                f"dedup_clients={r.get('dedup_clients')}")
        # hetuq wire accounting (docs/COMM_QUANT.md): worker-side raw-vs-
        # wire byte counters over every quantizable value payload — with
        # quantization off raw == wire and the ratio reads 1.00x
        qraw = qwire = 0.0
        for rk in state["ranks"].values():
            m = rk["metrics"]
            qraw += _defloat(m.get("hetu_comm_quant_raw_bytes_total")) or 0.0
            qwire += _defloat(m.get("hetu_comm_quant_wire_bytes_total")) \
                or 0.0
        if qwire:
            lines.append(
                f"  comm quant: raw {qraw / 2**20:.1f}MiB -> wire "
                f"{qwire / 2**20:.1f}MiB  ratio {qraw / qwire:.2f}x")
    if state["events"]:
        lines.append("recent events:")
        for e in state["events"]:
            fields = {k: v for k, v in e.items()
                      if k not in ("kind", "name", "ts", "rank", "pid")}
            lines.append(f"  [{time.strftime('%H:%M:%S', time.localtime(e.get('ts', 0)))}] "
                         f"r{e.get('rank', '?')} {e.get('name')} {fields}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="hetutop",
        description="live dashboard / schema check over a hetu_tpu "
                    "telemetry directory")
    ap.add_argument("dir", help="telemetry directory (HETU_TELEMETRY_DIR)")
    ap.add_argument("--check", action="store_true",
                    help="validate the JSONL schema and exit 0/1 (CI mode)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit (no screen clearing)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds in live mode (default 2)")
    args = ap.parse_args(argv)
    if args.check:
        return check_dir(args.dir)
    if not metrics_files(args.dir):
        print(f"hetutop: no metrics-r*.jsonl under {args.dir} (yet)",
              file=sys.stderr)
    if args.once:
        print(render_frame(gather(args.dir)))
        return 0
    follower = Follower(args.dir)   # incremental tail: O(new data)/frame
    try:
        while True:
            frame = render_frame(follower.poll())
            # ANSI clear + home; fall back gracefully on dumb terminals
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
