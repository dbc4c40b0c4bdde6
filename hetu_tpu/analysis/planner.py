"""hetuplan: the Tier C auto-parallelism planner pass (docs/ANALYSIS.md
"Tier C: planning").

Tier A lints a declared layout; this pass *chooses* one. Over the same
``GraphAnalyzer`` op graph and abstract shapes, :func:`plan_graph` prices
layout candidates with :mod:`cost_model` and returns a :class:`Plan`:

- **Per-parameter comm mode** — AllReduce vs PS by density × size, the
  reference's hand-tuned Hybrid heuristic automated (Automatic
  Cross-Replica Sharding, PAPERS.md arXiv:2004.13336, mechanizes exactly
  this kind of weight-update placement from a static cost model). Sparse
  (lookup-accessed) params prefer PS unless AllReduce is *meaningfully*
  cheaper: at equal wire cost the sparse route still avoids materializing
  the dense ``(vocab, dim)`` table gradient on-device (the 7.7x/19.7x
  dense-vs-rows cost PR 12 measured) and keeps the server-side update
  sparse.
- **Per-tensor comm quantization** — on/off from the analytic wire ratios
  (EQuARX, arXiv:2506.17615; PR 8's validated formulas): dense AllReduce
  tensors follow the hetuq size exemption (small/sensitive params stay
  exact), PS sparse rows quantize whenever the row-wise ``kQI8`` ratio
  clears the threshold (one f32 scale per row — worth it from tiny row
  widths up, independent of table size).
- **Mesh-shape search** — every (dp, tp, pp) factorization of the device
  budget the graph can actually realize (tp needs dispatch markers, pp
  needs pipeline ops/gpipe), each checked for HBM feasibility via the AOT
  memory-gate formula. An infeasible candidate first escalates to ZeRO-1
  (slots shard over dp), then remat, then PS-offload of sparse tables; a
  candidate that still fails the gate is NEVER the chosen plan.

Surfaces: ``hetulint --plan [--devices N] [--calibrate TEL_DIR] [--json]``
(CLI, findings are note-severity and suppressible like every pass),
``Plan.apply(config)`` / ``HetuConfig(plan="auto")`` (executor adoption at
build).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .findings import Finding, ERROR, WARN, NOTE
from .cost_model import (
    Calibration, CostModel, CostModelConfig, load_calibration,
    pipeline_bubble, ps_dense_bytes, ps_sparse_bytes, ring_allreduce_bytes,
)

# AllReduce must beat PS by this factor to claim a SPARSE param: at parity
# the sparse route wins on the costs the wire model can't see (no dense
# table-grad materialization, sparse server-side update)
SPARSE_AR_MARGIN = 1.2
# minimum analytic wire ratio before quantization is worth switching on
QUANT_RATIO_MIN = 1.2


@dataclass
class ParamDecision:
    """One parameter's planned communication treatment."""

    name: str
    size_elems: int
    nbytes: int
    dim: int
    sparse: bool
    density: float
    touched_rows: float
    mode: str                     # "AllReduce" | "PS" | "local"
    quant: Optional[str] = None   # None | "int8" | "kQI8"
    wire_ratio: float = 1.0
    reason: str = ""
    node: object = None

    def as_dict(self) -> dict:
        return {"param": self.name, "size": self.size_elems,
                "sparse": self.sparse,
                "density": round(self.density, 4) if self.sparse else None,
                "mode": self.mode, "quant": self.quant,
                "wire_ratio": round(self.wire_ratio, 3),
                "reason": self.reason}


@dataclass
class MeshCandidate:
    """One evaluated (dp, tp, pp) point of the search."""

    dp: int
    tp: int
    pp: int
    feasible: bool = False
    zero1: bool = False
    remat: bool = False
    ps_offload: bool = False
    predicted_step_ms: Optional[float] = None
    peak_gib: Optional[float] = None
    why: str = ""

    def as_dict(self) -> dict:
        return {"dp": self.dp, "tp": self.tp, "pp": self.pp,
                "feasible": self.feasible, "zero1": self.zero1,
                "remat": self.remat, "ps_offload": self.ps_offload,
                "predicted_step_ms": (round(self.predicted_step_ms, 4)
                                      if self.predicted_step_ms is not None
                                      else None),
                "peak_gib": (round(self.peak_gib, 3)
                             if self.peak_gib is not None else None),
                "why": self.why}


@dataclass
class Plan:
    """The planner's verdict: a full layout choice with priced rationale.

    ``mesh`` is ``None`` when NO candidate passed the HBM gate — an
    infeasible layout is never emitted as the choice (the gate's whole
    point). ``zero1``/``remat`` are advisory for the Op-graph executor
    (which has no in-graph ZeRO-1) and directly consumable by the
    functional models' ``zero1=``/``remat=`` knobs.
    """

    devices: int
    mesh: Optional[Dict[str, int]]          # {"dp", "tp", "pp"} | None
    comm_mode: Optional[str]                # None/AllReduce/PS/Hybrid
    comm_quant: str                         # "off" | "int8"
    zero1: bool
    remat: bool
    predicted_step_ms: Optional[float]
    breakdown: Dict[str, float]
    memory: Dict[str, float]
    params: List[ParamDecision]
    candidates: List[MeshCandidate]
    calibration: Optional[Calibration] = None
    anchor: object = field(default=None, repr=False)

    # ------------------------------------------------------------------
    def as_dict(self) -> dict:
        return {
            "devices": self.devices,
            "mesh": dict(self.mesh) if self.mesh else None,
            "comm_mode": self.comm_mode,
            "comm_quant": self.comm_quant,
            "zero1": self.zero1,
            "remat": self.remat,
            "predicted_step_ms": (round(self.predicted_step_ms, 4)
                                  if self.predicted_step_ms is not None
                                  else None),
            "breakdown": {k: round(v, 4) for k, v in self.breakdown.items()},
            "memory": {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in self.memory.items()},
            "params": [d.as_dict() for d in self.params],
            "candidates": [c.as_dict() for c in self.candidates],
            "calibration": (self.calibration.as_dict()
                            if self.calibration else None),
        }

    def summary(self) -> str:
        if self.mesh is None:
            return ("plan: NO feasible layout for the device budget "
                    f"({self.devices} device(s)) — every mesh candidate "
                    "fails the HBM gate even with ZeRO-1/remat")
        m = self.mesh
        lines = [
            f"plan: dp{m['dp']}/tp{m['tp']}/pp{m['pp']} over "
            f"{self.devices} device(s), comm_mode="
            f"{self.comm_mode or 'none'}, comm_quant={self.comm_quant}"
            + (", zero1" if self.zero1 else "")
            + (", remat" if self.remat else ""),
            f"predicted step {self.predicted_step_ms:.3f} ms ("
            + ", ".join(f"{k} {v:.3f}" for k, v in self.breakdown.items())
            + ")",
            f"projected HBM {self.memory['peak_gib']:.3f} GiB / "
            f"{self.memory['budget_gib']:g} GiB budget",
        ]
        for d in self.params:
            lines.append(f"  {d.name}: {d.mode}"
                         + (f" + {d.quant}" if d.quant else "")
                         + f" — {d.reason}")
        return "\n".join(lines)

    # ------------------------------------------------------------------
    def findings(self, config=None) -> List[Finding]:
        """The plan as structured findings — note severity, per-decision
        rationale, suppressible like every other pass (``plan-*`` ids);
        ``plan-infeasible`` is the one error. ``config`` (the running /
        declared config) adds ``plan-divergence`` warnings where it
        contradicts the choice."""
        out: List[Finding] = []
        if self.mesh is None:
            out.append(Finding.at(
                self.anchor, "plan-infeasible", ERROR,
                f"no (dp, tp, pp) factorization of {self.devices} device(s) "
                f"fits the {self.memory.get('budget_gib', 0):g} GiB HBM "
                "budget, even with ZeRO-1 + remat + PS offload — shrink the "
                "model, raise the budget, or add devices "
                f"(best candidate peaked at "
                f"{self.memory.get('peak_gib', 0):.2f} GiB)", "planner"))
        else:
            m = self.mesh
            rejected = sum(1 for c in self.candidates if not c.feasible)
            out.append(Finding.at(
                self.anchor, "plan-mesh", NOTE,
                f"chose dp{m['dp']}/tp{m['tp']}/pp{m['pp']} of "
                f"{len(self.candidates)} candidate(s) ({rejected} HBM-"
                f"rejected): predicted step {self.predicted_step_ms:.3f} ms, "
                f"projected HBM {self.memory['peak_gib']:.3f}/"
                f"{self.memory['budget_gib']:g} GiB", "planner"))
            if self.zero1 or self.remat:
                knobs = " + ".join(k for k, on in
                                   (("ZeRO-1", self.zero1),
                                    ("remat", self.remat)) if on)
                out.append(Finding.at(
                    self.anchor, "plan-memory", NOTE,
                    f"{knobs} adopted: the plain layout overflows the HBM "
                    f"gate; with it the candidate fits at "
                    f"{self.memory['peak_gib']:.3f} GiB", "planner"))
        for d in self.params:
            out.append(Finding.at(
                d.node, "plan-comm-mode", NOTE,
                f"{d.mode}" + (f" + {d.quant}" if d.quant else "")
                + f": {d.reason}", "planner"))
        quantized = [d for d in self.params if d.quant]
        if quantized:
            raw = wire = 0.0
            for d in quantized:
                if d.mode == "PS" and d.sparse:
                    b = ps_sparse_bytes(d.touched_rows, d.dim, quant=d.quant)
                elif d.mode == "PS":
                    b = ps_dense_bytes(d.size_elems, quant=d.quant)
                else:
                    b = ring_allreduce_bytes(d.size_elems,
                                             max(2, self.mesh["dp"])
                                             if self.mesh else 2,
                                             quant=d.quant)
                raw += b["raw"]
                wire += b["wire"]
            out.append(Finding.at(
                self.anchor, "plan-comm-quant", NOTE,
                f"{len(quantized)} tensor(s) quantized: analytic wire "
                f"{raw / 1e3:.1f} KB -> {wire / 1e3:.1f} KB per step "
                f"({raw / wire if wire else 1:.2f}x)", "planner"))
        out.extend(self.divergence_findings(config))
        return out

    def divergence_findings(self, config=None) -> List[Finding]:
        """``plan-divergence`` warnings: the running/declared config
        contradicts the planner's choice (a hand-picked layout the cost
        model disagrees with deserves a human look, not silence)."""
        out: List[Finding] = []
        if config is None:
            return out
        declared = getattr(config, "comm_mode", None)
        if declared is not None and self.comm_mode is not None \
                and declared != self.comm_mode:
            out.append(Finding.at(
                self.anchor, "plan-divergence", WARN,
                f"running config declares comm_mode={declared!r} but the "
                f"cost model chose {self.comm_mode!r} for this graph — "
                "hand-picked layout contradicts the planner; re-examine or "
                "suppress", "planner"))
        pol = getattr(config, "comm_quant_policy", None)
        declared_q = getattr(pol, "mode", None) if pol is not None else None
        if declared_q is not None and declared_q != "off" \
                and self.comm_quant == "off":
            out.append(Finding.at(
                self.anchor, "plan-divergence", WARN,
                f"running config arms comm_quant={declared_q!r} but the "
                "planner found no tensor worth quantizing (all below the "
                "exemption threshold or no comm legs)", "planner"))
        return out

    # ------------------------------------------------------------------
    def apply(self, config):
        """Adopt this plan on a ``HetuConfig``/``AnalysisConfig``: fills
        comm_mode and the comm_quant policy where the config left them
        unset (an explicitly declared value is never overridden — hetulint
        reports the divergence instead), re-deduces the mesh under the new
        comm_mode, and records zero1/remat advisories. Returns ``config``.
        """
        config.plan_adopted = self
        if getattr(config, "comm_mode", None) is None \
                and self.comm_mode is not None:
            if getattr(config, "anomaly_guard", False) \
                    and self.comm_mode in ("PS", "Hybrid"):
                raise ValueError(
                    "plan adoption chose comm_mode "
                    f"{self.comm_mode!r} but anomaly_guard is armed — PS-"
                    "hosted updates cannot be rolled back; disable the "
                    "guard or pass comm_mode explicitly")
            config.comm_mode = self.comm_mode
            # HetuConfig deduced its mesh before the plan existed (under
            # comm_mode=None); re-deduce now that a strategy is set
            if getattr(config, "mesh", None) is None \
                    and hasattr(config, "_deduce_mesh"):
                config.mesh = config._deduce_mesh()
        pol = getattr(config, "comm_quant_policy", None)
        if self.comm_quant != "off" \
                and not getattr(config, "gpipe", False) \
                and (pol is None or not getattr(pol, "active", False)):
            from ..comm_quant import resolve_policy
            config.comm_quant_policy = resolve_policy(self.comm_quant)
            config.comm_quant = self.comm_quant
        # advisory for the functional-model knobs (transformer/pipeline
        # zero1=, TransformerConfig.remat) — the Op-graph executor carries
        # them as metadata only
        config.plan_zero1 = self.zero1
        config.plan_remat = self.remat
        return config

    def device_group(self, device: str = "tpu"):
        """The chosen (dp, tp) mesh as a DeviceGroup literal for
        ``Executor(ctx=...)`` — ``context.mesh_device_group``'s tuple
        syntax carries the tp axis. None when no feasible layout exists
        or the layout is single-device."""
        if self.mesh is None or self.mesh["dp"] * self.mesh["tp"] <= 1:
            return None
        from ..context import mesh_device_group
        return mesh_device_group(self.mesh["dp"], self.mesh["tp"],
                                 device=device)


# ---------------------------------------------------------------------------
# decision rules
# ---------------------------------------------------------------------------

def decide_params(model: CostModel, dp: int,
                  ps_offload: bool = False) -> List[ParamDecision]:
    """Per-parameter comm-mode + quantization assignment at a given dp.

    dp == 1: no replication, nothing to synchronize — every param is
    ``local`` (unless ``ps_offload`` pushes sparse tables server-side for
    HBM). dp > 1: dense params price ring-AllReduce vs PS dense push/pull
    (AllReduce wins on the fabric); sparse params price PS row traffic vs
    dense-ifying the table grad for AllReduce — PS keeps the param unless
    AllReduce is ≥``SPARSE_AR_MARGIN``× cheaper, because the wire model
    undercounts the dense route (table-grad materialization, dense update).
    """
    cmc = model.cmc
    out: List[ParamDecision] = []
    for p in model.params:
        quant = None
        ratio = 1.0
        if p.forced_ps:
            mode = "PS"
            reason = ("explicit PS push in the graph pins this param to "
                      "the server (the rows route) — a layout choice "
                      "cannot remove a graph op")
            if p.sparse:
                qs = ps_sparse_bytes(p.touched_rows, p.dim, quant="kQI8")
                if qs["ratio"] >= QUANT_RATIO_MIN:
                    quant, ratio = "kQI8", qs["ratio"]
            elif p.size >= cmc.quant_min_size:
                qd = ps_dense_bytes(p.size, quant="kQI8",
                                    block=cmc.quant_block)
                if qd["ratio"] >= QUANT_RATIO_MIN:
                    quant, ratio = "kQI8", qd["ratio"]
        elif dp <= 1 and not (ps_offload and p.sparse):
            mode = "local"
            reason = "single replica: no gradient synchronization needed"
        elif p.sparse:
            ps = ps_sparse_bytes(p.touched_rows, p.dim, quant=None)
            ar = ring_allreduce_bytes(p.size, max(2, dp))
            # the AllReduce route must also build + move the dense table
            # grad through HBM (3 passes over table bytes: zeros, scatter,
            # read) — the PR-12 measured cost the wire bytes don't show
            ps_ms = (ps["wire"] * max(1, dp)
                     / (cmc.ps_servers * cmc.ps_gbs * 1e9) * 1e3)
            ar_ms = (ar["wire"] / (cmc.net_gbs * 1e9) * 1e3
                     + 3.0 * p.nbytes / (cmc.peak_gbs * 1e9) * 1e3)
            # ps_offload overrides the wire comparison: the table must
            # leave the device for the candidate to fit the HBM gate
            if not ps_offload and dp > 1 \
                    and ar_ms * SPARSE_AR_MARGIN < ps_ms:
                mode = "AllReduce"
                reason = (f"density {p.density:.2f} high enough that a "
                          f"dense all-reduce ({ar_ms:.4f} ms) beats PS row "
                          f"traffic ({ps_ms:.4f} ms) by >"
                          f"{SPARSE_AR_MARGIN}x")
            else:
                mode = "PS"
                qs = ps_sparse_bytes(p.touched_rows, p.dim, quant="kQI8")
                if qs["ratio"] >= QUANT_RATIO_MIN:
                    quant, ratio = "kQI8", qs["ratio"]
                if ps_offload:
                    reason = ("sparse table offloaded to PS for HBM "
                              "headroom (the layout overflows the gate "
                              "with it device-resident)")
                elif dp > 1:
                    reason = (
                        f"sparse table, density {p.density:.2f} "
                        f"(~{p.touched_rows:.0f}/{p.vocab} rows/step): "
                        f"PS moves {ps['wire'] / 1e3:.1f} KB of rows vs "
                        f"{ar['wire'] / 1e3:.1f} KB dense all-reduce + "
                        "a table-shaped grad materialization")
                else:
                    reason = "sparse table offloaded to PS for HBM headroom"
        else:
            ar = ring_allreduce_bytes(p.size, dp)
            psd = ps_dense_bytes(p.size)
            ar_ms = ar["wire"] / (cmc.net_gbs * 1e9) * 1e3
            ps_ms = (psd["wire"] * dp
                     / (cmc.ps_servers * cmc.ps_gbs * 1e9) * 1e3)
            if ps_ms < ar_ms:
                mode = "PS"
                reason = (f"dense but PS cheaper here: {ps_ms:.4f} ms vs "
                          f"ring {ar_ms:.4f} ms")
                qd = ps_dense_bytes(p.size, quant="kQI8",
                                    block=cmc.quant_block)
                if p.size >= cmc.quant_min_size \
                        and qd["ratio"] >= QUANT_RATIO_MIN:
                    quant, ratio = "kQI8", qd["ratio"]
            else:
                mode = "AllReduce"
                reason = (f"dense grad: ring all-reduce "
                          f"{ar['wire'] / 1e3:.1f} KB ({ar_ms:.4f} ms) vs "
                          f"PS {psd['wire'] * dp / 1e3:.1f} KB "
                          f"({ps_ms:.4f} ms)")
                qa = ring_allreduce_bytes(p.size, dp, quant="int8",
                                          block=cmc.quant_block)
                if p.tp_sharded:
                    # the executor exempts tp-sharded params from hetuq
                    # (their sync is not a pure-DP all-reduce) — mirror it
                    reason += "; quant off (tp-sharded, hetuq-exempt)"
                elif p.size >= cmc.quant_min_size \
                        and qa["ratio"] >= QUANT_RATIO_MIN:
                    quant, ratio = "int8", qa["ratio"]
                elif p.size < cmc.quant_min_size:
                    reason += (f"; quant off ({p.size} elems below the "
                               f"{cmc.quant_min_size}-elem exemption)")
        out.append(ParamDecision(
            name=p.name, size_elems=p.size, nbytes=p.nbytes, dim=p.dim,
            sparse=p.sparse, density=p.density,
            touched_rows=p.touched_rows, mode=mode, quant=quant,
            wire_ratio=ratio, reason=reason, node=p.node))
    return out


def _mesh_candidates(devices: int, tp_able: bool, pp_able: bool):
    """Every (dp, tp, pp) factorization of the device budget the graph
    can realize. tp needs dispatch markers; pp needs pipeline structure."""
    def divisors(n):
        return [d for d in range(1, n + 1) if n % d == 0]

    out = []
    for tp in (divisors(devices) if tp_able else [1]):
        for pp in (divisors(devices // tp) if pp_able else [1]):
            if devices % (tp * pp):
                continue
            dp = devices // (tp * pp)
            out.append((dp, tp, pp))
    return sorted(set(out))


def evaluate_candidate(model: CostModel, dp: int, tp: int, pp: int,
                       microbatches: int) -> tuple:
    """Price one mesh point, escalating through the memory fallbacks.

    Returns ``(MeshCandidate, decisions, memory_dict)``. Escalation
    order when the AOT-gate formula projects an overflow: ZeRO-1 (slots
    shard over dp), then remat (saved activations scaled by
    ``remat_factor``), then PS-offload of sparse tables. A candidate
    that still overflows is marked infeasible and can never be chosen.
    """
    decisions = decide_params(model, dp)
    ps_ids = frozenset(id(d.node) for d in decisions if d.mode == "PS")
    zero1 = remat = ps_off = False
    has_slots = any(p.slot_factor for p in model.params)
    while True:
        mem = model.memory(dp, tp, pp, ps_resident=ps_ids,
                           zero1=zero1, remat=remat)
        if mem["feasible"]:
            break
        if not zero1 and dp > 1 and has_slots:
            zero1 = True
            continue
        if not remat and model.training:
            remat = True
            continue
        if not ps_off and any(p.sparse for p in model.params) \
                and not all(d.mode == "PS" for d in decisions
                            if d.sparse):
            ps_off = True
            decisions = decide_params(model, dp, ps_offload=True)
            ps_ids = frozenset(id(d.node) for d in decisions
                               if d.mode == "PS")
            continue
        cand = MeshCandidate(
            dp=dp, tp=tp, pp=pp, feasible=False, zero1=zero1,
            remat=remat, ps_offload=ps_off, peak_gib=mem["peak_gib"],
            why=(f"HBM gate: {mem['peak_gib']:.2f} GiB > "
                 f"{mem['budget_gib']:g} GiB budget even with "
                 "ZeRO-1/remat/PS-offload"))
        return cand, decisions, mem
    bubble = pipeline_bubble(pp, microbatches)
    compute = model.compute_ms(dp, tp, remat=remat) / max(1, pp)
    if bubble:
        compute /= (1.0 - bubble)
    ar_ms = model.allreduce_ms(decisions, dp)
    ps_ms = model.ps_ms(decisions, dp)
    host = model.host_ms()
    step = compute + ar_ms + ps_ms + host
    cand = MeshCandidate(
        dp=dp, tp=tp, pp=pp, feasible=True, zero1=zero1, remat=remat,
        ps_offload=ps_off, predicted_step_ms=step,
        peak_gib=mem["peak_gib"], why="")
    breakdown = {"compute_ms": compute, "allreduce_ms": ar_ms,
                 "ps_ms": ps_ms, "host_ms": host,
                 "bubble_frac": bubble}
    return cand, decisions, {"mem": mem, "breakdown": breakdown}


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------

def plan_graph(graph, config=None, devices: Optional[int] = None,
               calibrate=None, cost_config: Optional[CostModelConfig] = None,
               feed_meta: Optional[dict] = None,
               target: Optional[str] = None) -> Plan:
    """Plan a layout for ``graph`` (an Op, list, or ``{target: [ops]}``
    dict — the Executor eval spec).

    ``devices``: the device budget to lay out over (default: the local
    jax device count). ``calibrate``: a telemetry dir / roofline-JSON
    path (str) or a prebuilt :class:`Calibration`. ``config`` supplies
    dataloader/feed context and is diffed for ``plan-divergence`` — the
    planner never reads its comm_mode as a hint.
    """
    from .analyzer import GraphAnalyzer

    if devices is None:
        try:
            import jax
            devices = max(1, len(jax.devices()))
        except Exception:  # noqa: BLE001 — planning must not need devices
            devices = 1
    devices = max(1, int(devices))
    analyzer = GraphAnalyzer(graph, config=config, target=target,
                             feed_meta=feed_meta)
    from .analyzer import AnalysisContext
    ctx = AnalysisContext(analyzer.eval_nodes, analyzer.topo, config=config,
                          target=analyzer.target, feed_meta=feed_meta,
                          ps_embed_ids=analyzer.ps_embed_ids)
    calibration = None
    if isinstance(calibrate, Calibration):
        calibration = calibrate
    elif calibrate:
        calibration = load_calibration(str(calibrate))
    model = CostModel(analyzer.topo, ctx.abstract, cmc=cost_config,
                      calibration=calibration, training=True, config=config,
                      ps_embed_ids=analyzer.ps_embed_ids)
    microbatches = (getattr(config, "gpipe_microbatches", None)
                    or model.cmc.microbatches)

    candidates: List[MeshCandidate] = []
    best = None   # (cand, decisions, extras)
    for dp, tp, pp in _mesh_candidates(devices, model.tp_able,
                                       model.pp_able):
        cand, decisions, extras = evaluate_candidate(
            model, dp, tp, pp, microbatches)
        candidates.append(cand)
        if cand.feasible and (best is None
                              or cand.predicted_step_ms
                              < best[0].predicted_step_ms):
            best = (cand, decisions, extras)

    anchor = next((n for n in analyzer.topo if n.is_optimizer),
                  next(iter(analyzer.topo), None))
    if best is None:
        worst_peak = min((c.peak_gib for c in candidates
                          if c.peak_gib is not None), default=0.0)
        cmc = model.cmc
        return Plan(devices=devices, mesh=None, comm_mode=None,
                    comm_quant="off", zero1=False, remat=False,
                    predicted_step_ms=None, breakdown={},
                    memory={"peak_gib": worst_peak,
                            "budget_gib": cmc.hbm_budget_gb},
                    params=[], candidates=candidates,
                    calibration=calibration, anchor=anchor)

    cand, decisions, extras = best
    modes = {d.mode for d in decisions if d.mode != "local"}
    if modes == {"AllReduce"}:
        comm_mode = "AllReduce"
    elif modes == {"PS"}:
        comm_mode = "PS"
    elif modes:
        comm_mode = "Hybrid"
    else:
        comm_mode = None
    comm_quant = ("int8" if any(d.quant for d in decisions) else "off")
    return Plan(
        devices=devices,
        mesh={"dp": cand.dp, "tp": cand.tp, "pp": cand.pp},
        comm_mode=comm_mode, comm_quant=comm_quant,
        zero1=cand.zero1, remat=cand.remat,
        predicted_step_ms=cand.predicted_step_ms,
        breakdown=extras["breakdown"], memory=extras["mem"],
        params=decisions, candidates=candidates,
        calibration=calibration, anchor=anchor)


# ---------------------------------------------------------------------------
# CI self-test (hetulint --plan --check)
# ---------------------------------------------------------------------------

def _overflow_graph():
    """A graph whose dp-replicated layout overflows a ~3 GiB budget but
    whose ZeRO-1 variant fits: one 1.07 GiB Adam-managed weight (param
    1.07 + slots 2.15 + grad 1.07 GiB plain; slots/dp under ZeRO-1).
    Nothing materializes — initializers carry shapes only."""
    import numpy as np
    import hetu_tpu as ht

    x = ht.Variable(name="plan_big_x",
                    value=np.zeros((32, 4096), np.float32),
                    trainable=False)
    w = ht.init.random_normal((4096, 65536), stddev=0.02, name="plan_big_w")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    train = ht.optim.AdamOptimizer(1e-3).minimize(loss)
    return {"train": [loss, train]}


def plan_self_check(out=None) -> int:
    """Tier-1-safe smoke of the planning contract over the bundled
    builders + a synthetic HBM-overflow graph. Returns 0 when every
    claim holds — the verify-skill/CI hook (docs/ANALYSIS.md)."""
    import sys

    out = out or sys.stdout
    from . import examples
    from .analyzer import AnalysisConfig
    from .cli import _builder_result

    ok = True

    def check(label, cond):
        nonlocal ok
        state = "ok" if cond else "FAIL"
        if not cond:
            ok = False
        print(f"hetulint --plan --check: {label} -> {state}", file=out)

    # 1. CTR-PS: Hybrid with quantized sparse rows, no hand hints
    graph, cfg_kwargs = _builder_result(examples.build_ctr_ps)
    plan = plan_graph(graph, config=AnalysisConfig(), devices=8)
    table = next((d for d in plan.params if d.sparse), None)
    dense = [d for d in plan.params if not d.sparse]
    check("ctr_ps plans Hybrid", plan.comm_mode == "Hybrid")
    check("ctr_ps sparse table -> PS + kQI8",
          table is not None and table.mode == "PS"
          and table.quant == "kQI8")
    check("ctr_ps dense params -> AllReduce",
          bool(dense) and all(d.mode == "AllReduce" for d in dense))

    # 2. MLP: pure dense -> AllReduce dp8, feasible, quant obeys exemption
    graph, _ = _builder_result(examples.build_mlp)
    plan = plan_graph(graph, devices=8)
    check("mlp plans AllReduce dp8",
          plan.comm_mode == "AllReduce" and plan.mesh == {"dp": 8, "tp": 1,
                                                          "pp": 1})
    small = [d for d in plan.params if d.size_elems < 2048]
    check("mlp small params keep exact wire (exemption)",
          all(d.quant is None for d in small))

    # 3. HBM gate: a graph whose plain layout overflows adopts ZeRO-1;
    # one no budget can hold is never emitted as a chosen plan
    big = _overflow_graph()
    plan = plan_graph(big, devices=8,
                      cost_config=CostModelConfig(hbm_budget_gb=3.0))
    check("overflowing layout adopts ZeRO-1, fits the gate",
          plan.mesh is not None and plan.zero1
          and plan.memory.get("feasible") is True)
    plan = plan_graph(big, devices=8,
                      cost_config=CostModelConfig(hbm_budget_gb=0.5))
    check("impossible budget -> no plan + plan-infeasible error",
          plan.mesh is None
          and any(f.lint == "plan-infeasible" and f.severity == ERROR
                  for f in plan.findings()))

    # 4. calibration shifts the prediction in the measured direction
    graph, _ = _builder_result(examples.build_mlp)
    base = plan_graph(graph, devices=1)
    cal = Calibration(legs_ms={
        "compute": (base.breakdown.get("compute_ms", 0.0) or 1e-3) * 2.0,
        "feed": 0.05, "poststep": 0.05})
    shifted = plan_graph(graph, devices=1, calibrate=cal)
    check("calibration shifts prediction toward measured",
          shifted.predicted_step_ms > base.predicted_step_ms)

    return 0 if ok else 1
