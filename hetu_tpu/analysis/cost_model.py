"""hetuplan cost model: prices for a layout candidate (docs/ANALYSIS.md
"Tier C: planning").

The planner (:mod:`planner`) searches layouts; this module prices them.
Three families of cost terms, all derived from define-time information:

- **Compute** — the hetuprof roofline formulas (``profiler.roofline_rows``)
  over hetulint's abstract shapes vs the assumed peaks: per op family,
  ``max(flops/peak_tflops, bytes/peak_gbs)``. Same math as
  ``hetuprof --roofline`` so a measured residual from one surface calibrates
  the other.
- **Communication** — analytic wire-byte formulas per leg: ring AllReduce
  (reduce-scatter + all-gather, the hetuq quantized decomposition priced
  exactly as ``comm_quant.allreduce_wire_report`` so planner claims and the
  exported ``hetu_comm_quant_*`` gauges agree), PS dense push/pull and PS
  sparse row traffic with the ``kQI8`` container's per-row scale overhead
  (EQuARX-style wire ratios, docs/COMM_QUANT.md), and the pipeline bubble
  fraction.
- **Memory** — per-device HBM projection in the AOT memory-gate
  decomposition (``peak = args + out + temp − alias``, the
  ``last_memory_analysis`` / ``__graft_entry__.aot_memory_check`` formula)
  so "would this candidate fit" is answered by the same algebra the gate
  enforces. ZeRO-1 shards optimizer slots over dp; remat scales the saved
  activations by ``remat_factor``.

Every number here is a MODEL against ASSUMED peaks (docs/ROOFLINE.md:
assumptions, not readings). :class:`Calibration` folds measured data back
in: per-family roofline residuals (the ``hetuprof --roofline --json``
table) and measured critical-path legs from a telemetry dir (PR 13's
``cp_legs`` machinery) — ``hetulint --plan --calibrate TEL_DIR``.
"""
from __future__ import annotations

import glob
import json
import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..telemetry import profiler as _prof
from ..comm_quant import DEFAULT_BLOCK, DEFAULT_MIN_SIZE

# assumed interconnect peaks, same env convention as the roofline peaks
# (docs/ROOFLINE.md): collective fabric (ICI-class) and the PS/host link
# (NIC-class) are different orders of magnitude, which is most of why the
# dense/sparse comm-mode split exists at all
DEFAULT_NET_GBS = float(os.environ.get("HETU_PEAK_NET_GBS", "45"))
DEFAULT_PS_GBS = float(os.environ.get("HETU_PEAK_PS_GBS", "12.5"))
# same env as the AOT memory gate (__graft_entry__.aot_memory_check)
DEFAULT_HBM_GB = float(os.environ.get("HETU_HBM_BUDGET_GB", "16"))


@dataclass
class CostModelConfig:
    """Assumed peaks + model knobs. All overridable per call; the defaults
    come from the same envs the roofline and the AOT gate read."""

    peak_tflops: float = None
    peak_gbs: float = None
    net_gbs: float = None          # collective fabric, per device
    ps_gbs: float = None           # PS/host link, per server
    ps_servers: int = 1
    hbm_budget_gb: float = None
    quant_block: int = DEFAULT_BLOCK
    quant_min_size: int = DEFAULT_MIN_SIZE
    # fraction of saved activations remat keeps live (stage boundaries)
    remat_factor: float = 0.3
    # pipeline microbatch count for the bubble model (config.gpipe_microbatches
    # overrides when declared)
    microbatches: int = 4

    def __post_init__(self):
        if self.peak_tflops is None:
            self.peak_tflops = _prof.DEFAULT_PEAK_TFLOPS
        if self.peak_gbs is None:
            self.peak_gbs = _prof.DEFAULT_PEAK_GBS
        if self.net_gbs is None:
            self.net_gbs = DEFAULT_NET_GBS
        if self.ps_gbs is None:
            self.ps_gbs = DEFAULT_PS_GBS
        if self.hbm_budget_gb is None:
            self.hbm_budget_gb = DEFAULT_HBM_GB


# ---------------------------------------------------------------------------
# comm-leg algebra (pure, unit-tested against hand-computed formulas)
# ---------------------------------------------------------------------------

def ring_allreduce_bytes(n_elems: int, dp: int, quant: Optional[str] = None,
                         block: int = DEFAULT_BLOCK) -> Dict[str, float]:
    """Per-device wire bytes of one ring all-reduce of ``n_elems`` f32.

    The ring moves ``(dp-1)/dp`` of the payload per leg; the two legs are
    reduce-scatter + all-gather. The hetuq decomposition keeps the
    reduce-scatter exact (f32 — the accumulation never sees quantization
    error) and compresses only the all-gather leg to 1 byte/elem + one f32
    scale per ``block`` (comm_quant.quantized_allreduce). Returns
    ``{"raw", "wire", "ratio"}`` — raw is the all-f32 wire, wire the one
    this quant choice actually moves."""
    if dp <= 1:
        return {"raw": 0.0, "wire": 0.0, "ratio": 1.0}
    frac = (dp - 1) / dp
    rs = 4.0 * n_elems * frac
    ag_raw = 4.0 * n_elems * frac
    raw = rs + ag_raw
    if quant in ("int8", "fp8"):
        nb = -(-n_elems // block)
        wire = rs + (n_elems + 4.0 * nb) * frac
    else:
        wire = raw
    return {"raw": raw, "wire": wire,
            "ratio": raw / wire if wire else 1.0}


def ps_dense_bytes(n_elems: int, quant: Optional[str] = None,
                   block: int = DEFAULT_BLOCK) -> Dict[str, float]:
    """Per-worker per-step PS wire bytes for a dense param: one gradient
    push + one value pull, each ``4n`` raw or the ``kQI8`` container
    (1 byte/elem + one f32 scale per 256-elem block) when quantized —
    csrc/ps/net.h's dense layout."""
    leg_raw = 4.0 * n_elems
    if quant in ("int8", "kQI8"):
        nb = -(-n_elems // block)
        leg = float(n_elems) + 4.0 * nb
    else:
        leg = leg_raw
    raw = 2.0 * leg_raw
    wire = 2.0 * leg
    return {"raw": raw, "wire": wire,
            "ratio": raw / wire if wire else 1.0}


def ps_sparse_bytes(rows: float, dim: int, quant: Optional[str] = None
                    ) -> Dict[str, float]:
    """Per-worker per-step PS wire bytes for a lookup-accessed table:
    ``rows`` touched rows of width ``dim`` move twice (pull the rows, push
    the row gradients), each with an int64 row id. The ``kQI8`` sparse
    layout is row-wise: 1 byte/elem + ONE f32 scale per row
    (csrc/ps/net.h), so the ratio approaches 4x as ``dim`` grows."""
    ids = 8.0 * rows
    leg_raw = 4.0 * rows * dim + ids
    if quant in ("int8", "kQI8"):
        leg = rows * dim + 4.0 * rows + ids
    else:
        leg = leg_raw
    return {"raw": 2.0 * leg_raw, "wire": 2.0 * leg,
            "ratio": leg_raw / leg if leg else 1.0}


def expected_unique(vocab: int, lookups: float) -> float:
    """Expected distinct rows touched by ``lookups`` uniform draws from a
    ``vocab``-row table: ``V·(1 − (1−1/V)^L)``. Uniform is the coarse
    prior — real CTR streams are zipfian (fewer uniques); the planner only
    needs the order of magnitude, and calibration absorbs the rest."""
    if vocab <= 0 or lookups <= 0:
        return 0.0
    return float(vocab) * (1.0 - (1.0 - 1.0 / vocab) ** float(lookups))


def pipeline_bubble(pp: int, microbatches: int) -> float:
    """GPipe bubble fraction: ``(pp−1)/(m+pp−1)`` of the step is idle
    ramp-up/drain."""
    if pp <= 1:
        return 0.0
    m = max(1, int(microbatches))
    return (pp - 1) / (m + pp - 1)


# ---------------------------------------------------------------------------
# calibration — measured data folded back into the model
# ---------------------------------------------------------------------------

@dataclass
class Calibration:
    """Measured corrections for the analytic model.

    - ``family_residual``: op family -> measured/predicted multiplier, the
      residual column of ``hetuprof --roofline --json``.
    - ``legs_ms``: mean measured critical-path legs (feed/ps_pull/compute/
      ps_push/poststep) from a telemetry dir — PR 13's ``cp_legs``.
    - ``step_ms``: mean measured steady-state step time.

    The compute residual is leg-level: measured compute leg over the
    model's single-device compute prediction for the SAME graph (so
    calibrate with a run of the graph being planned). Host overhead
    (feed + poststep legs) is additive and layout-invariant in the model.
    """

    family_residual: Dict[str, float] = field(default_factory=dict)
    legs_ms: Dict[str, float] = field(default_factory=dict)
    step_ms: Optional[float] = None
    source: str = ""
    # single-device uncalibrated compute prediction for the GRAPH THE
    # MEASUREMENT CAME FROM — makes the compute residual a true
    # graph-independent ratio (a cross-size calibration sets it). Unset,
    # the residual is taken against the planned graph's own baseline —
    # correct under the documented same-graph contract of
    # ``hetulint --plan --calibrate``.
    baseline_compute_ms: Optional[float] = None

    @property
    def host_ms(self) -> float:
        """Measured feed + poststep wall time per step (additive,
        layout-invariant in the model)."""
        return (self.legs_ms.get("feed", 0.0)
                + self.legs_ms.get("poststep", 0.0))

    @property
    def measured_work_ms(self) -> Optional[float]:
        """Measured per-step device-work window: the wall step minus the
        host legs and the PS waits. NOT the dispatch stamp — the executor
        dispatches asynchronously, so the compute leg alone undercounts
        the device time that drains between stamps; the wall remainder is
        what the work actually cost."""
        if self.step_ms:
            work = (float(self.step_ms) - self.host_ms
                    - self.legs_ms.get("ps_pull", 0.0)
                    - self.legs_ms.get("ps_push", 0.0))
            if work > 0:
                return work
        v = self.legs_ms.get("compute")
        return float(v) if v else None

    @property
    def measured_ps_ms(self) -> Optional[float]:
        v = (self.legs_ms.get("ps_pull", 0.0)
             + self.legs_ms.get("ps_push", 0.0))
        return float(v) if v else None

    def as_dict(self) -> dict:
        return {"source": self.source, "step_ms": self.step_ms,
                "legs_ms": {k: round(v, 4)
                            for k, v in self.legs_ms.items()},
                "family_residual": {k: round(v, 4) for k, v
                                    in self.family_residual.items()}}


def _residuals_from_roofline_doc(doc) -> Dict[str, float]:
    """Family residuals out of a ``hetuprof --roofline --json`` document —
    either the structured ``{"kind": "roofline", "rows": [...]}`` form or
    the bare row list."""
    rows = doc.get("rows", []) if isinstance(doc, dict) else doc
    out: Dict[str, float] = {}
    for r in rows if isinstance(rows, list) else []:
        if not isinstance(r, dict):
            continue
        fam, resid = r.get("family"), r.get("residual")
        if fam and isinstance(resid, (int, float)) and resid > 0 \
                and math.isfinite(resid):
            out[fam] = float(resid)
    return out


def load_calibration(path: str) -> Calibration:
    """Build a :class:`Calibration` from measured artifacts.

    ``path`` may be a telemetry directory (metrics-r*.jsonl step records →
    mean critical-path legs + step time; any ``roofline*.json`` files in it
    → family residuals) or a single roofline-JSON file. Missing pieces
    degrade silently — a calibration of nothing is the uncalibrated model.
    """
    cal = Calibration(source=path)
    if os.path.isfile(path):
        try:
            with open(path) as f:
                cal.family_residual = _residuals_from_roofline_doc(
                    json.load(f))
        except (OSError, ValueError):
            pass
        return cal
    if not os.path.isdir(path):
        return cal
    records = _prof.read_metrics_records(path)
    means = _prof.step_phase_means(records)
    if means:
        cal.step_ms = means.get("step_ms")
        cal.legs_ms = {k: float(v)
                       for k, v in _prof.cp_legs(means).items()}
    # live hetuwatch stream (docs/OBSERVABILITY.md pillar 6): a watched
    # run's kind:"watch" rows carry per-family EWMA residuals and measured
    # legs continuously — calibration no longer needs a dedicated offline
    # run. The last (most-converged) row wins; rows from a stale elastic
    # era abstain and carry no residuals, so they contribute nothing.
    watch_rows = [r for r in records
                  if r.get("kind") == "watch" and "abstain" not in r]
    if watch_rows:
        last = watch_rows[-1]
        fams = last.get("families")
        if isinstance(fams, dict):
            for fam, resid in fams.items():
                if isinstance(resid, (int, float)) and resid > 0 \
                        and math.isfinite(resid):
                    cal.family_residual.setdefault(fam, float(resid))
        if not cal.legs_ms:
            # no step records in the dir (e.g. a pruned watch-only
            # stream): the watch rows themselves supply the legs
            legs_sum: Dict[str, float] = {}
            for r in watch_rows:
                for leg, v in (r.get("legs") or {}).items():
                    legs_sum[leg] = legs_sum.get(leg, 0.0) + float(v)
            cal.legs_ms = {k: v / len(watch_rows)
                           for k, v in legs_sum.items()}
            cal.step_ms = sum(float(r.get("step_ms", 0.0))
                              for r in watch_rows) / len(watch_rows)
    # explicit roofline docs override the watch stream's leg-level prior
    for p in sorted(glob.glob(os.path.join(path, "roofline*.json"))):
        try:
            with open(p) as f:
                cal.family_residual.update(
                    _residuals_from_roofline_doc(json.load(f)))
        except (OSError, ValueError):
            continue
    return cal


# ---------------------------------------------------------------------------
# per-parameter profiles
# ---------------------------------------------------------------------------

@dataclass
class ParamProfile:
    """What the comm-mode decision needs to know about one trainable var."""

    name: str
    size: int                      # elements
    nbytes: int
    dim: int                       # trailing dim (row width for tables)
    sparse: bool                   # read through an embedding lookup
    touched_rows: float = 0.0      # expected distinct rows per step
    density: float = 1.0           # touched_rows / vocab
    tp_sharded: bool = False       # a dispatch marker pins its layout
    slot_factor: int = 0           # optimizer state copies (Adam=2, SGD=0)
    forced_ps: bool = False        # an explicit PS push pins it to PS
    node: object = None            # live PlaceholderOp handle

    @property
    def vocab(self) -> int:
        return self.size // max(1, self.dim)


_SLOT_FACTORS = {"AdamOptimizer": 2, "AdamWOptimizer": 2,
                 "MomentumOptimizer": 1, "AdaGradOptimizer": 1,
                 "SGDOptimizer": 0}


def param_profiles(topo, abstract, ps_embed_ids=frozenset()
                   ) -> List[ParamProfile]:
    """Profiles for every optimizer-managed trainable variable.

    Sparse classification is STRUCTURAL, no hand hints: any variable read
    through an embedding lookup (``embed_node``) is sparse — the same rule
    the executor applies at build. Touched rows come from the lookup
    index shapes under the uniform-draw expectation; an explicit
    ``embedding_lookup_gradient_op`` routed to a PS push (the PR-12 rows
    route) counts through its own index input.
    """
    from ..graph.node import PlaceholderOp
    from ..graph.ops.comm import DispatchOp

    lookup_elems: Dict[int, float] = {}
    # (table id, index-node id) pairs already counted: a lookup and the
    # explicit rows-route grad op share ONE index tensor — the grad push
    # covers the same rows the lookup pulled, not an additional batch
    counted: set = set()
    sparse_ids: set = set(ps_embed_ids)
    by_name: Dict[str, object] = {}

    def count_lookup(var, idx_node):
        idx_shape = abstract.shape_of(idx_node)
        if not idx_shape or (id(var), id(idx_node)) in counted:
            return
        counted.add((id(var), id(idx_node)))
        lookup_elems[id(var)] = (lookup_elems.get(id(var), 0.0)
                                 + float(np.prod(idx_shape)))

    for node in topo:
        if isinstance(node, PlaceholderOp) and node.trainable:
            by_name.setdefault(node.name, node)
        embed = getattr(node, "embed_node", None)
        if embed is not None and getattr(embed, "trainable", False):
            sparse_ids.add(id(embed))
            if len(node.inputs) > 1:
                count_lookup(embed, node.inputs[1])
        # PR-12 rows route: an explicit embed-grad op names its table via
        # the consuming push's ps_id; its index input sizes the traffic
        if getattr(node, "opname", None) == "EmbeddingLookUpGradient":
            for consumer in topo:
                if getattr(consumer, "ps_id", None) is not None \
                        and node in consumer.inputs:
                    var = by_name.get(consumer.ps_id)
                    if var is not None and len(node.inputs) > 1:
                        sparse_ids.add(id(var))
                        count_lookup(var, node.inputs[1])

    tp_pinned: set = set()
    for node in topo:
        if isinstance(node, DispatchOp) \
                and getattr(node.inputs[0], "trainable", False):
            tp_pinned.add(id(node.inputs[0]))

    out: List[ParamProfile] = []
    seen: set = set()

    def profile(var, slot_factor, forced_ps=False):
        if id(var) in seen:
            return
        seen.add(id(var))
        shape = (abstract.shape_of(var)
                 or tuple(getattr(var, "shape", ()) or ()))
        if not shape:
            return
        size = int(np.prod(shape))
        dim = int(shape[-1]) if len(shape) > 1 else 1
        itemsize = np.dtype(getattr(var, "dtype", np.float32)).itemsize
        sparse = id(var) in sparse_ids
        touched = 0.0
        density = 1.0
        if sparse:
            vocab = size // max(1, dim)
            touched = expected_unique(vocab,
                                      lookup_elems.get(id(var), 0.0))
            density = touched / vocab if vocab else 1.0
        out.append(ParamProfile(
            name=var.name, size=size, nbytes=size * itemsize, dim=dim,
            sparse=sparse, touched_rows=touched, density=density,
            tp_sharded=id(var) in tp_pinned, slot_factor=slot_factor,
            forced_ps=forced_ps, node=var))

    for node in topo:
        if not node.is_optimizer:
            continue
        slot_factor = _SLOT_FACTORS.get(type(node.optimizer).__name__, 1)
        for var in getattr(node, "vars", ()):
            profile(var, slot_factor)
    # params synced only through an explicit PS push (the rows-route
    # pattern): no OptimizerOp manages them worker-side — the server owns
    # the update, and the push op is a structural commitment to PS the
    # planner must respect (removing it would change the graph, not just
    # the layout)
    for node in topo:
        ps_id = getattr(node, "ps_id", None)
        if ps_id is not None and ps_id in by_name:
            profile(by_name[ps_id], 0, forced_ps=True)
    return out


# ---------------------------------------------------------------------------
# the cost model proper
# ---------------------------------------------------------------------------

class CostModel:
    """Prices one graph's compute/comm/memory for any layout candidate.

    Built once per planning run from the topo + abstract shapes; the
    planner then queries it per (dp, tp, pp, zero1, remat, per-param comm
    assignment) candidate. ``calibration`` (optional) folds measured
    residuals in — see :class:`Calibration`.
    """

    def __init__(self, topo, abstract, cmc: Optional[CostModelConfig] = None,
                 calibration: Optional[Calibration] = None,
                 training: bool = True, config=None,
                 ps_embed_ids=frozenset()):
        self.topo = list(topo)
        self.abstract = abstract
        self.cmc = cmc or CostModelConfig()
        self.calibration = calibration
        self.training = training
        self.config = config          # HetuConfig / AnalysisConfig or None
        # roofline families over the same abstract shapes hetuprof uses —
        # one source of truth for the compute prediction
        self.roofline = _prof.roofline_rows(
            self.topo, training=training,
            peak_tflops=self.cmc.peak_tflops, peak_gbs=self.cmc.peak_gbs)
        self.params = param_profiles(self.topo, abstract,
                                     ps_embed_ids=ps_embed_ids)
        self._act_bytes = self._activation_bytes()
        self._feed_bytes = self._feed_input_bytes()

    # -- structural capabilities ---------------------------------------
    @property
    def tp_able(self) -> bool:
        from ..graph.ops.comm import DispatchOp
        return any(isinstance(n, DispatchOp) for n in self.topo)

    @property
    def pp_able(self) -> bool:
        from ..graph.ops.comm import PipelineSendOp
        return (any(isinstance(n, PipelineSendOp) for n in self.topo)
                or bool(getattr(self.config, "gpipe", False)))

    # -- compute -------------------------------------------------------
    def base_compute_ms(self, calibrated: bool = True) -> float:
        """Single-device per-step compute prediction: sum of per-family
        roofline times, each scaled by its measured residual when the
        calibration carries one."""
        total_us = 0.0
        fr = (self.calibration.family_residual
              if calibrated and self.calibration else {})
        for r in self.roofline:
            total_us += r.predicted_us * fr.get(r.family, 1.0)
        return total_us / 1e3

    def compute_ms(self, dp: int, tp: int = 1, remat: bool = False) -> float:
        """Per-step compute for a candidate: batch-linear work divides by
        dp (each replica computes its shard) and matmul-class work by tp;
        the optimizer update is per-parameter and does not shrink with dp.
        Remat re-runs the forward inside backward: +1 forward on the 3x
        fwd+bwd+bwd training multiplier (~+33% matmul compute)."""
        fr = (self.calibration.family_residual if self.calibration else {})
        opt_us = 0.0
        rest_us = 0.0
        mm_us = 0.0
        for r in self.roofline:
            us = r.predicted_us * fr.get(r.family, 1.0)
            if r.family.startswith("Optimizer"):
                opt_us += us
            elif r.family in _prof._MATMUL_FAMILIES \
                    or r.family in _prof._CONV_FAMILIES:
                mm_us += us
            else:
                rest_us += us
        if remat and self.training:
            mm_us *= 4.0 / 3.0
            rest_us *= 1.5
        ms = (opt_us + (mm_us / max(1, tp) + rest_us) / max(1, dp)) / 1e3
        # leg-level residual: measured work window over the calibration
        # run's predicted compute — a RATIO, so it corrects everything the
        # family residuals missed (real vs assumed peaks, fusion, runtime
        # drain) and transfers across graph sizes. The baseline is the
        # measured graph's own prediction when the calibration carries it
        # (a cross-size calibration); otherwise this graph's — the
        # documented same-graph --calibrate contract.
        if self.calibration and self.calibration.measured_work_ms:
            base = (self.calibration.baseline_compute_ms
                    or self.base_compute_ms(calibrated=True))
            if base > 0:
                ms *= self.calibration.measured_work_ms / base
        return ms

    # -- communication -------------------------------------------------
    def allreduce_ms(self, decisions, dp: int) -> float:
        """Ring-AllReduce time for every param assigned AllReduce."""
        if dp <= 1:
            return 0.0
        wire = 0.0
        for d in decisions:
            if d.mode != "AllReduce":
                continue
            wire += ring_allreduce_bytes(
                d.size_elems, dp, quant=d.quant,
                block=self.cmc.quant_block)["wire"]
        return wire / (self.cmc.net_gbs * 1e9) * 1e3

    def ps_ms(self, decisions, dp: int) -> float:
        """PS traffic time: every worker's push+pull bytes land on the
        server links (``ps_servers`` × ``ps_gbs``) — the PS tier's
        bottleneck is the server side once dp grows."""
        per_worker_ms = self._uncal_ps_ms_single(decisions)
        ms = per_worker_ms * max(1, dp)
        if ms > 0 and self.calibration \
                and self.calibration.measured_ps_ms \
                and per_worker_ms > 0:
            # leg residual only when the measured run exercised the PS
            # path; the single-worker prediction is the residual baseline
            ms *= self.calibration.measured_ps_ms / per_worker_ms
        return ms

    def _uncal_ps_ms_single(self, decisions) -> float:
        """One worker's PS push+pull time — ONE copy of the per-decision
        wire pricing (ps_ms scales and residual-corrects it)."""
        per_worker = 0.0
        for d in decisions:
            if d.mode != "PS":
                continue
            if d.sparse:
                per_worker += ps_sparse_bytes(
                    d.touched_rows, d.dim, quant=d.quant)["wire"]
            else:
                per_worker += ps_dense_bytes(
                    d.size_elems, quant=d.quant,
                    block=self.cmc.quant_block)["wire"]
        return per_worker / (self.cmc.ps_servers * self.cmc.ps_gbs * 1e9) \
            * 1e3

    def host_ms(self) -> float:
        """Measured feed/poststep overhead (layout-invariant additive term);
        zero without calibration — the analytic model cannot see it."""
        return self.calibration.host_ms if self.calibration else 0.0

    # -- memory (the AOT-gate decomposition) ---------------------------
    def _activation_bytes(self) -> int:
        total = 0
        for node in self.topo:
            if node.is_placeholder or node.is_dataloader \
                    or node.is_optimizer or node.is_gradient:
                continue
            m = self.abstract.meta.get(id(node))
            total += _prof._nbytes(m) if m is not None else 0
        return total

    def _feed_input_bytes(self) -> int:
        total = 0
        for node in self.topo:
            if not (node.is_dataloader
                    or (node.is_placeholder
                        and getattr(node, "is_feed", False))):
                continue
            m = self.abstract.meta.get(id(node))
            total += _prof._nbytes(m) if m is not None else 0
        return total

    def memory(self, dp: int, tp: int = 1, pp: int = 1,
               ps_resident=frozenset(), zero1: bool = False,
               remat: bool = False) -> Dict[str, float]:
        """Projected per-device HBM in the AOT-gate decomposition.

        ``ps_resident``: param ids hosted server-side (they cost the
        device nothing). Params replicate over dp (the lint this planner
        automates away is exactly that cost); tp-pinned params shard over
        tp; ZeRO-1 shards optimizer slots over dp; remat keeps
        ``remat_factor`` of the saved activations. peak = args + out +
        temp − alias, alias = donated params + slots.
        """
        param_b = slot_b = grad_b = 0.0
        for p in self.params:
            if id(p.node) in ps_resident:
                continue
            local = p.nbytes / (tp if p.tp_sharded else 1) / max(1, pp)
            param_b += local
            slot_b += local * p.slot_factor / (dp if zero1 else 1)
            grad_b += local
        act = self._act_bytes / max(1, dp) / max(1, pp)
        if self.training:
            act *= 2.0              # forward values saved for backward
            if remat:
                act *= self.cmc.remat_factor
        feeds = self._feed_bytes / max(1, dp)
        args = param_b + slot_b + feeds
        out_b = param_b + slot_b    # next-step state (aliased)
        alias = param_b + slot_b
        temp = act + (grad_b if self.training else 0.0)
        peak = args + out_b + temp - alias
        return {"argument_bytes": args, "output_bytes": out_b,
                "temp_bytes": temp, "alias_bytes": alias,
                "peak_bytes": peak,
                "peak_gib": peak / 2**30,
                "budget_gib": self.cmc.hbm_budget_gb,
                "feasible": peak / 2**30 <= self.cmc.hbm_budget_gb}
