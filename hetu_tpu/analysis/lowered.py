"""Tier B: static analysis of the *lowered* program.

Tier A sees the Op graph; Tier B sees what XLA will actually run, through the
hooks every ``SubExecutor`` already carries: ``_lowered()`` (StableHLO of the
latest executed step), ``dump_hlo`` and ``last_cost_analysis``. These checks
need at least one executed step — they answer "is the step program the step
program you meant to compile", which only exists after a run:

- **Recompilation detector** — each distinct feed/batch signature compiles a
  fresh XLA program. Signature churn (one python-int shape per step, an
  unpadded last batch, a host-side lr baked as a constant) silently turns a
  training loop into a compile loop. Budget is per-subexecutor.
- **Donation/aliasing check** — the training step donates params/slots/state
  buffers; if the lowered text carries no aliasing attributes the program
  double-buffers every parameter.
- **Host-transfer check** — host callbacks (``io_callback``, debug prints)
  inside the step serialize the device on the host round-trip every step.
- **Replicated-large-tensor lint** — a parameter replicated across a dp>1
  mesh spends ``dp * nbytes`` of HBM; cost-analysis byte counts put the
  program's total traffic next to the worst offenders (the GSPMD-style
  sharded-weight-update work in PAPERS.md is the fix this lint motivates).
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from .findings import Finding, WARN, NOTE

# replicated-large-tensor default threshold; see resolve_replicated_threshold
DEFAULT_REPLICATED_THRESHOLD = 64 << 20


def resolve_replicated_threshold(config=None) -> int:
    """Threshold for the replicated-large-tensor lint, resolved the usual
    way: an explicit ``AnalysisConfig(replicated_threshold_bytes=...)`` (or
    any config carrying that attribute) wins, then the
    ``HETU_REPLICATED_THRESHOLD_BYTES`` env (how CI tightens it for
    planner-chosen tp layouts), then the 64 MiB default."""
    t = getattr(config, "replicated_threshold_bytes", None)
    if t is None:
        t = os.environ.get("HETU_REPLICATED_THRESHOLD_BYTES")
    return DEFAULT_REPLICATED_THRESHOLD if t in (None, "") else int(t)


def _fmt_bytes(n) -> str:
    return f"{n / 1e6:.1f} MB" if n >= 1e6 else f"{n / 1e3:.1f} KB"

HOST_CALLBACK_MARKERS = (
    "xla_python_cpu_callback", "xla_ffi_python_cpu_callback",
    "xla_python_gpu_callback", "infeed", "outfeed",
)
DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")

_SIG_PARTS = ("feed signature", "dataloader-batch signature",
              "optimizer host token", "PS staged-row shapes",
              "introspection cadence", "poisoned op")


def _sub_finding(sub, lint, severity, message) -> Finding:
    f = Finding(lint=lint, severity=severity, message=message,
                op_name=sub.name, op_type="SubExecutor",
                pass_name="lowered")
    f.op = sub
    return f


def _lowered_text(sub) -> Optional[str]:
    try:
        low = sub._lowered()
        return None if low is None else low.as_text()
    except Exception:  # noqa: BLE001 — diagnostics only
        return None


def _describe_sig_change(prev, cur) -> str:
    """Human-readable diff of two compile-cache keys."""
    changed = [name for name, a, b in zip(_SIG_PARTS, prev, cur) if a != b]
    if not changed:
        return "signatures differ in an unnamed component"
    detail = []
    for name, a, b in zip(_SIG_PARTS, prev, cur):
        if a != b:
            detail.append(f"{name}: {a!r} -> {b!r}")
    return "; ".join(detail)


def recompile_findings(sub, budget: int = 3) -> list[Finding]:
    """Flag a subexecutor whose compile cache outgrew ``budget`` distinct
    step signatures — the signature churn that turns steps into compiles.
    Counted over SHAPE signatures (``_base_sigs``) when available: the
    hetuscope cadence/poison variants of one signature are deliberate
    extra compiles, not churn."""
    cache = getattr(sub, "_compiled", None)
    if cache is None:
        return []
    # collapse the hetuscope cadence/poison variants (2 trailing key
    # components) onto their shape signature, preserving first-seen order:
    # both the count and the churn diff must describe SHAPE churn, not a
    # deliberate variant switch
    sigs = list(dict.fromkeys(
        k[:len(_SIG_PARTS) - 2] if len(k) > len(_SIG_PARTS) - 2 else k
        for k in cache))
    n = len(sigs)
    if n <= budget:
        return []
    churn = (f"; last change: {_describe_sig_change(sigs[-2], sigs[-1])}"
             if len(sigs) >= 2 else "")
    return [_sub_finding(
        sub, "recompile-budget", WARN,
        f"{n} distinct step programs compiled (budget {budget}) — "
        "the step signature churns across steps, so steps pay compile "
        f"latency instead of running{churn}. Pad batches (drop_last), fix "
        "feed shapes, or hoist host-side optimizer state")]


def donation_findings(sub) -> list[Finding]:
    """Training steps donate params/slots/op-state; a lowered program with no
    aliasing attribute re-allocates every buffer each step."""
    if not getattr(sub, "training", False):
        return []
    ex = sub.executor
    has_state = (bool(ex.param_nodes) or bool(sub.optimizer_nodes)
                 or bool(sub.stateful_nodes))
    if not has_state:
        return []
    txt = _lowered_text(sub)
    if txt is None:
        return []
    if not any(m in txt for m in DONATION_MARKERS):
        return [_sub_finding(
            sub, "donation-missing", WARN,
            "training step program carries no input/output buffer aliasing "
            "— params and optimizer state are double-buffered every step "
            "(HETU_NO_DONATE set, or donation lost in lowering)")]
    return []


def host_transfer_findings(sub) -> list[Finding]:
    """Host callbacks compiled INTO the step serialize the device on a
    host round-trip per step."""
    txt = _lowered_text(sub)
    if txt is None:
        return []
    out = []
    for marker in HOST_CALLBACK_MARKERS:
        if marker in txt:
            out.append(_sub_finding(
                sub, "host-transfer", WARN,
                f"compiled step program contains a host transfer "
                f"({marker!r}, {txt.count(marker)} site(s)) — every step "
                "blocks on a host round-trip; move the callback out of the "
                "step or gate it off the hot path"))
    return out


def cost_analysis_of(sub) -> Optional[dict]:
    """Cost analysis dict of the latest executed step, or None — the
    analysis-side alias of ``SubExecutor.last_cost_analysis``."""
    return sub.last_cost_analysis()


def replicated_tensor_findings(sub, threshold_bytes: Optional[int] = None
                               ) -> list[Finding]:
    """Parameters replicated (PartitionSpec ``P()``) across a dp>1 mesh with
    ``nbytes >= threshold`` — each replica burns a full copy of HBM and the
    update is recomputed everywhere (see PAPERS.md: automatic cross-replica
    sharding of the weight update). ``threshold_bytes=None`` resolves via
    :func:`resolve_replicated_threshold` (config attr → env → 64 MiB)."""
    cfg = sub.config
    if threshold_bytes is None:
        threshold_bytes = resolve_replicated_threshold(cfg)
    mesh = getattr(cfg, "mesh", None)
    dp = getattr(cfg, "dp_size", 1)
    if mesh is None or dp <= 1:
        return []
    ex = sub.executor
    topo_ids = {id(n) for n in sub.topo}
    cost = cost_analysis_of(sub) or {}
    prog_bytes = cost.get("bytes accessed")
    out = []
    for node in ex.param_nodes:
        if id(node) not in topo_ids:
            continue
        spec = cfg.param_specs.get(id(node))
        if spec is not None and any(s is not None for s in spec):
            continue  # sharded over some axis
        arr = ex.state["params"].get(id(node))
        nbytes = getattr(arr, "nbytes", 0)
        if nbytes >= threshold_bytes:
            extra = (f"; the step program moves "
                     f"{_fmt_bytes(prog_bytes)} total"
                     if prog_bytes else "")
            f = Finding.at(
                node, "replicated-large-tensor", WARN,
                f"parameter ({_fmt_bytes(nbytes)}) is fully replicated "
                f"across the {dp}-way dp axis — {dp}x HBM and a redundant "
                f"update on every replica{extra}; shard it with "
                "ht.dispatch or a param spec", "lowered")
            out.append(f)
    return out


def analyze_executor(executor, budget: int = 3,
                     large_tensor_bytes: Optional[int] = None
                     ) -> list[Finding]:
    """All Tier B checks over every subexecutor that has run at least one
    step. Gpipe subexecutors (their own per-stage programs) are skipped."""
    out: list[Finding] = []
    for sub in executor.subexecutors.values():
        if not hasattr(sub, "_compiled"):
            continue
        out.extend(recompile_findings(sub, budget))
        if getattr(sub, "_last_call", None) is not None:
            out.extend(donation_findings(sub))
            out.extend(host_transfer_findings(sub))
            out.extend(replicated_tensor_findings(sub, large_tensor_bytes))
    return out


class RecompileMonitor:
    """Per-subexecutor recompilation budget you can poll inside a training
    loop: ``monitor.check()`` returns NEW findings (a sub is re-reported only
    when its compile count grows past the last reported value)."""

    def __init__(self, executor, budget: int = 3):
        self.executor = executor
        self.budget = int(budget)
        self._reported: dict[str, int] = {}

    def check(self) -> list[Finding]:
        out = []
        for name, sub in self.executor.subexecutors.items():
            cache = getattr(sub, "_compiled", None)
            if cache is None:
                continue
            base = getattr(sub, "_base_sigs", None)
            n = len(base) if base else len(cache)
            if n > self.budget and n > self._reported.get(name, 0):
                self._reported[name] = n
                out.extend(recompile_findings(sub, self.budget))
        return out
