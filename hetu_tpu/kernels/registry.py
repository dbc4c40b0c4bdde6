"""hetukern dispatch registry: the one gate between graph ops and the
Pallas kernel tier (docs/KERNELS.md).

Every kernel in ``hetu_tpu/kernels`` registers itself here as a
:class:`KernelSpec` — ``{name, pallas_fn, xla_fallback, eligibility}`` —
and every call site goes through :func:`dispatch`, never straight at the
``pallas_fn``. The mode knob (``HetuConfig(kernels="off"|"auto"|"force")``
/ ``HETU_KERNELS``) decides which implementation serves a call:

- ``off``   — the XLA fallback, unconditionally. Bit-identical to the
  pre-hetukern tree: the fallback IS the expression the op used before
  the tier existed.
- ``auto``  — the Pallas kernel when the shape/dtype eligibility
  predicate passes AND the backend is a real TPU; the fallback otherwise
  (per call, per shape — a 100-row lookup falls back while the 1M-row
  one next to it takes the kernel). Off-TPU, ``auto`` always falls back:
  interpret-mode Pallas is a *testing* vehicle, slower than the XLA
  fallback it mirrors.
- ``force`` — the Pallas kernel, interpret-mode off-TPU (how the CPU
  equality tests drive the kernel path); an ineligible shape raises
  :class:`KernelEligibilityError` instead of silently falling back —
  hetulint's ``kernels_pass`` catches this at define time.

Dispatch decisions happen at TRACE time (the call sites live inside the
jitted step), so the ``hetu_kernel_dispatch_total{kernel,path}`` counter
ticks once per compiled program per call site, not once per step — it
answers "which tier serves this op family in the programs now running",
which is what hetutop's ``kernels:`` panel shows. A process-local mirror
(:func:`dispatch_stats`) backs the hetulint fallback-ratio note when
telemetry is off.

The mode is scoped, not global: the Executor wraps every step
trace/lower in ``with active(config.kernels):`` so two executors with
different settings interleave correctly; bare calls outside any scope
resolve from ``HETU_KERNELS`` (default ``auto``).
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Optional

MODES = ("off", "auto", "force")

# shared TPU tiling/budget constants for the kernel modules (one home so
# a budget or tile change cannot silently drift between kernels)
LANE = 128
SUBLANE = 8
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


class KernelEligibilityError(ValueError):
    """kernels="force" met a shape/dtype the Pallas kernel cannot take."""

    def __init__(self, kernel: str, reason: str):
        super().__init__(
            f"kernels='force': {kernel} is ineligible for this call — "
            f"{reason}. Use kernels='auto' to fall back per-shape, or fix "
            "the shape (docs/KERNELS.md lists each kernel's eligibility "
            "rules)")
        self.kernel = kernel
        self.reason = reason


class KernelSpec:
    """One registered kernel: the Pallas implementation, the XLA expression
    it must match, and the predicate deciding per-call eligibility.

    ``eligibility(*args, **kwargs) -> (ok, reason)`` sees the same
    arguments as the implementations; it must only read shapes/dtypes (it
    is also called by hetulint with ``ShapeDtypeStruct`` stand-ins)."""

    def __init__(self, name: str, pallas_fn: Callable, xla_fallback: Callable,
                 eligibility: Callable):
        self.name = name
        self.pallas_fn = pallas_fn
        self.xla_fallback = xla_fallback
        self.eligibility = eligibility


_REGISTRY: dict[str, KernelSpec] = {}

# process-local dispatch tallies: {(kernel, path): count}. Mirrors the
# telemetry counter so the hetulint fallback-ratio note works without an
# active telemetry session.
_stats: dict[tuple, int] = {}
# why auto declined: {(kernel, reason): count}, so a run on the chip can
# show that every fallback was a shape's doing and none the backend's
_reasons: dict[tuple, int] = {}
_stats_lock = threading.Lock()

# scoped-mode stack (executor traces push config.kernels here); thread-local
# because PS stream threads must not see a trace's scope
_tls = threading.local()


def register_kernel(name: str, *, pallas_fn: Callable, xla_fallback: Callable,
                    eligibility: Callable) -> KernelSpec:
    spec = KernelSpec(name, pallas_fn, xla_fallback, eligibility)
    _REGISTRY[name] = spec
    return spec


def get_kernel(name: str) -> Optional[KernelSpec]:
    return _REGISTRY.get(name)


def registered_kernels() -> dict[str, KernelSpec]:
    return dict(_REGISTRY)


def resolve_mode(mode: Optional[str] = None) -> str:
    """Config-or-env resolution (the telemetry convention): explicit wins,
    then ``HETU_KERNELS``, then ``auto`` (which changes nothing off-TPU —
    eligibility gates the kernel path to real TPU backends)."""
    if mode is None:
        mode = os.environ.get("HETU_KERNELS") or "auto"
    if mode not in MODES:
        raise ValueError(f"kernels must be one of {MODES}, got {mode!r}")
    return mode


class active:
    """``with active("force"): ...`` — scope the dispatch mode for the
    enclosed trace. Re-entrant; the innermost scope wins.

    ``spmd=True`` marks the enclosed trace as a GSPMD multi-device
    program (the executor passes ``mesh is not None and mesh.size > 1``):
    a bare ``pallas_call`` inside such a program has no SPMD partitioning
    rule — GSPMD would fail to lower it or replicate the operand — so
    every kernel's eligibility declines under this flag. Per-shard
    ``shard_map`` wrapping of the kernels is the documented follow-up
    (docs/KERNELS.md); until then the tier serves single-device programs.
    """

    def __init__(self, mode: Optional[str], spmd: bool = False):
        self.mode = resolve_mode(mode)
        self.spmd = bool(spmd)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append((self.mode, self.spmd))
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()
        return False


def current_mode() -> str:
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1][0]
    return resolve_mode(None)


def in_spmd_scope() -> bool:
    """Is the current trace scoped as a GSPMD multi-device program?"""
    stack = getattr(_tls, "stack", None)
    return bool(stack) and stack[-1][1]


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _count(kernel: str, path: str, reason: Optional[str] = None) -> None:
    with _stats_lock:
        key = (kernel, path)
        _stats[key] = _stats.get(key, 0) + 1
        if reason is not None:
            _reasons[(kernel, reason)] = _reasons.get((kernel, reason), 0) + 1
    from .. import telemetry as _tel
    t = _tel.get()
    if t is not None:
        t.metrics.counter("hetu_kernel_dispatch_total",
                          {"kernel": kernel, "path": path}).inc()


def dispatch_stats() -> dict:
    """``{(kernel, path): count}`` snapshot of every dispatch decision this
    process made (trace-time tallies — see the module docstring)."""
    with _stats_lock:
        return dict(_stats)


def fallback_reasons() -> dict:
    """``{(kernel, reason): count}`` for every dispatch ``auto`` served
    from the fallback: the eligibility predicate's reason, or the backend
    when an eligible shape met a non-TPU one."""
    with _stats_lock:
        return dict(_reasons)


def reset_stats() -> None:
    with _stats_lock:
        _stats.clear()
        _reasons.clear()


def fallback_ratio(kernel: str) -> Optional[float]:
    """Share of this kernel's AUTO-mode dispatches served by the fallback,
    or None when it was never dispatched under auto. Force-mode servings
    count under the distinct ``forced`` path, so an equality smoke run
    before linting cannot dilute this ratio."""
    s = dispatch_stats()
    pallas = s.get((kernel, "pallas"), 0)
    fb = s.get((kernel, "fallback"), 0)
    total = pallas + fb
    return (fb / total) if total else None


def dispatch(name: str, *args, **kwargs):
    """Serve one kernel call through the mode/eligibility gate.

    Paths counted: ``pallas`` (kernel served under auto), ``forced``
    (kernel served under force), ``fallback`` (auto declined — ineligible
    shape or non-TPU backend), ``off`` (mode off). ``force`` raises on
    ineligibility rather than counting a fallback."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"no kernel {name!r} registered "
                       f"(have: {sorted(_REGISTRY)})")
    mode = current_mode()
    if mode == "off":
        _count(name, "off")
        return spec.xla_fallback(*args, **kwargs)
    ok, reason = _check_eligibility(spec, args, kwargs)
    if mode == "force":
        if not ok:
            raise KernelEligibilityError(name, reason or "ineligible")
        _count(name, "forced")
        return spec.pallas_fn(*args, **kwargs)
    # auto: Pallas only where it can win — an eligible shape on a real TPU
    if ok and _on_tpu():
        _count(name, "pallas")
        return spec.pallas_fn(*args, **kwargs)
    _count(name, "fallback",
           "backend is not a tpu" if ok else (reason or "ineligible"))
    return spec.xla_fallback(*args, **kwargs)


def _check_eligibility(spec: KernelSpec, args, kwargs):
    """Shared pre-check + per-kernel predicate: the partitioned-context
    decline lives HERE (once), not copy-pasted into every predicate."""
    if _partitioned_context():
        return False, ("inside a partitioned trace (shard_map named axis "
                       "or GSPMD multi-device scope)")
    return spec.eligibility(*args, **kwargs)


def eligibility_of(name: str, *args, **kwargs):
    """(ok, reason) for a hypothetical call — what hetulint's
    ``kernels_pass`` evaluates against abstract shapes."""
    spec = _REGISTRY.get(name)
    if spec is None:
        return False, f"no kernel {name!r} registered"
    return _check_eligibility(spec, args, kwargs)


def _partitioned_context() -> bool:
    """True when a bare ``pallas_call`` would face partitioning the
    kernels do not implement: a GSPMD multi-device scope (the executor's
    ``active(..., spmd=True)``) or a named-axis (shard_map/pmap) trace.
    Eligibility predicates decline here so ``auto`` keeps partitioned
    programs on their XLA fallbacks."""
    return in_spmd_scope() or _in_named_axis_trace()


def _in_named_axis_trace() -> bool:
    """True inside a shard_map/pmap/named-vmap trace, where a pallas_call
    cannot be partitioned by GSPMD — eligibility predicates use this to
    decline (the DistGCN call site lives inside shard_map).

    One probe, jax's own axis environment. It is a private name: if a jax
    upgrade moves it, this raises at the first dispatch instead of making
    ``auto`` decline every kernel without a word."""
    from jax._src.core import get_axis_env
    return bool(get_axis_env().axis_sizes)
