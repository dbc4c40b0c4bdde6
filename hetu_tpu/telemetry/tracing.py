"""Structured step/op tracing: Chrome-trace-format JSON (Perfetto-loadable).

``Tracer`` records complete ("ph": "X") events with microsecond timestamps,
one lane per thread (the PS push/pull streams show up as their own rows under
the worker's process lane). Per-rank files are merged into one timeline with
rank lanes by ``bin/hetutrace``.

The one vocabulary of names (below, and docs/OBSERVABILITY.md) is written
where the work happens and lands in whatever ``jax.profiler`` capture is
open, with no switch: ``span``/``step_span`` open the ``hetu.*`` host spans
of ``SubExecutor.run`` as ``jax.profiler.TraceAnnotation`` s (jax's own
always-on TraceMe, ~0.4 us each with no capture open), so they share the
profiler's clock and thread line with the ``PjitFunction`` call and the
device ops; ``scoped`` puts a phase scope into the compiled program's HLO
metadata. ``HETU_XLA_TRACE=dir[:start_step[:n_steps]]``
(:class:`XlaTraceWindow`) is one way to open such a capture: a bounded
``jax.profiler.start_trace``/``stop_trace`` window around the configured
steps, so a production job can capture an XLA-level trace of steps
1000..1009 without tracing the whole run.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

# trace clock: perf_counter in µs, with BOTH anchors recorded in metadata —
# the unix wall clock and the raw perf_counter value. On Linux perf_counter
# reads CLOCK_MONOTONIC (since boot, shared by every process on a host), so
# hetutrace's merge can re-anchor same-host ranks on the monotonic deltas:
# an NTP step mid-run moves the wall anchors but not the mono ones, which is
# exactly the bug class that bit the PR 4 req_id seeding. Cross-HOST merges
# fall back to the wall anchors (mono origins differ per boot) — the host
# name rides along so the merge can tell.
_T0_PERF = time.perf_counter()
_T0_UNIX = time.time()

# -- the one vocabulary -------------------------------------------------------
# Each name is written by one module and read by the metrics PERF.md section
# 3 lists; the Pallas kernels' names live with the kernels (docs/KERNELS.md).
STEP = "hetu_step"        # one SubExecutor.run call, step_num=<step>
# phase scopes in the compiled program (HLO metadata `op_name`): a device op
# under SCOPE_OPT is optimizer work, one under `transpose(` backward (its
# `checkpoint`ed body recomputation), a collective by opcode, the rest
# forward
SCOPE_FWD = "hetu_fwd"    # around the loss function that is differentiated
SCOPE_OPT = "hetu_opt"    # around the optimizer update
# the four parts of a MoE block (transformer._moe_mlp), nested under
# SCOPE_FWD: the phase rule above still says forward / recompute / backward
SCOPE_MOE_ROUTE = "hetu_moe_route"        # router matmul, softmax, top-k,
                                          # sort, group sizes, both aux losses
SCOPE_MOE_DISPATCH = "hetu_moe_dispatch"  # token rows gathered by expert
SCOPE_MOE_EXPERTS = "hetu_moe_experts"    # grouped matmuls + activation
SCOPE_MOE_COMBINE = "hetu_moe_combine"    # un-permute, weight, sum over k
MOE_SCOPES = (SCOPE_MOE_ROUTE, SCOPE_MOE_DISPATCH, SCOPE_MOE_EXPERTS,
              SCOPE_MOE_COMBINE)
# a looped model's exit head (transformer.loss_fn at n_loops > 1), nested
# under SCOPE_FWD: the exit gate, the n_loops head passes, the exit
# distribution q and its entropy. The counter beside it is the pure function
# `transformer.exit_stats` (mean q(t) an exit, the expected exit step)
SCOPE_EXIT = "hetu_exit"
# the four parts of a Mamba-2 mixer (transformer._mamba), nested under
# SCOPE_FWD like the MoE scopes; benchmark/reduce/ssm.py reads them
SCOPE_SSM_PROJ = "hetu_ssm_proj"  # the in- and the out-projection
SCOPE_SSM_CONV = "hetu_ssm_conv"  # causal depthwise convolution, bias, SiLU
SCOPE_SSM_SCAN = "hetu_ssm_scan"  # from the convolution's output to the
                                  # gate: dt, the log-decay, the chunked
                                  # recurrence (`_ssd`), the D skip
SCOPE_SSM_GATE = "hetu_ssm_gate"  # y SiLU(z) and its RMSNorm
SSM_SCOPES = (SCOPE_SSM_PROJ, SCOPE_SSM_CONV, SCOPE_SSM_SCAN, SCOPE_SSM_GATE)
# the three parts of the chunked scan (transformer._ssd, _mamba), nested
# INSIDE SCOPE_SSM_SCAN: `.../hetu_ssm_scan/hetu_ssd_inchunk/...`; dt's
# softplus stays directly under the outer scope
SCOPE_SSD_INCHUNK = "hetu_ssd_inchunk"  # the chunks cut, the log-decay, the
                                        # decay matrix, masked C B^T, its
                                        # product with x dt
SCOPE_SSD_STATES = "hetu_ssd_states"    # each chunk's own state and the
                                        # recurrence over the chunk states
SCOPE_SSD_ENTER = "hetu_ssd_enter"      # the entering state's part C S, and
                                        # the D skip
SSD_SCOPES = (SCOPE_SSD_INCHUNK, SCOPE_SSD_STATES, SCOPE_SSD_ENTER)
# the ordinary parts of a block (transformer._attention, _dense_mlp, _norm),
# nested under SCOPE_FWD; benchmark/reduce/block.py reads them, an op under
# the innermost scope of its path. A MoE block keeps MOE_SCOPES for its MLP
# and a mamba layer SSM_SCOPES for its mixer. Residual adds, checkpoint
# names and the residual stream's sharding constraints are in none of them
SCOPE_BLK_QKV = "hetu_blk_qkv"    # the fused wqkv projection and its bias,
                                  # the split, QK-norm, RoPE, the attention
                                  # multiplier, the grouped-query repeat
SCOPE_BLK_ATTN = "hetu_blk_attn"  # scores, softmax, P V: the flash kernels
                                  # (`.../hetu_blk_attn/flash_fwd/...`) or
                                  # the dot / ring path
SCOPE_BLK_WO = "hetu_blk_wo"      # the output projection and its bias
SCOPE_BLK_MLP_UP = "hetu_blk_mlp_up"      # w1 (and w3), bias, activation
SCOPE_BLK_MLP_DOWN = "hetu_blk_mlp_down"  # w2 and its bias
SCOPE_BLK_NORM = "hetu_blk_norm"  # every LayerNorm / RMSNorm of the residual
                                  # stream (`_norm`: pre, post, sandwich,
                                  # final); QK-norm stays with SCOPE_BLK_QKV
BLOCK_SCOPES = (SCOPE_BLK_QKV, SCOPE_BLK_ATTN, SCOPE_BLK_WO,
                SCOPE_BLK_MLP_UP, SCOPE_BLK_MLP_DOWN, SCOPE_BLK_NORM)
# the two outside the block
SCOPE_EMBED = "hetu_embed"  # token (position, segment) lookups, BERT's
                            # embedding LayerNorm, the embedding multiplier;
                            # backward: the scatter-add into the table
SCOPE_HEAD = "hetu_head"    # the vocabulary head and its loss, fused or
                            # einsum (under SCOPE_EXIT on a looped model:
                            # `hetu_exit/hetu_head/...`); BERT's MLM
                            # transform, decoder, NSP head and both losses
# what the trunk's `jax.checkpoint` may keep of a layer's forward pass
# (`jax.ad_checkpoint.checkpoint_name`; the identity outside a checkpoint).
# Each name sits where the value is made; `transformer._remat_names` admits
# them by bytes, `transformer.encode` hands the admitted ones to the policy
REMAT_X1 = "hetu_x1"      # h + attention's output: what the next norm reads
REMAT_X2 = "hetu_x2"      # x1 + the MLP's output (post-LN: ln2's input)
REMAT_ATTN_O = "hetu_attn_o"      # attention's output, `wo`'s input
REMAT_ATTN_LSE = "hetu_attn_lse"  # the flash kernel's row statistic
# q, k, v as the attention kernels take them on the SPLIT path
# (`transformer._split_heads`: after QK-norm, RoPE and the multiplier, before
# the grouped-query repeat, so k and v at `kv_heads`): what the backward
# kernels read again, and with them `wqkv` and RoPE's rolls
REMAT_ATTN_Q = "hetu_attn_q"
REMAT_ATTN_K = "hetu_attn_k"
REMAT_ATTN_V = "hetu_attn_v"
# the sandwich norms' inputs (`cfg.sandwich_norm` alone: elsewhere the value
# feeds a residual add, whose backward pass reads nothing): the mixer's
# output, `wo`'s on an attention layer, and the MLP's, `w2`'s
REMAT_NORM1_IN = "hetu_norm1_in"
REMAT_NORM2_IN = "hetu_norm2_in"
# in the order `_remat_names` admits them; the last two groups by ms of the
# step saved a GiB kept on the v5e, each measured alone on Ouro's 24 block
# applications: q, k, v 20.8 ms for 1.125 GiB, the norms' inputs 6.3 for
# 0.75 (PERF.md, PR 36). The rule on q, k, v is BY PATH, read from the code
# and not from the model. Where the kernels read the fused [q | k | v]
# projection IN PLACE (`transformer._attention`: nothing touches q or k on
# the way; BERT) nothing of it is a candidate: a layer's slice of the kept
# `[layers, B, T, 3 D]` stack has to be copied out for them, which cost the
# v5e as much as the matmul costs to run again, for 3.4 GiB (PERF.md, PR
# 28). On the split path q, k and v are three arrays of their own already,
# and running them again is RoPE's rolls on top of the matmul
REMAT_CANDIDATES = ((REMAT_X1, REMAT_X2), (REMAT_ATTN_O, REMAT_ATTN_LSE),
                    (REMAT_ATTN_Q, REMAT_ATTN_K, REMAT_ATTN_V),
                    (REMAT_NORM1_IN, REMAT_NORM2_IN))
# host spans inside SubExecutor.run, children of STEP, in call order
(BOUNDARY, FEED, DL_WAIT, PS_PULL, BUILD, DISPATCH, PREFETCH, PS_PUSH,
 POSTSTEP) = STEP_SPANS = (
    "hetu.boundary",      # supervisor / elastic / pilot hooks
    "hetu.feed",          # placeholders through _prepare_input
    "hetu.dl_wait",       # dataloader get_batch, resident cursors: input wait
    "hetu.ps_pull",       # staged lookups, prefetch misses, wait_dense
    "hetu.build",         # signature, cache lookup, _build (compiled=1 then)
    "hetu.dispatch",      # fn(*args): host dispatch time, NOT device time
    "hetu.prefetch",      # next batch's device_put
    "hetu.ps_push",       # gradient push issue, next-batch prefetch pulls
    "hetu.poststep",      # state commit, guard read, hetuscope, telemetry
)

# jax.profiler.TraceAnnotation, resolved lazily on first use (None =
# unresolved; _NoSpan where jax is unavailable — stay stdlib-importable)
_ANNOT = None


try:
    _HOST = os.uname().nodename
except (AttributeError, OSError):  # non-POSIX fallback
    _HOST = "localhost"

# the CORRECT mono-comparability key: CLOCK_MONOTONIC counts from kernel
# boot, and the kernel's boot_id uniquely names that boot — two processes
# share a monotonic origin iff they share it (containers with identical
# image hostnames do; distinct machines never do, whatever their names)
try:
    with open("/proc/sys/kernel/random/boot_id") as _f:
        _BOOT_ID = _f.read().strip()
except OSError:
    _BOOT_ID = ""   # non-Linux: merge falls back to wall anchors


def _now_us() -> float:
    return (time.perf_counter() - _T0_PERF) * 1e6


class _SpanCtx:
    """Context manager for one span; re-entrant use creates nested events
    (Perfetto nests same-tid "X" events by containment)."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0

    def __enter__(self) -> "_SpanCtx":
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc) -> None:
        self._tracer._emit(self.name, self.cat, self._t0,
                           _now_us() - self._t0, self.args)


class Tracer:
    """Chrome-trace event buffer for ONE process (= one rank).

    Events buffer in memory and are written as a complete JSON object on
    ``flush()`` (rewrite-in-place via tmp+rename: the file on disk is always
    valid JSON, even mid-run). A step loop flushes every ``flush_every``
    spans; resilience abort paths flush explicitly before ``os._exit``.
    """

    def __init__(self, path: str, rank: int = 0, flush_every: int = 2048,
                 max_events: Optional[int] = None):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self.rank = int(rank)
        self.flush_every = int(flush_every)
        # the file is rewritten whole on each flush (that is what keeps it
        # valid JSON at every instant), so the buffer must be bounded —
        # past the cap new events are counted as dropped, not appended;
        # trace mode is for bounded diagnosis windows, not week-long runs
        self.max_events = (int(os.environ.get("HETU_TRACE_MAX_EVENTS",
                                              "200000"))
                           if max_events is None else int(max_events))
        self.dropped = 0
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()   # serializes tmp+rename
        self._events: list[dict] = []
        self._thread_named: set[int] = set()
        self._metadata = [
            {"ph": "M", "pid": self.rank, "name": "process_name",
             "args": {"name": f"rank {self.rank}"}},
        ]
        self._since_flush = 0

    def span(self, name: str, cat: str = "step",
             args: Optional[dict] = None) -> _SpanCtx:
        return _SpanCtx(self, name, cat, args)

    def instant(self, name: str, cat: str = "event",
                args: Optional[dict] = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "p",
              "ts": round(_now_us(), 1), "pid": self.rank,
              "tid": self._tid()}
        if args:
            ev["args"] = args
        self._append(ev)

    def complete(self, name: str, t0_perf: float, t1_perf: float,
                 cat: str = "step", args: Optional[dict] = None) -> None:
        """Emit a finished span from two ``time.perf_counter()`` readings —
        the executor's hot path records bare timestamps and emits post-hoc,
        so the traced and untraced step bodies stay structurally identical
        (no nested with-blocks to keep in sync)."""
        self._emit(name, cat, (t0_perf - _T0_PERF) * 1e6,
                   (t1_perf - t0_perf) * 1e6, args)

    def _tid(self) -> int:
        t = threading.current_thread()
        tid = t.ident or 0
        if tid not in self._thread_named:
            self._thread_named.add(tid)
            self._metadata.append(
                {"ph": "M", "pid": self.rank, "tid": tid,
                 "name": "thread_name", "args": {"name": t.name}})
        return tid

    def _emit(self, name: str, cat: str, ts_us: float, dur_us: float,
              args: Optional[dict]) -> None:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": round(ts_us, 1), "dur": round(dur_us, 1),
              "pid": self.rank, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._append(ev)

    def _append(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)
            self._since_flush += 1
            need_flush = self._since_flush >= self.flush_every
        if need_flush:
            self.flush()

    def flush(self) -> str:
        """Write the complete trace file (valid JSON at every point).

        The event list is COPIED under the buffer lock (concat) — the dump
        below must not iterate a list a stream thread is appending to —
        and the tmp+rename pair is serialized by its own lock: two
        concurrent flushes (step loop + PS stream crossing ``flush_every``,
        or an abort-path flush) each publish a complete file, last one
        wins, instead of interleaving writes into one shared .tmp."""
        with self._lock:
            other = {"clock_anchor_unix_s": round(_T0_UNIX, 3),
                     "clock_anchor_mono_s": round(_T0_PERF, 6),
                     "host": _HOST,
                     "boot_id": _BOOT_ID,
                     "rank": self.rank}
            if self.dropped:
                other["dropped_events"] = self.dropped
            events = self._metadata + self._events
            self._since_flush = 0
        doc = {
            "displayTimeUnit": "ms",
            "otherData": other,
            "traceEvents": events,
        }
        with self._flush_lock:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, separators=(",", ":"))
            os.replace(tmp, self.path)
        return self.path


class XlaTraceWindow:
    """Bounded jax.profiler trace window.

    ``spec`` is ``dir[:start_step[:n_steps]]`` (defaults: start 0, 10 steps).
    ``on_step(step)`` opens/closes the profiler window; call it at every
    step boundary — two integer compares when outside the window. The
    Executor owns one whether or not telemetry is on; a window still open
    at interpreter exit is stopped there, or jax discards the profile.
    """

    def __init__(self, spec: str):
        parts = spec.split(":")
        self.dir = parts[0]
        self.start_step = int(parts[1]) if len(parts) > 1 and parts[1] else 0
        self.n_steps = int(parts[2]) if len(parts) > 2 and parts[2] else 10
        self._active = False
        self._done = False

    @classmethod
    def from_env(cls) -> Optional["XlaTraceWindow"]:
        spec = os.environ.get("HETU_XLA_TRACE")
        return cls(spec) if spec else None

    def on_step(self, step: int) -> None:
        if self._done:
            return
        end = self.start_step + self.n_steps
        if not self._active:
            if step >= end:
                # resumed past the window (auto-resume restores the step
                # counter): never open — a late start would capture the
                # wrong steps, not the configured ones
                self._done = True
            elif step >= self.start_step:
                import atexit

                import jax.profiler
                jax.profiler.start_trace(self.dir)
                self._active = True
                atexit.register(self.stop)
        elif step >= end:
            self.stop()

    def stop(self) -> None:
        if self._active:
            import jax.profiler
            jax.profiler.stop_trace()
            self._active = False
            self._done = True



class _NoSpan:
    """Stands in for a TraceAnnotation where jax cannot be imported."""

    def __init__(self, _name, **_args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **_args):
        pass


class _Stamped:
    """A span that also keeps its ``perf_counter`` stamps in ``stamps``:
    what ``last_phases``, ``Tracer.complete``, hetutrail's legs and the
    watch read, so each phase is delimited once."""

    __slots__ = ("_ann", "_name", "_stamps", "_t0")

    def __init__(self, ann, name, stamps):
        self._ann, self._name, self._stamps = ann, name, stamps

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = t0 = time.perf_counter()
        self._stamps[self._name] = (t0, t0)   # its start, while it is open
        return self._ann

    def __exit__(self, *exc):
        self._stamps[self._name] = (self._t0, time.perf_counter())
        return self._ann.__exit__(*exc)


def _resolve():
    """jax.profiler's TraceAnnotation, or the stand-in: once, not per span."""
    global _ANNOT
    try:
        from jax.profiler import TraceAnnotation
        _ANNOT = TraceAnnotation
    except Exception:  # noqa: BLE001 — a span is best-effort
        _ANNOT = _NoSpan
    return _ANNOT


def span(name: str, stamps: Optional[dict] = None, **args):
    """Open the host span ``name`` (one of ``STEP_SPANS``) as a
    ``jax.profiler.TraceAnnotation``; entering it yields the annotation
    (``set_metadata(k=v)`` adds arguments). With a ``stamps`` dict (a timed
    step) the span's ``(start, end)`` ``perf_counter`` readings are also
    kept there under its name."""
    ann = (_ANNOT or _resolve())(name, **args)
    return ann if stamps is None else _Stamped(ann, name, stamps)


def step_span(step: int, stamps: Optional[dict] = None):
    """The step-level span ``STEP`` with ``step_num``, the identifier every
    child span shares: what ``jax.profiler.StepTraceAnnotation`` writes
    (``_r=1`` marks a step to the profiler's tools)."""
    return span(STEP, stamps, _r=1, step_num=int(step))


def scoped(name: str, fn):
    """``fn`` run under ``jax.named_scope(name)``: how a train step marks
    the function it differentiates (``SCOPE_FWD``; its backward ops then
    carry ``transpose(jvp(hetu_fwd))``) and its optimizer update
    (``SCOPE_OPT``). Trace-time only: HLO metadata, no run-time cost."""
    import jax
    return jax.named_scope(name)(fn)
