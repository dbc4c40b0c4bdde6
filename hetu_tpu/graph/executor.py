"""The Executor: define-then-run semantics compiled to single XLA programs.

Capability parity with the reference's ``gpu_ops/executor.py`` (HetuConfig
:103, Executor :301, SubExecutor :769, gradients :1096), redesigned for TPU:

The reference interprets the graph node-by-node in Python (executor.py:1029),
hand-assigning each op to one of five CUDA streams and synchronizing events.
Here each (subexecutor, feed-shape-signature) pair is traced ONCE into a
single jitted XLA program: the whole forward+backward+optimizer step — params
in, params out, buffers donated — so the Python overhead per step is one
function call and XLA owns scheduling, fusion, memory planning and collective
insertion. The reference's memory planner (executor.py:912), stream dispatch
(:1045-1073) and transfer-op insertion have no equivalent because XLA subsumes
them.

Data parallelism: with ``comm_mode='AllReduce'`` the executor builds a 1-axis
``jax.sharding.Mesh`` over the device group, shards feeds/batches along the
batch axis and replicates parameters; GSPMD inserts the gradient psum over ICI
(the reference drives NCCL per-gradient from Python on a dedicated stream,
AllReduceCommunicate.py:15-34).
"""
from __future__ import annotations

import json
import os
import pickle
import re
import time
import zlib
from typing import Any, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..context import DeviceGroup, get_current_context
from ..telemetry import tracing as _tr
from ..ndarray import (DLContext, NDArray, ND_Sparse_Array, SparseValue, cpu,
                       tpu, tpu_devices)
from .node import Op, PlaceholderOp, find_topo_sort
from .gradients import gradients, GradientOp, GradientContext
from .ops.comm import AllReduceCommunicateOp, DispatchOp, PipelineSendOp, PipelineReceiveOp
from .ops.ps import ParameterServerCommunicateOp, ParameterServerSparsePullOp

_NO_OUTPUT = "<no-output>"
_PS_RESIDENT = "<ps-resident-parameter>"

# op-name -> jax.named_scope name: "/" would open a NESTED scope (one op
# must be one scope segment so the profiler's HLO-metadata join stays 1:1)
_SCOPE_BAD = re.compile(r"[/\s]+")


def _op_scope(node: Op) -> str:
    return _SCOPE_BAD.sub("_", node.name)


def _flight_crc(feed_dict, batch_host) -> int:
    """Cheap batch fingerprint for the flight recorder: a chained crc32
    over a bounded stride-sample (≤512 elements per array, first + spread)
    of every fed/loaded host array — identifies WHICH batch a recorded
    step saw without storing data or paying a full-array pass per step."""
    h = 0
    vals = list(batch_host.values())
    for v in (feed_dict or {}).values():
        if hasattr(v, "asnumpy"):
            v = v.asnumpy()
        vals.append(v)
    for v in vals:
        try:
            a = np.asarray(v).ravel()
            stride = max(1, a.size // 512)
            h = zlib.crc32(np.ascontiguousarray(a[::stride][:512]).tobytes(),
                           h)
        except (TypeError, ValueError):
            continue
    return h


def _device_live_bytes() -> Optional[float]:
    """Live allocated device memory (bytes_in_use), or None where the
    backend keeps no allocator stats (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
        return float(stats["bytes_in_use"]) if stats else None
    except Exception:  # noqa: BLE001 — observability only
        return None


class HetuConfig:
    """Execution configuration (reference executor.py:103).

    Unused reference knobs that have no TPU meaning (stream counts, lazy
    memory planning) are accepted and ignored so call sites port unchanged.
    """

    def __init__(self, eval_node_list, train_name="*", val_name="*", ctx=None,
                 seed=None, comm_mode=None, mesh=None, use_sparse_pull=True,
                 cstable_policy=None, bsp=False, prefetch=True, enable_lazy=False,
                 cache_bound=100, log_path=None, gpipe=False,
                 gpipe_microbatches=None, dtype=np.float32,
                 dp_axis="dp", mp_axis="tp", anomaly_guard=False,
                 telemetry=None, introspect=None, comm_quant=None,
                 comm_quant_block=None, comm_quant_min_size=None,
                 comm_quant_error_feedback=None, comm_quant_force=(),
                 kernels=None, plan=None, watch=None, slo=None, **kwargs):
        self.eval_node_list = eval_node_list
        self.ctx = ctx
        self.seed = seed if seed is not None else np.random.randint(0, 2**31 - 1)
        self.comm_mode = comm_mode
        self.bsp = bsp
        self.prefetch = prefetch
        # accepted for API parity, no behavioral switch here: the PS path
        # ALWAYS stages sparse row pulls (the reference's False mode pulls
        # whole tables — strictly worse on TPU), and logging goes through
        # the standard logger rather than a file path
        self.use_sparse_pull = use_sparse_pull
        self.cstable_policy = cstable_policy
        self.cache_bound = cache_bound
        self.log_path = log_path
        self.gpipe = gpipe
        # microbatch count for dataloader-fed gpipe runs (run() without a
        # feed list); explicit feed lists carry their own M
        self.gpipe_microbatches = gpipe_microbatches
        # compute dtype: bf16 keeps the MXU fed at full rate; master params,
        # optimizer state and updates stay f32 (mixed precision — the
        # reference is f32-only, c_runtime_api.h GetDataSize :74-82)
        self.dtype = np.dtype(dtype)
        self.compute_dtype = self.dtype
        self.dp_axis = dp_axis
        self.mp_axis = mp_axis
        # resilience: in-trace finite-check gating the state commit (see
        # hetu_tpu/resilience.py). A NaN/Inf loss, parameter update or slot
        # leaves params/slots/op-state bit-identical to pre-step.
        from ..resilience import env_truthy
        self.anomaly_guard = bool(anomaly_guard) \
            or env_truthy("HETU_ANOMALY_GUARD")
        # observability: "off" (default, zero per-step overhead), "metrics"
        # (registry + per-step JSONL), or "trace" (+ Chrome-trace spans).
        # Env default: HETU_TELEMETRY; output dir: HETU_TELEMETRY_DIR.
        # See hetu_tpu/telemetry and docs/OBSERVABILITY.md.
        from ..telemetry import resolve_mode
        self.telemetry = resolve_mode(telemetry)
        # numeric-health introspection (docs/OBSERVABILITY.md "numeric
        # health"): 0 = off (default, zero per-step scope work — same
        # None-check-only contract as telemetry), N = fused in-graph stats
        # every N steps + flight recorder + NaN/Inf provenance on guard
        # trips. Env default: HETU_INTROSPECT (+ HETU_INTROSPECT_EVERY).
        from ..telemetry.scope import resolve_introspect
        self.introspect = resolve_introspect(introspect)
        # hetuwatch (docs/OBSERVABILITY.md pillar 6): runtime plan-
        # divergence sentinel. 0 = off (default, zero per-step watch work —
        # one attribute check, same contract as telemetry/introspect), N =
        # judge the measured critical-path legs against the adopted plan's
        # prediction every N steps, export residual gauges + kind:"watch"
        # JSONL, and latch plan_divergence / SLO-breach events. Env
        # default: HETU_WATCH (+ HETU_WATCH_EVERY). SLO budgets come from
        # slo= / HETU_SLO_SPEC (e.g. "step_ms<25,ps_pull_frac<0.3") and
        # are validated here so a bad spec fails at build, not mid-run.
        from ..telemetry.watch import parse_slo_spec, resolve_watch
        self.watch = resolve_watch(watch)
        self.slo = slo if slo is not None \
            else os.environ.get("HETU_SLO_SPEC", "")
        parse_slo_spec(self.slo)
        # hetuq (docs/COMM_QUANT.md): quantized communication policy. "off"
        # (default) leaves every comm path bit-identical to pre-hetuq
        # behavior; "int8"/"fp8" compresses the DP AllReduce broadcast half
        # in-trace (per-block scaling, optional error-feedback residual as
        # executor state, small params exempt by min_size) and arms the PS
        # worker's int8 wire container. Env default: HETU_COMM_QUANT (+
        # _BLOCK/_MIN/_EF).
        from ..comm_quant import resolve_policy
        self.comm_quant_policy = resolve_policy(
            comm_quant, comm_quant_block, comm_quant_min_size,
            comm_quant_error_feedback, comm_quant_force)
        self.comm_quant = self.comm_quant_policy.mode
        # hetukern (docs/KERNELS.md): Pallas kernel tier dispatch mode.
        # "off" = every call site serves its pre-hetukern XLA expression,
        # bit-identical; "auto" (default) = eligible shapes take the Pallas
        # kernel on real TPU backends and fall back per-shape elsewhere —
        # off-TPU auto IS the pre-hetukern path; "force" = kernels
        # everywhere (interpret mode off-TPU), ineligible shapes raise.
        # Env default: HETU_KERNELS. The executor scopes this mode around
        # every trace/lower so interleaved executors never leak settings.
        from ..kernels.registry import resolve_mode as _kresolve
        self.kernels = _kresolve(kernels)
        # hetuplan (docs/ANALYSIS.md "Tier C: planning"): "auto" asks the
        # Executor to run the cost-model planner over the graph at build
        # and adopt its comm_mode / comm_quant choice wherever this config
        # left them unset (an explicit declaration always wins — hetulint
        # --plan reports the divergence instead). A prebuilt analysis.Plan
        # is adopted as-is. Env default: HETU_PLAN=auto (off/0/false/none
        # disable — the HETU_KERNELS/HETU_COMM_QUANT convention).
        if plan is None:
            env_plan = os.environ.get("HETU_PLAN", "").strip().lower()
            if env_plan and env_plan not in ("off", "0", "false", "none",
                                             "no"):
                plan = env_plan
        if isinstance(plan, str) and plan not in ("auto",):
            raise ValueError(
                f"plan must be None, 'auto', or an analysis.Plan; "
                f"got {plan!r}")
        self.plan = plan
        self.plan_adopted = None   # set by Plan.apply at executor build
        if self.comm_quant != "off" and gpipe:
            raise ValueError(
                "comm_quant is not supported with gpipe=True: the pipeline "
                "executor owns its own cross-stage transfers")
        if self.anomaly_guard and comm_mode in ("PS", "Hybrid"):
            raise ValueError(
                "anomaly_guard gates the on-device state commit, but PS-"
                "hosted parameters update server-side per gradient push and "
                "cannot be skipped after the fact — run PS/Hybrid jobs "
                "without the guard")
        if mesh is not None and not isinstance(mesh, Mesh):
            raise ValueError(
                f"mesh must be a jax.sharding.Mesh, got {type(mesh).__name__}")
        self.mesh = mesh
        self.placeholder_to_arr_map = {}
        self.param_specs: dict[int, P] = {}  # placeholder id -> PartitionSpec
        self.has_dispatch = any(
            isinstance(n, DispatchOp)
            for n in find_topo_sort(self.eval_node_list))
        if self.mesh is None:
            self.mesh = self._deduce_mesh()
        if self.has_dispatch and (
                self.mesh is None or self.mp_axis not in self.mesh.axis_names):
            raise ValueError(
                "the graph contains ht.dispatch(...) tensor-parallel markers "
                "but no model-parallel mesh axis exists; place the model-"
                "parallel subgraph in a tuple DeviceGroup context (e.g. "
                "ctx=[(tpu(0), tpu(1)), (tpu(2), tpu(3))] for 2 workers x "
                f"2-way TP) or pass mesh= with a {self.mp_axis!r} axis")
        if self.kernels == "force" and self.mesh is not None \
                and self.mesh.size > 1:
            raise ValueError(
                "kernels='force' cannot serve a multi-device (GSPMD) "
                "program: a bare pallas_call has no SPMD partitioning "
                "rule, so every kernel would raise at trace time. Use "
                "kernels='auto' (partitioned programs keep their XLA "
                "fallbacks) — docs/KERNELS.md")
        self.device = self._deduce_device()

    # -- device & mesh deduction -------------------------------------------
    def _ctx_list(self):
        if isinstance(self.ctx, DeviceGroup):
            return self.ctx.flat()
        if isinstance(self.ctx, DLContext):
            return [self.ctx]
        if isinstance(self.ctx, (list, tuple)):
            return DeviceGroup(list(self.ctx)).flat()
        return []

    def _find_mp_group(self) -> Optional[DeviceGroup]:
        """Largest model-parallel (tuple-containing) DeviceGroup attached to
        the executor ctx or any graph node (reference context.py tuple syntax:
        ``[(d0, d1), (d2, d3)]`` = 2 workers x 2-way model parallel)."""
        best = None
        candidates = []
        if isinstance(self.ctx, DeviceGroup):
            candidates.append(self.ctx)
        for n in find_topo_sort(self.eval_node_list):
            if isinstance(n.raw_ctx, DeviceGroup):
                candidates.append(n.raw_ctx)
        for g in candidates:
            if g.is_mp and (best is None
                            or g.mp_device_num > best.mp_device_num):
                best = g
        return best

    def _deduce_mesh(self) -> Optional[Mesh]:
        mp_group = self._find_mp_group()
        if mp_group is not None:
            sizes = {len(c) for c in mp_group if isinstance(c, tuple)}
            if len(sizes) != 1 or not all(
                    isinstance(c, tuple) for c in mp_group):
                raise ValueError(
                    f"model-parallel DeviceGroup {mp_group} must consist of "
                    "uniform tuples: [(d0, d1), (d2, d3)] = 2 workers x 2-way")
            tp = sizes.pop()
            dp = mp_group.worker_num
            devs = [c.jax_device() for c in mp_group.flat()]
            if len(set(devs)) != dp * tp:
                raise ValueError(
                    f"model-parallel DeviceGroup {mp_group} resolves to "
                    f"{len(set(devs))} distinct devices, need {dp}x{tp}")
            return Mesh(np.array(devs).reshape(dp, tp),
                        (self.dp_axis, self.mp_axis))
        if self.comm_mode not in ("AllReduce", "Hybrid"):
            return None
        ctxs = self._ctx_list()
        if len(ctxs) > 1:
            devs = [c.jax_device() for c in ctxs]
        else:
            devs = tpu_devices()
        if len(devs) <= 1:
            return None
        return Mesh(np.array(devs), (self.dp_axis,))

    @property
    def dp_size(self) -> int:
        if self.mesh is None or self.dp_axis not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[self.dp_axis]

    def _deduce_device(self):
        ctxs = self._ctx_list()
        if ctxs:
            return ctxs[0].jax_device()
        return None


class TraceContext:
    """Per-trace services handed to ``Op.compute`` (replaces the reference's
    stream_handle/event plumbing)."""

    def __init__(self, config: HetuConfig, topo, training: bool, env: dict,
                 rng_key, step, op_state_in: dict):
        self.config = config
        self.topo = topo
        self.training = training
        self.env = env
        self.rng_key = rng_key
        self.step = step
        self.op_state_in = op_state_in
        self.op_state_updates: dict[int, Any] = {}
        self.param_updates: dict[int, Any] = {}
        self.slot_updates: dict[int, Any] = {}
        self.ps_grad_outputs: dict[int, Any] = {}
        # hetuq error-feedback residuals: executor-threaded state keyed by
        # quantized AllReduce op id (in: previous step's residual; updates:
        # this step's quantization error, committed like slots)
        self.qresid_in: dict[int, Any] = {}
        self.qresid_updates: dict[int, Any] = {}
        self.grad_cache: dict[int, dict[int, Any]] = {}
        self._in_grad_retrace = False
        # f32 master copies of params when compute_dtype is lower precision
        # (filled by the step builder; optimizer updates read these)
        self.master_params: dict[int, Any] = {}
        # hetuscope hooks: a clip_grad_norm optimizer publishes its fused
        # global-norm reduction here so the introspection stats reuse it
        # (one computation, two consumers); poison_scope is the nan_op
        # fault target — that op's output is NaN'd inside the trace
        self.grad_global_norm: Optional[Any] = None
        self.poison_scope: Optional[str] = None
        # Fold the node's position WITHIN this topo, not its process-global
        # id: global ids depend on how many nodes earlier code constructed,
        # which made RNG streams (dropout etc.) vary with test order.
        self._node_index = {id(n): i for i, n in enumerate(topo)}

    # -- RNG ---------------------------------------------------------------
    def next_rng(self, node: Op):
        return jax.random.fold_in(
            self.rng_key, self._node_index.get(id(node), node.id))

    # -- collectives (GSPMD) ----------------------------------------------
    def allreduce(self, x, param_node=None, op=None):
        mesh = self.config.mesh
        if mesh is None:
            return x
        # Constrain the gradient to the target parameter's own spec: GSPMD
        # inserts the psum over the dp axis (the MPI+NCCL module's job in the
        # reference); a tp-sharded parameter's gradient stays tp-sharded.
        spec = (self.config.param_specs.get(id(param_node), P())
                if param_node is not None else P())
        # hetuq: ops the Executor marked (comm_quant policy, eligibility by
        # size/override) lower as reduce-scatter(f32) -> blockwise quantize
        # -> all-gather(int8/fp8) -> dequantize, with the error-feedback
        # residual threaded through executor state (docs/COMM_QUANT.md)
        if op is not None and getattr(op, "comm_quant", False) \
                and self.config.comm_quant_policy.active \
                and hasattr(x, "dtype") \
                and jnp.issubdtype(x.dtype, jnp.floating) \
                and self.config.dp_axis in mesh.axis_names:
            from .. import comm_quant as _cq
            out, new_resid = _cq.quantized_allreduce(
                x, self.qresid_in.get(id(op)), mesh, self.config.dp_axis,
                NamedSharding(mesh, spec), self.config.comm_quant_policy)
            if new_resid is not None and not self._in_grad_retrace:
                self.qresid_updates[id(op)] = new_resid
            return out
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def apply_dispatch(self, op: DispatchOp, x):
        mesh = self.config.mesh
        if mesh is None or self.config.mp_axis not in mesh.axis_names:
            raise ValueError(
                f"{op.name}: dispatch requires a mesh with a "
                f"{self.config.mp_axis!r} axis (HetuConfig should have "
                "raised at construction)")
        if len(op.parts) != x.ndim:
            raise ValueError(
                f"{op.name}: parts {op.parts} does not match input rank "
                f"{x.ndim}")
        spec = op.partition_spec(mesh, self.config.dp_axis,
                                 self.config.mp_axis)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    # -- PS hooks (installed by their runtimes) -----------------------------
    def ps_push_pull(self, op, grad):
        """PS comm op inside the trace: capture the gradient as an extra
        program output; the host pushes it to the server post-step (the
        reference instead issues the RPC from the interpreter on the d2h
        stream, ParameterServerCommunicate.py:38-50)."""
        def f32(g):
            if hasattr(g, "dtype") and g.dtype != jnp.float32:
                return g.astype(jnp.float32)  # PS stores/accumulates f32
            return g

        from .ops.embedding import IndexedRows
        if isinstance(grad, IndexedRows):
            # hetukern rows-mode embedding grad: ids stay int, values f32
            self.ps_grad_outputs[id(op)] = IndexedRows(grad.rows,
                                                       f32(grad.grads))
            return None
        # a shared-table gradient arrives as a tuple of per-lookup row grads
        self.ps_grad_outputs[id(op)] = (
            tuple(f32(g) for g in grad) if isinstance(grad, tuple) else f32(grad))
        return None

    def ps_sparse_pull(self, op, vals):
        raise AssertionError(
            "ParameterServerSparsePullOp values are staged by the executor")

    # -- autodiff ----------------------------------------------------------
    def gradient_of(self, gctx: GradientContext, x: Op):
        key = id(gctx)
        if key not in self.grad_cache:
            xs = gctx.xs
            sub_topo = gctx.downstream_nodes(self.topo)
            base_env = self.env

            down_ids = {id(n) for n in sub_topo}

            def fwd(x_vals):
                # drop downstream nodes so they re-trace as functions of xs
                env2 = {k: v for k, v in base_env.items() if k not in down_ids}
                for n, v in zip(xs, x_vals):
                    env2[id(n)] = v
                sub_tc = TraceContext(self.config, self.topo, self.training,
                                      env2, self.rng_key, self.step,
                                      self.op_state_in)
                sub_tc._in_grad_retrace = True
                # the vjp re-trace must see the same poisoned op as the
                # primal trace, or grads would flow from clean values
                sub_tc.poison_scope = self.poison_scope
                for node in sub_topo:
                    # skip the gradient/comm/optimizer tail — only the forward
                    # path to the loss matters inside the vjp closure
                    if node.is_gradient or node.is_optimizer:
                        continue
                    if any(id(i) not in env2 for i in node.inputs):
                        continue
                    _eval_node(node, env2, sub_tc)
                loss_val = env2[id(gctx.loss)]
                return jnp.sum(loss_val)  # loss is scalar already in practice

            x_vals = [self.env[id(n)] for n in xs]
            grads = jax.grad(fwd)(x_vals)
            self.grad_cache[key] = {id(n): g for n, g in zip(xs, grads)}
        return self.grad_cache[key][id(x)]


def _eval_node(node: Op, env: dict, tc: TraceContext):
    """Evaluate one node into ``env`` (shared by main trace and vjp re-trace)."""
    if id(node) in env:
        return
    input_vals = [env[id(i)] for i in node.inputs]
    cdtype = tc.config.compute_dtype
    if cdtype != np.float32:
        # enforce the compute dtype at every op boundary: stateful ops
        # (batchnorm running stats) legitimately produce f32 and would
        # otherwise poison downstream matmuls back to full precision.
        # XLA elides the no-op casts.
        input_vals = [
            v.astype(cdtype)
            if (isinstance(v, jax.Array) or hasattr(v, "aval"))
            and jnp.issubdtype(getattr(v, "dtype", np.int32), jnp.floating)
            and v.dtype != cdtype else v
            for v in input_vals]
    if any(v is _PS_RESIDENT for v in input_vals):
        raise ValueError(
            f"{node.name} reads a PS-resident embedding table directly; only "
            "embedding_lookup_op / parameterServerSparsePull_op may touch "
            "PS-hosted tables (their rows are staged by the executor)")
    # every op's lowering runs under jax.named_scope(op.name): the HLO
    # metadata op_name path then carries graph-op identity, which is what
    # lets hetuprof attribute device-trace time back to Ops (and dump_hlo
    # readers navigate the fused program). Trace-time only — zero per-step
    # runtime cost, and backward ops inherit the scope through the vjp.
    if node.stateful:
        state_in = tc.op_state_in[id(node)]
        with jax.named_scope(_op_scope(node)):
            out, new_state = node.compute_stateful(input_vals, state_in, tc)
        # op state (running stats) keeps its own dtype across steps — under
        # bf16 compute the update must not silently downcast the f32 stats
        new_state = jax.tree.map(
            lambda new, old: new.astype(old.dtype)
            if hasattr(old, "dtype") and hasattr(new, "dtype")
            and new.dtype != old.dtype else new,
            new_state, state_in)
        if not tc._in_grad_retrace:
            tc.op_state_updates[id(node)] = new_state
        env[id(node)] = out
    else:
        with jax.named_scope(_op_scope(node)):
            env[id(node)] = node.compute(input_vals, tc)
    if tc.poison_scope is not None and _op_scope(node) == tc.poison_scope:
        # nan_op fault (HETU_FAULT_SPEC, test mode): poison exactly this
        # op's output so provenance can be proven to localize it
        out = env[id(node)]
        if hasattr(out, "dtype") and jnp.issubdtype(out.dtype,
                                                    jnp.floating):
            env[id(node)] = jnp.full_like(out, jnp.nan)


class SubExecutor:
    """One named evaluation target compiled into jitted programs
    (reference SubExecutor executor.py:769)."""

    def __init__(self, name: str, eval_nodes: list[Op], executor: "Executor"):
        self.name = name
        self.eval_nodes = eval_nodes
        self.executor = executor
        self.config = executor.config
        self.topo = find_topo_sort(eval_nodes)
        self.training = any(n.is_optimizer for n in self.topo)
        self.feed_nodes = [n for n in self.topo
                           if n.is_placeholder and getattr(n, "is_feed", False)]
        self.dataloader_nodes = [n for n in self.topo if n.is_dataloader]
        self.stateful_nodes = [n for n in self.topo if n.stateful]
        self.optimizer_nodes = [n for n in self.topo if n.is_optimizer]
        # hetuq: quantized-AllReduce ops appearing in this target's topo and
        # the subset carrying error-feedback residual state — the residuals
        # ride through the jitted step like optimizer slots
        _qids = {id(n) for n in getattr(executor, "qar_ops", ())}
        self.qar_nodes = [n for n in self.topo if id(n) in _qids]
        self.qresid_nodes = [n for n in self.qar_nodes
                             if id(n) in executor.state.get("qresid", {})]
        # finite-check + gated commit only makes sense where state commits
        self.anomaly_guard = self.training and self.config.anomaly_guard
        self._compiled: dict[tuple, Any] = {}
        self._last_call = None  # (jitted fn, args) of the latest run
        # hetuscope introspection (docs/OBSERVABILITY.md "numeric health"):
        # armed iff the Executor built an Introspector and this target
        # trains. Stats/poison variants of the step compile under distinct
        # cache keys; _base_sigs tracks the shape signatures alone so those
        # variants never read as recompile churn. _scope_meta is the
        # (topo-ordered scope keys, per-op input map) pair captured while
        # tracing a stats variant — what find_culprit walks.
        self.introspect = self.training and executor.introspector is not None
        self._base_sigs: set = set()
        self._replay_compiled: dict[tuple, Any] = {}
        self._scope_meta: Optional[tuple] = None
        # compiled-executable handles keyed by the jitted fn, so repeated
        # cost/memory/HLO queries re-lower once per signature, not per query
        self._exe_cache: dict[int, Any] = {}
        # device-side input double buffer: id(node) -> (host batch, device arr)
        self._dev_prefetch: dict[int, tuple] = {}
        # telemetry (docs/OBSERVABILITY.md): PS server-health poll cadence
        # and the last recorded per-phase wall times (graphboard's
        # render(..., timings=True) overlay reads these)
        self._tel_ps_every = max(1, int(os.environ.get(
            "HETU_TELEMETRY_PS_EVERY", "20")))
        self.last_phases: Optional[dict] = None
        self._tel_cp_cache: dict = {}   # hetutrail critical-path gauges
        self._tel_watch_cache: dict = {}   # hetuwatch residual gauges

        # -- PS bookkeeping (comm_mode PS/Hybrid) --------------------------
        ps = executor.ps_runtime
        self.ps_staged_ops = []    # lookup/sparse-pull ops fed by host pulls
        self.ps_sparse_vars = []   # PS-resident tables appearing in the topo
        self.ps_dense_vars = []    # PS-hosted dense params fed per step
        self.ps_comm_ops = []      # gradient push ops, in topo order
        if ps is not None:
            for n in self.topo:
                embed = getattr(n, "embed_node", None)
                if embed is not None and id(embed) in ps.params \
                        and ps.params[id(embed)].sparse:
                    self.ps_staged_ops.append(n)
                if isinstance(n, ParameterServerCommunicateOp) \
                        and getattr(n, "ps_param_node", None) is not None:
                    self.ps_comm_ops.append(n)
                if n.is_placeholder and id(n) in ps.params:
                    if ps.params[id(n)].sparse:
                        self.ps_sparse_vars.append(n)
                    else:
                        self.ps_dense_vars.append(n)
            for op in self.ps_staged_ops:
                idx_node = op.inputs[1]
                if not (idx_node in self.feed_nodes
                        or idx_node in self.dataloader_nodes):
                    raise ValueError(
                        f"PS-hosted lookup {op.name!r}: the index input "
                        f"{idx_node.name!r} must be a feed or dataloader "
                        "node (its value is needed host-side to pull rows)")
        # staged lookups grouped by table: a shared table (several lookup
        # ops) pulls the union of its indices once per step
        self._staged_by_table: dict[int, list] = {}
        for op in self.ps_staged_ops:
            self._staged_by_table.setdefault(id(op.embed_node), []).append(op)

        # -- device-resident datasets (TPU infeed design) -------------------
        # A small, sequential (no shuffle/func, drop_last) dataset uploads to
        # the device ONCE; the jitted step slices its batch with a traced
        # cursor. Replaces the reference's 3-deep pinned-buffer H2D ring
        # (dataloader.py:26-55) with zero per-step host->device traffic.
        self.resident_dl: dict[int, Any] = {}
        self._dl_cursor: dict[int, int] = {}
        limit = float(os.environ.get("HETU_DEVICE_DATA_MB", "1024")) * 1e6
        if executor.config.mesh is None:
            ps_idx = {id(op.inputs[1]) for op in self.ps_staged_ops}
            for n in self.dataloader_nodes:
                dl = getattr(n, "dataloaders", {}).get(self.name)
                if (dl is not None and dl.func is None and not dl.shuffle
                        and dl.drop_last and id(n) not in ps_idx
                        and dl._data.nbytes <= limit):
                    self.resident_dl[id(n)] = (
                        executor._prepare_input(dl._data, batch=False),
                        dl.batch_size, dl.batch_num)
        self.host_dl_nodes = [n for n in self.dataloader_nodes
                              if id(n) not in self.resident_dl]
        self.res_dl_nodes = [n for n in self.dataloader_nodes
                             if id(n) in self.resident_dl]

    # ------------------------------------------------------------------
    def _signature(self, feed_vals, batch_vals):
        def sig(v):
            if isinstance(v, SparseValue):
                return ("sparse", tuple(v.data.shape), v.nrow, v.ncol)
            return (tuple(v.shape), str(v.dtype))

        # host-side optimizer state (e.g. ReduceOnPlateau's current lr) is
        # baked into the trace as constants — key the cache on it so host
        # lr changes retrace instead of being silently ignored
        opt_tokens = tuple(n.optimizer.cache_token() for n in self.optimizer_nodes)
        return (tuple(sig(v) for v in feed_vals),
                tuple(sig(v) for v in batch_vals), opt_tokens)

    @staticmethod
    def _push_idx(op, staged_idx):
        """Index argument for one PS grad push: None (dense), one array
        (single lookup), or a tuple of per-lookup arrays (shared table —
        the runtime concatenates and dedup-sums, matching the reference's
        IndexedSlices accumulation)."""
        lks = getattr(op, "staged_lookups", None)
        if not lks:
            return None
        if len(lks) == 1:
            return staged_idx[id(lks[0])]
        return tuple(staged_idx[id(lk)] for lk in lks)

    def _host_value(self, node, feed_dict, batch_host):
        """Host-side numpy value of a feed/dataloader node (pre device_put)."""
        if node in feed_dict:
            v = feed_dict[node]
            if hasattr(v, "asnumpy"):
                v = v.asnumpy()
            return np.asarray(v)
        if id(node) in batch_host:
            return batch_host[id(node)]
        raise ValueError(f"no host value for {node.name!r}")

    def _build(self, introspect_now=False, poison_scope=None,
               donate_ok=True):
        """Build one jitted step variant. ``introspect_now`` fuses the
        hetuscope per-op/per-param reductions into the program and returns
        them as one extra output; ``poison_scope`` NaN-poisons that op's
        output inside the trace (the ``nan_op`` fault); ``donate_ok=False``
        builds the no-donation debug variant the provenance replay uses
        (inputs must survive the call)."""
        from ..telemetry import scope as _scope
        ex = self.executor
        param_nodes = ex.param_nodes
        pf_names = {id(n): f for n, f in zip(ex.param_nodes,
                                             ex._param_file_names())}
        topo = self.topo
        eval_nodes = self.eval_nodes
        training = self.training
        feed_nodes = self.feed_nodes
        dl_nodes = self.dataloader_nodes
        stateful_nodes = self.stateful_nodes
        opt_nodes = self.optimizer_nodes
        config = self.config

        ps_staged_ops = self.ps_staged_ops
        ps_sparse_vars = self.ps_sparse_vars
        ps_dense_vars = self.ps_dense_vars
        ps_comm_ops = self.ps_comm_ops
        qresid_nodes = self.qresid_nodes

        host_dl_nodes = self.host_dl_nodes
        res_dl_specs = [(n,) + self.resident_dl[id(n)][1:]
                        for n in self.res_dl_nodes]

        compute_dtype = config.compute_dtype

        def cast_in(v):
            """Cast a float input to the compute dtype (bf16 mixed precision);
            master params stay f32 outside ``env``."""
            if compute_dtype == np.float32:
                return v
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(compute_dtype)
            return v

        guard = self.anomaly_guard

        def step_fn(params_t, slots_t, opstate_t, rng_root, step, feeds_t,
                    batches_t, dl_cursors_t, res_data_t, ps_staged_t,
                    ps_dense_t, inject_nan_t, qresid_t):
            # fold the step into the rng INSIDE the trace: doing it eagerly
            # costs ~5 dispatched host ops per step (free here)
            rng = jax.random.fold_in(rng_root, step)
            env: dict[int, Any] = {}
            masters: dict[int, Any] = {}
            for node, val in zip(param_nodes, params_t):
                env[id(node)] = cast_in(val)
                masters[id(node)] = val
            for node, val in zip(feed_nodes, feeds_t):
                env[id(node)] = cast_in(val)
            for node, val in zip(host_dl_nodes, batches_t):
                env[id(node)] = cast_in(val)
            # device-resident datasets: slice the batch on device. The data
            # rides in as an ARGUMENT, not a closure constant — constants are
            # serialized into the (size-limited) remote compile request.
            for (node, bs, bnum), data, cur in zip(res_dl_specs, res_data_t,
                                                   dl_cursors_t):
                # named like its dataloader node so hetuprof attributes the
                # on-device batch slice instead of an anonymous dynamic_slice
                with jax.named_scope(_op_scope(node)):
                    start = (cur % bnum) * bs
                    batch = jax.lax.dynamic_slice_in_dim(data, start, bs,
                                                         axis=0)
                    env[id(node)] = cast_in(batch)
            # PS-resident embeddings: staged rows stand in for the lookup
            # output; the table itself never exists on device
            for node, val in zip(ps_staged_ops, ps_staged_t):
                env[id(node)] = cast_in(val)
            for node in ps_sparse_vars:
                env[id(node)] = _PS_RESIDENT
            for node, val in zip(ps_dense_vars, ps_dense_t):
                env[id(node)] = cast_in(val)
            op_state_in = {id(n): s for n, s in zip(stateful_nodes, opstate_t)}
            tc = TraceContext(config, topo, training, env, rng, step, op_state_in)
            tc.master_params = masters
            tc.poison_scope = poison_scope
            tc.qresid_in = {id(n): v for n, v in zip(qresid_nodes, qresid_t)}
            slots_in = {id(n): s for n, s in zip(opt_nodes, slots_t)}
            for node in topo:
                if id(node) in env:
                    continue
                if node.is_placeholder:
                    raise ValueError(f"Placeholder {node.name} was not fed")
                if node.is_optimizer:
                    # the phase scope is the parent, the op stays one
                    # segment below it (hetuprof's scope_of join); forward
                    # and backward carry jax's own jvp(/transpose( marks,
                    # GradientOp being a jax.vjp
                    with jax.named_scope(_tr.SCOPE_OPT), \
                            jax.named_scope(_op_scope(node)):
                        node.apply_updates(env, slots_in[id(node)], tc)
                    env[id(node)] = _NO_OUTPUT
                    continue
                _eval_node(node, env, tc)
            outputs = tuple(
                jnp.zeros(()) if (env[id(n)] is _NO_OUTPUT or env[id(n)] is None)
                else env[id(n)]
                for n in eval_nodes)
            new_params = tuple(tc.param_updates.get(id(n), masters[id(n)])
                               for n in param_nodes)
            new_slots = tuple(tc.slot_updates.get(id(n), slots_in[id(n)])
                              for n in opt_nodes)
            new_opstate = tuple(tc.op_state_updates.get(id(n), op_state_in[id(n)])
                                for n in stateful_nodes)
            ps_grads = tuple(tc.ps_grad_outputs[id(op)] for op in ps_comm_ops)
            new_qresid = tuple(tc.qresid_updates.get(id(n), tc.qresid_in[id(n)])
                               for n in qresid_nodes)
            scope_stats = ()
            if introspect_now:
                # -- hetuscope in-graph stats (one extra fetch) ------------
                # Per-op activation stats for every float-typed value in
                # the env (activations, grads, fed inputs) keyed by the
                # same named_scope identity hetuprof joins on, plus
                # per-parameter grad norms and update/param ratios.
                # Computed BEFORE the guard gating so the table describes
                # the ATTEMPTED update — exactly what a NaN post-mortem
                # needs. XLA fuses the reductions into the step program.
                key_by_id: dict[int, str] = {}
                used: set[str] = set()
                op_entries = []
                for node in topo:
                    v = env.get(id(node))
                    if node.is_optimizer or v is None or v is _NO_OUTPUT \
                            or v is _PS_RESIDENT or isinstance(v, tuple):
                        continue
                    if not (hasattr(v, "dtype")
                            and jnp.issubdtype(v.dtype, jnp.floating)) \
                            or not getattr(v, "size", 0):
                        continue
                    k = _op_scope(node)
                    if k in used:   # duplicate user op names stay distinct
                        k = f"{k}__{node.id}"
                    used.add(k)
                    key_by_id[id(node)] = k
                    op_entries.append((k, v))
                param_entries = []
                for onode in opt_nodes:
                    for var, gnode in zip(onode.vars, onode.inputs):
                        g = env.get(id(gnode))
                        if g is None or isinstance(g, tuple) \
                                or not hasattr(g, "dtype"):
                            continue   # PS-managed: server owns the update
                        param_entries.append(
                            (pf_names.get(id(var), var.name), g,
                             masters.get(id(var)),
                             tc.param_updates.get(id(var))))
                loss_val = None
                for n, v in zip(eval_nodes, outputs):
                    if n.is_optimizer:
                        continue
                    if hasattr(v, "dtype") \
                            and jnp.issubdtype(v.dtype, jnp.floating) \
                            and getattr(v, "size", 0) == 1:
                        loss_val = v
                        break
                # stats pack into ONE stacked vector (the single extra
                # fetch); the slot spec + topo order + input map are
                # trace-time metadata, captured host-side for find_culprit
                spec, scope_stats = _scope.traced_stats(
                    op_entries, param_entries, loss_val,
                    tc.grad_global_norm)
                self._scope_meta = (
                    [key_by_id[id(n)] for n in topo if id(n) in key_by_id],
                    {key_by_id[id(n)]: [key_by_id[id(i)] for i in n.inputs
                                        if id(i) in key_by_id]
                     for n in topo if id(n) in key_by_id},
                    spec)
            finite = jnp.bool_(True)
            if guard:
                # -- anomaly guard (resilience layer) ----------------------
                # inject_nan_t is the deterministic fault hook: poison the
                # update BEFORE the finite-check, so the guard path is
                # exercised end to end (a scalar arg — no retrace per step)
                def is_float(v):
                    return (hasattr(v, "dtype")
                            and jnp.issubdtype(v.dtype, jnp.floating))

                new_params = tuple(
                    jnp.where(inject_nan_t, jnp.full_like(p, jnp.nan), p)
                    if is_float(p) else p for p in new_params)
                checks = [jnp.all(jnp.isfinite(v)) for v in outputs
                          if is_float(v)]
                checks += [jnp.all(jnp.isfinite(p)) for p in new_params
                           if is_float(p)]
                for s in new_slots + new_opstate:
                    checks += [jnp.all(jnp.isfinite(l))
                               for l in jax.tree.leaves(s) if is_float(l)]
                if checks:
                    finite = jnp.all(jnp.stack(checks))

                # gate the whole commit: an anomalous step leaves params,
                # slots and op state bit-identical to pre-step
                def keep(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(finite, a, b), new, old)

                new_params = tuple(
                    jnp.where(finite, p, masters[id(n)])
                    for p, n in zip(new_params, param_nodes))
                new_slots = tuple(keep(s, slots_in[id(n)])
                                  for s, n in zip(new_slots, opt_nodes))
                new_opstate = tuple(
                    keep(s, op_state_in[id(n)])
                    for s, n in zip(new_opstate, stateful_nodes))
                # error-feedback residuals roll back with the params: a
                # rolled-back step must not leave a phantom residual behind
                new_qresid = tuple(
                    jnp.where(finite, a, b)
                    for a, b in zip(new_qresid, qresid_t))
            return outputs, new_params, new_slots, new_opstate, ps_grads, \
                new_qresid, finite, scope_stats

        # HETU_NO_DONATE=1: bisect knob — donation changes XLA's buffer
        # assignment, so a suspect step can be run without it.
        # qresid (arg 12) donates like the state it is: the hetuq residuals
        # are full-size param copies, and without donation each step would
        # transiently double their HBM footprint
        donate = ((0, 1, 2, 12) if training and donate_ok
                  and os.environ.get("HETU_NO_DONATE") != "1" else ())
        return jax.jit(step_fn, donate_argnums=donate)

    def _kern_spmd(self) -> bool:
        """Is this subexecutor's program a GSPMD multi-device program? A
        bare pallas_call inside one has no SPMD partitioning rule, so the
        kernel tier's eligibility declines under this scope
        (registry.in_spmd_scope; per-shard shard_map wrapping is the
        documented follow-up in docs/KERNELS.md)."""
        mesh = self.config.mesh
        return mesh is not None and mesh.size > 1

    def _record_telemetry(self, tel, step, stamps, compiled_now, feed_vals,
                          batch_vals):
        """Per-step telemetry: phase spans (trace mode), step metrics and
        the JSONL step record; PS server health on its poll cadence. Runs
        only when telemetry is active, inside ``hetu.poststep`` — the
        ``perf_counter`` stamps are the ones the step's ``hetu.*`` spans
        took (``tracing.span``), and this emits everything post-hoc."""
        ex = self.executor
        t_end = time.perf_counter()
        t0 = stamps[_tr.STEP][0]
        t_pre = stamps[_tr.PS_PULL][1]
        t_c0, t_c1 = stamps[_tr.BUILD]
        t_d0, t_d1 = stamps[_tr.DISPATCH]
        ps_comm_ms = ps_pull_ms = ps_push_ms = None
        if ex.ps_runtime is not None:
            # the two PS legs separately (pull wait in prestep, push in
            # poststep): what hetutrail's critical path decomposes
            ps_pull_ms, ps_push_ms = (
                (stamps[k][1] - stamps[k][0]) * 1e3
                for k in (_tr.PS_PULL, _tr.PS_PUSH))
            ps_comm_ms = ps_pull_ms + ps_push_ms
        step_ms = (t_end - t0) * 1e3
        phases = {"prestep_ms": (t_pre - t0) * 1e3,
                  "dispatch_ms": (t_d1 - t_d0) * 1e3,
                  "poststep_ms": (t_end - t_d1) * 1e3}
        tel.record_compiles()
        t_x = t_c1      # where the step's compile ends and its compute starts
        if compiled_now:
            # jax.jit compiles lazily: the step-fn build is `hetu.build`, and
            # the first dispatch carries jax's trace, lowering and compile
            # (or cache read). The compile log has them by program
            # (tracing.compile_log); their union inside the dispatch's stamps
            # is the rest of this step's compile
            inside = _tr.clip_spans(
                _tr.compile_spans(_tr.compile_log(since=t_d0)), t_d0, t_d1)
            phases["compile_ms"] = (
                t_c1 - t_c0 + _tr.span_union(inside)) * 1e3
            t_x = max([t_d0] + [e for _, e in inside])
        if ps_comm_ms is not None:
            phases["ps_comm_ms"] = ps_comm_ms
            phases["ps_pull_ms"] = ps_pull_ms
            phases["ps_push_ms"] = ps_push_ms
        self.last_phases = {"step_ms": step_ms, "step": int(step), **phases}
        tracer = tel.tracer
        label = "step" if self.training else "eval"
        if tracer is not None:
            tracer.complete(f"{label}:{self.name}", t0, t_end,
                            args={"step": int(step)})
            tracer.complete("feed", t0, t_pre)
            if compiled_now:
                tracer.complete("compile", t_c0, t_x)
            # on a compiled_now step `compute` is the dispatch less the
            # compile: from the last compiled program's end
            tracer.complete("compute", t_x if compiled_now else t_d0, t_d1)
            tracer.complete("poststep", t_d1, t_end)
        tm = ex._tel_metrics
        if not self.training:
            tm["eval_ms"].observe(step_ms)
            return
        tm["step_ms"].observe(step_ms)
        tm["steps"].inc()
        bs = None
        for v in list(batch_vals) + list(feed_vals):
            shape = getattr(v, "shape", None)
            if shape:
                bs = int(shape[0])
                break
        if bs is None and self.res_dl_nodes:
            bs = self.resident_dl[id(self.res_dl_nodes[0])][1]
        if bs:
            tm["examples"].inc(bs)
        if ps_comm_ms is not None and step_ms > 0:
            # critical-path PS RPC share of the step (staging pulls + push
            # issue). The gauge exists only for PS/Hybrid runs; AllReduce
            # comm lives inside the XLA program — hetuprof --attr separates
            # it offline from the device trace (docs/PROFILING.md).
            tel.metrics.gauge("hetu_comm_fraction").set(
                min(1.0, ps_comm_ms / step_ms))
        # hetutrail critical path (docs/OBSERVABILITY.md pillar 5): the
        # blocking chain per step as hetu_critical_path_ms{leg=...} gauges
        # plus hetu_cp_fraction (dominant leg's share) — the cost-model
        # calibration signal hetuprof's cp_fraction column reads back
        from ..telemetry import trail as _trail_mod
        _trail_mod.export_critical_path(
            tel.metrics, _trail_mod.step_legs(phases),
            cache=self._tel_cp_cache)
        # hetuwatch (pillar 6): judge this step against the adopted plan's
        # stamped prediction on the watch cadence. None when unarmed — the
        # only cost the default run pays is this attribute check.
        # compile steps are excluded (the step_phase_means convention):
        # trace+compile wall time is warm-up, not plan divergence
        pw = ex.plan_watch
        if pw is not None and not compiled_now and step % pw.every == 0:
            self._watch_observe(tel, ex, pw, step, step_ms, phases)
        if compiled_now:
            tm["compiles"].inc()
            # recompile churn counts distinct SHAPE signatures, not the
            # hetuscope cadence/poison variants of the same signature
            if len(self._base_sigs) > 1:
                tm["recompiles"].inc()
            mon = ex._tel_recompile_mon
            if mon is not None:
                for f in mon.check():
                    # signature-churn diagnosis from the existing Tier B
                    # RecompileMonitor, surfaced as a telemetry event
                    tel.event("recompile_budget", sub=self.name,
                              message=f.message)
            cost = self.last_cost_analysis() or {}
            if cost.get("flops"):
                tm["flops"].set(float(cost["flops"]))
            # 6ND companion denominator (docs/ROOFLINE.md): 6·N·tokens,
            # tokens from the first integer-typed 2-D feed (token ids) or
            # the batch size. hetutop shows MFU under BOTH this and the
            # measured cost-analysis flops (which include attention).
            tokens = None
            for v in list(feed_vals) + list(batch_vals):
                shape = getattr(v, "shape", None)
                dt = getattr(v, "dtype", None)
                if shape is not None and len(shape) >= 2 and dt is not None \
                        and jnp.issubdtype(dt, jnp.integer):
                    tokens = int(shape[0]) * int(shape[1])
                    break
            if tokens is None:
                tokens = bs
            if tokens and ex.n_params_total:
                tel.metrics.gauge("hetu_flops_per_step_6nd").set(
                    6.0 * ex.n_params_total * tokens)
            # HBM accounting of the program just compiled, next to the live
            # allocator gauge polled below — predicted vs resident
            mem = self.last_memory_analysis()
            if mem:
                for k, v in mem.items():
                    tel.metrics.gauge(f"hetu_hbm_{k}").set(float(v))
        tel.step_record(self.name, step, step_ms, phases=phases)
        ps = ex.ps_runtime
        if step % self._tel_ps_every == 0:
            live = _device_live_bytes()
            if live is not None:
                tel.metrics.gauge("hetu_hbm_live_bytes").set(live)
        if ps is not None and step % self._tel_ps_every == 0:
            for row in ps.telemetry_stats():
                tel.record(**row)

    # -- hetuwatch (docs/OBSERVABILITY.md pillar 6) -------------------------
    def _watch_observe(self, tel, ex, pw, step, step_ms, phases):
        """One cadence observation of the plan-divergence sentinel: fold
        this step's measured legs into the residual windows, export the
        residual/divergence gauges, stream the kind:"watch" JSONL row
        (what ``hetulint --plan --calibrate`` and ``hetuprof --gate`` read
        back), and route any latched events through the resilience bus.
        Runs on the watch cadence only; never raises — the sentinel must
        not take the step down with it."""
        from ..resilience import _flight_flush, _incident, _tel_event
        from ..telemetry import trail as _trail_mod
        from ..telemetry import watch as _watch_mod
        try:
            if pw.families is None:
                # op-family -> leg identities (the roofline's op_family
                # naming): every traced family executes inside dispatch =
                # the compute leg; PS-staged pulls and gradient pushes own
                # the boundary legs. Built once, on the first observation.
                from ..telemetry.profiler import op_family
                fams = {}
                pull = {id(n) for n in self.ps_staged_ops}
                push = {id(n) for n in self.ps_comm_ops}
                for n in self.topo:
                    if not n.inputs:   # placeholders aren't a family
                        continue
                    leg = ("ps_pull" if id(n) in pull
                           else "ps_push" if id(n) in push else "compute")
                    fams.setdefault(op_family(n.name), leg)
                pw.families = fams
            wv = getattr(getattr(ex, "elastic", None), "world_version",
                         None)
            row, events = pw.observe(step, phases=phases, step_ms=step_ms,
                                     world_version=wv)
            _watch_mod.export_watch(tel.metrics, pw._ewma,
                                    row.get("divergence"),
                                    cache=self._tel_watch_cache)
            tel.record("watch", **row)
            # hetupilot rides the same residual stream the row exports —
            # the controller's measurement windows ARE the watch windows
            pilot = getattr(ex, "pilot", None)
            if pilot is not None:
                pilot.feed_row(row)
            for e in events:
                name = e.pop("name")
                if name == "plan_divergence":
                    # name the blocking server+param via hetutrail's span
                    # join (rare-event path; requires HETU_TRAIL_DIR).
                    # This step's own spans may still be in the native
                    # ring, so fall back one step — the breach is K
                    # windows old by the time the latch fires.
                    trail_dir = _trail_mod.armed()
                    if trail_dir and e.get("leg", "").startswith("ps_"):
                        loaded = _trail_mod.load_dir(trail_dir)
                        joined, _rate = _trail_mod.join_spans(
                            loaded["client"], loaded["server"])
                        for s in (int(step), int(step) - 1):
                            by_server, by_tensor = \
                                _trail_mod._ps_attribution(joined, s,
                                                           tel.rank)
                            if by_server:
                                e["server"] = max(by_server,
                                                  key=by_server.get)
                                if by_tensor:
                                    e["param"] = max(by_tensor,
                                                     key=by_tensor.get)
                                break
                    rec = _watch_mod.recommend(pw.plan, e.get("leg", ""),
                                               e.get("ratio", 0.0))
                    e["recommendation"] = rec["message"]
                    # the bounded plan delta as the suppressible finding
                    # shape hetulint emits (advisory — never actuated here;
                    # the pilot actuates at the NEXT step boundary, inside
                    # the elastic two-phase barrier)
                    tel.record("finding", **rec)
                    if pilot is not None and rec.get("delta") is not None:
                        pilot.feed_recommendation(rec["delta"], dict(e))
                _tel_event(name, sub=self.name, **e)
                if pilot is not None:
                    pilot.feed_event(name, e)
                if name == "slo_breach":
                    # the flight ring holds the steps AROUND the breach —
                    # flush it while they are still in the window
                    _flight_flush(f"slo_breach:{e.get('slo')}")
                    _incident("slo_breach", step=step, slo=e.get("slo"),
                              value=e.get("value"))
        except Exception:  # noqa: BLE001 — sentinel must never kill a step
            pass

    # -- hetuscope helpers --------------------------------------------------
    def _default_poison_scope(self) -> Optional[str]:
        """Target of a ``nan_op@step`` fault with no explicit op name: the
        first computing node in topological order."""
        for n in self.topo:
            if n.inputs and not n.is_optimizer:
                return _op_scope(n)
        return None

    def _host_lr(self) -> Optional[float]:
        """Best-effort host-visible learning rate for the flight record
        (None for purely traced schedules)."""
        for n in self.optimizer_nodes:
            lr = n.optimizer.learning_rate
            try:
                return float(lr.get()) if hasattr(lr, "get") else float(lr)
            except (TypeError, ValueError):
                continue
        return None

    def _flight_cursors(self) -> Optional[dict]:
        """Dataloader positions (host cursors + device-resident cursors)
        for the flight record — with the batch crc32 and the step's RNG
        fold, enough to re-point a replay at the failing batch."""
        out = {}
        for n in self.host_dl_nodes:
            dl = getattr(n, "dataloaders", {}).get(self.name)
            cur = getattr(dl, "_cursor", None)
            if cur is not None:
                out[n.name] = int(cur)
        for n in self.res_dl_nodes:
            out[n.name] = int(self._dl_cursor.get(id(n), 0))
        return out or None

    def _loss_at_trip(self, outputs) -> Optional[float]:
        """The first scalar float eval output (the loss, by convention) as
        a host float — read only on a guard trip, where the step already
        synced on the finite flag."""
        for n, v in zip(self.eval_nodes, outputs):
            if n.is_optimizer:
                continue
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating) \
                    and getattr(v, "size", 0) == 1:
                return float(np.asarray(v))
        return None

    def _provenance_replay(self, step, base_key, feed_vals, batch_vals,
                           dl_cursors, res_data, ps_staged_vals,
                           ps_dense_vals, inject_nan, poison_scope):
        """Debug sub-executor for NaN/Inf provenance: re-run the failing
        step bit-identically — the guard's gated commit left params/slots/
        op-state at their pre-step values, the step number re-seeds the
        same RNG fold, and the feed/batch device arrays were not donated —
        through a no-donation stats variant of the same program, then
        localize the first op (topological order) that emitted non-finite
        values. Compile cost is paid once per signature, only after a
        trip."""
        ex = self.executor
        rkey = base_key + (poison_scope,)
        fn = self._replay_compiled.get(rkey)
        if fn is None:
            fn = self._build(introspect_now=True, poison_scope=poison_scope,
                             donate_ok=False)
            self._replay_compiled[rkey] = fn
        params_t = tuple(ex.state["params"][id(n)] for n in ex.param_nodes)
        slots_t = tuple(ex.state["slots"][id(n)]
                        for n in self.optimizer_nodes)
        opstate_t = tuple(ex.state["op_state"][id(n)]
                          for n in self.stateful_nodes)
        args = (params_t, slots_t, opstate_t, ex.rng_root, np.int32(step),
                tuple(feed_vals), tuple(batch_vals), tuple(dl_cursors),
                res_data, tuple(ps_staged_vals), tuple(ps_dense_vals),
                np.bool_(inject_nan),
                tuple(ex.state["qresid"][id(n)] for n in self.qresid_nodes))
        from ..telemetry import scope as _scope
        from ..kernels import registry as _kreg
        with _kreg.active(self.config.kernels, spmd=self._kern_spmd()):
            *_rest, stats_t = fn(*args)
        order, inputs_map, spec = self._scope_meta
        stats = _scope.host_stats(spec, stats_t)
        return _scope.find_culprit(order, inputs_map, stats, step)

    def _lowered(self):
        """Re-lower the latest executed step (hits the compilation cache)."""
        if self._last_call is None:
            return None
        fn, args = self._last_call
        from ..kernels import registry as _kreg
        with _kreg.active(self.config.kernels, spmd=self._kern_spmd()):
            return fn.lower(*args)

    def _executable(self):
        """Compiled executable of the latest executed step, cached per
        jitted program: ``last_cost_analysis``/``last_memory_analysis``/
        ``dump_hlo(stage="optimized")`` used to re-lower + re-look-up the
        compile cache on EVERY query — cache-hitting but not free (a
        whole-program re-trace each time); now one fetch per signature."""
        if self._last_call is None:
            return None
        fn, args = self._last_call
        exe = self._exe_cache.get(id(fn))
        if exe is None:
            from ..kernels import registry as _kreg
            with _kreg.active(self.config.kernels, spmd=self._kern_spmd()):
                exe = fn.lower(*args).compile()
            self._exe_cache[id(fn)] = exe
        return exe

    def last_cost_analysis(self):
        """XLA cost analysis (flops etc.) of the latest executed step, for
        MFU reporting and the Tier B lints (reaches the compilation cache —
        no recompile): a dict, or None when nothing has run or the backend
        exposes no analysis."""
        try:
            exe = self._executable()
            ca = None if exe is None else exe.cost_analysis()
        except Exception:  # noqa: BLE001 — diagnostics only
            return None
        return ca if isinstance(ca, dict) else None

    def last_memory_analysis(self) -> Optional[dict]:
        """HBM accounting of the latest executed step program as a plain
        dict (``argument/output/temp/alias/generated_code`` bytes plus the
        derived ``peak_bytes`` = args + out + temp − alias, the same formula
        as the AOT HBM gate in ``__graft_entry__.aot_memory_check``), from
        the same cached compiled handle as :meth:`last_cost_analysis`.
        None when nothing has run or the backend exposes no analysis."""
        try:
            exe = self._executable()
            ma = None if exe is None else exe.memory_analysis()
        except Exception:  # noqa: BLE001 — diagnostics only
            return None
        if ma is None:
            return None
        out = {}
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
            out[field.replace("_size_in_bytes", "_bytes")] = \
                int(getattr(ma, field, 0) or 0)
        out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"] - out["alias_bytes"])
        return out

    def dump_hlo(self, path=None, stage="stablehlo"):
        """The compiled program of the latest executed step as text — the
        whole subexecutor is ONE XLA program, so this is the full fused
        truth of what runs per step (the deep-debug complement to
        graphboard's op-level topo view). ``stage``: "stablehlo" (lowered,
        pre-optimization) or "optimized" (post-XLA-passes HLO, with fusion
        decisions and layouts). Returns the text; also writes it when
        ``path`` is given."""
        if stage not in ("stablehlo", "optimized"):
            raise ValueError(f"stage must be 'stablehlo' or 'optimized', "
                             f"got {stage!r}")
        if stage == "optimized":
            exe = self._executable()
            text = None if exe is None else exe.as_text()
        else:
            lowered = self._lowered()
            text = None if lowered is None else lowered.as_text()
        if text is None:
            return None
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    # ------------------------------------------------------------------
    def run(self, feed_dict=None, convert_to_numpy_ret_vals=False,
            eval_node_list=None):
        """One step. Every phase is one ``hetu.*`` span of
        ``telemetry/tracing.py``'s vocabulary, a child of ``hetu_step``, in
        whatever jax.profiler capture is open; the ``perf_counter`` stamps
        telemetry, hetutrail and hetuscope read are taken by the same spans,
        and only when one of them is on."""
        ex = self.executor
        tel = ex.telemetry   # None when telemetry is off (the only check)
        intro = ex.introspector if self.introspect else None
        stamps = {} if tel is not None or intro is not None else None
        step = ex.state["step"]
        if ex.xla_window is not None and self.training:
            # env-gated deep dive: HETU_XLA_TRACE=dir[:start[:n]] opens a
            # bounded jax.profiler window around the configured steps,
            # before the step's own span so the capture holds it whole
            ex.xla_window.on_step(step)
        with _tr.step_span(step, stamps):
            return self._run_step(ex, tel, intro, stamps, step, feed_dict,
                                  convert_to_numpy_ret_vals, eval_node_list)

    def _run_step(self, ex, tel, intro, stamps, step, feed_dict,
                  convert_to_numpy_ret_vals, eval_node_list):
        """The body of ``run``, inside its ``hetu_step`` span."""
        with _tr.span(_tr.BOUNDARY, stamps):
            # resilience supervisor (watchdog beat, host fault injection);
            # training targets only — an eval pass is not a supervised step
            sup = getattr(ex, "supervisor", None) if self.training else None
            if sup is not None:
                sup.pre_step(ex, self, step)
            # hetu-elastic: pending-resize check AFTER fault injection (a
            # ps_join fault proposes the resize this same boundary commits)
            ela = getattr(ex, "elastic", None) if self.training else None
            if ela is not None:
                ela.step_boundary(self, step)
            # hetupilot actuation/verdict point, AFTER the elastic agent's
            # own commit (a pilot barrier must never race a real pending
            # resize). An actuation rebuilds ex.subexecutors: this (stale)
            # instance delegates the step to its replacement, which
            # re-enters this hook idempotently at the same step.
            pil = getattr(ex, "pilot", None) if self.training else None
            if pil is not None:
                pil.step_boundary(self, step)
                fresh = ex.subexecutors.get(self.name)
                if fresh is not None and fresh is not self:
                    return fresh.run(
                        feed_dict=feed_dict,
                        convert_to_numpy_ret_vals=convert_to_numpy_ret_vals,
                        eval_node_list=eval_node_list)
        feed_dict = feed_dict or {}
        with _tr.span(_tr.FEED, stamps):
            feed_vals = []
            for node in self.feed_nodes:
                if node not in feed_dict:
                    raise ValueError(
                        f"Missing feed for placeholder {node.name!r}")
                feed_vals.append(ex._prepare_input(
                    feed_dict[node], batch=getattr(node, "batch", True)))
        with _tr.span(_tr.DL_WAIT, stamps):
            batch_host = {}
            batch_vals = []
            for n in self.host_dl_nodes:
                hv = n.get_batch(self.name)
                pf = self._dev_prefetch.pop(id(n), None)
                # identity check: get_batch returns the exact peeked object
                # when the prefetch ran, so a hit means the device_put
                # already happened
                dv = pf[1] if pf is not None and pf[0] is hv \
                    else ex._prepare_input(hv)
                batch_host[id(n)] = np.asarray(hv)
                batch_vals.append(dv)
            dl_cursors = []
            for n in self.res_dl_nodes:
                cur = self._dl_cursor.get(id(n), 0)
                dl_cursors.append(np.int32(cur))
                self._dl_cursor[id(n)] = cur + 1

        # -- PS pre-step: pull this batch's embedding rows ------------------
        # Lookups are grouped by table: a table feeding several lookup ops
        # (shared CTR embeddings) pulls the UNION of its row indices once,
        # then distributes rows to each lookup — one RPC instead of k.
        # The pull wait and the push leg are two spans: hetutrail's critical
        # path needs to know WHICH PS leg blocked, not just the total.
        ps = ex.ps_runtime
        with _tr.span(_tr.PS_PULL, stamps):
            staged_idx: dict[int, np.ndarray] = {}
            staged_rows: dict[int, np.ndarray] = {}
            for tid, ops in self._staged_by_table.items():
                p = ps.params[tid]
                for op in ops:
                    staged_idx[id(op)] = self._host_value(
                        op.inputs[1], feed_dict, batch_host)
                if len(ops) == 1:
                    op = ops[0]
                    idx = staged_idx[id(op)]
                    rows = (ps.take_prefetched(id(op), idx)
                            if ps.async_enabled else None)
                    if rows is None:
                        rows = ps.stage_lookup(p, idx)
                    staged_rows[id(op)] = rows
                else:
                    flat = [np.ascontiguousarray(staged_idx[id(op)],
                                                 np.int64).ravel()
                            for op in ops]
                    union = np.unique(np.concatenate(flat))
                    # union prefetch (keyed by table): issued post-step from
                    # the peeked next batches, consumed here when they match
                    urows = (ps.take_prefetched(tid, union)
                             if ps.async_enabled else None)
                    if urows is None:
                        urows = ps.stage_lookup(p, union)      # (U, *tail)
                    tail = tuple(p.shape[1:])
                    for op, f in zip(ops, flat):
                        pos = np.searchsorted(union, f)
                        staged_rows[id(op)] = urows[pos].reshape(
                            tuple(np.shape(staged_idx[id(op)])) + tail)
            ps_staged_vals = [ex._prepare_input(staged_rows[id(op)])
                              for op in self.ps_staged_ops]
            ps_dense_vals = []
            for n in self.ps_dense_vars:
                p = ps.params[id(n)]
                ps.wait_dense(p)   # async DDPushPull updates host_value
                ps_dense_vals.append(
                    ex._prepare_input(p.host_value, batch=False))

        with _tr.span(_tr.BUILD, stamps) as build_span:
            # hetuscope: cadence-gated stats variant + nan_op fault
            # poisoning. Variants key the compile cache alongside the shape
            # signature; _base_sigs keeps recompile accounting blind to them.
            introspect_now = intro is not None and step % intro.cadence == 0
            poison_scope = None
            if sup is not None and hasattr(sup, "poison_op"):
                p = sup.poison_op(step)
                if p is not None:
                    poison_scope = p or self._default_poison_scope()

            base_key = self._signature(feed_vals, batch_vals) + (
                tuple(tuple(v.shape) for v in ps_staged_vals),)
            key = base_key + (introspect_now, poison_scope)
            fn = self._compiled.get(key)
            compiled_now = fn is None
            if compiled_now:
                build_span.set_metadata(compiled=1)
                fn = self._build(introspect_now=introspect_now,
                                 poison_scope=poison_scope)
                self._compiled[key] = fn
            self._base_sigs.add(base_key)

        with _tr.span(_tr.DISPATCH, stamps):
            params_t = tuple(ex.state["params"][id(n)]
                             for n in ex.param_nodes)
            slots_t = tuple(ex.state["slots"][id(n)]
                            for n in self.optimizer_nodes)
            opstate_t = tuple(ex.state["op_state"][id(n)]
                              for n in self.stateful_nodes)
            qresid_t = tuple(ex.state["qresid"][id(n)]
                             for n in self.qresid_nodes)
            res_data = tuple(self.resident_dl[id(n)][0]
                             for n in self.res_dl_nodes)
            inject_nan = bool(self.anomaly_guard and sup is not None
                              and sup.inject_nan(step))
            args = (params_t, slots_t, opstate_t, ex.rng_root,
                    np.int32(step), tuple(feed_vals), tuple(batch_vals),
                    tuple(dl_cursors), res_data, tuple(ps_staged_vals),
                    tuple(ps_dense_vals), np.bool_(inject_nan), qresid_t)
            self._last_call = (fn, args)
            # hetukern: scope the kernel dispatch mode around the call — jit
            # traces lazily, so the trace (where dispatch decisions live)
            # runs under this scope; on cache-hit steps the context is a
            # ~µs no-op
            from ..kernels import registry as _kreg
            with _kreg.active(self.config.kernels, spmd=self._kern_spmd()):
                outputs, new_params, new_slots, new_opstate, ps_grads, \
                    qresid_out, finite_t, scope_stats_t = fn(*args)

        # -- device-side input prefetch: enqueue batch N+1's device_put now,
        # so its H2D transfer overlaps this step's compute (the reference's
        # 3-deep pinned ring + h2d stream, dataloader.py:26-55)
        with _tr.span(_tr.PREFETCH, stamps):
            for n in self.host_dl_nodes:
                if hasattr(n, "peek_batch"):
                    nxt = n.peek_batch(self.name)
                    self._dev_prefetch[id(n)] = (nxt,
                                                 ex._prepare_input(nxt))

        # -- PS post-step: push gradients (reference push/pull, ASP/BSP) ----
        with _tr.span(_tr.PS_PUSH, stamps):
            if ps is not None and ps.async_enabled:
                # async push: the device sync (np.asarray) happens on the
                # push thread, off the critical path
                items = []
                for op, grad in zip(self.ps_comm_ops, ps_grads):
                    p = ps.params[id(op.ps_param_node)]
                    idx = self._push_idx(op, staged_idx)
                    items.append((p, grad, idx))
                if items:
                    ps.push_grads_async(items, step)
                # prefetch pulls for batch N+1 (dataloader-fed lookups
                # only): issued now, so under ASP they overlap this step's
                # compute and its pushes — the reference's prefetch-stream
                # semantics. Single-lookup tables prefetch per op; a shared
                # table prefetches the UNION of its peeked next batches
                # (keyed by table id, matching the union pull in the
                # pre-step).
                for tid, ops in self._staged_by_table.items():
                    idx_nodes = [op.inputs[1] for op in ops]
                    if not all(n in self.dataloader_nodes
                               and hasattr(n, "peek_batch")
                               for n in idx_nodes):
                        continue
                    if len(ops) == 1:
                        ps.prefetch_lookup(
                            id(ops[0]), ps.params[tid],
                            np.asarray(idx_nodes[0].peek_batch(self.name)))
                    else:
                        nxt = np.unique(np.concatenate(
                            [np.ascontiguousarray(
                                np.asarray(n.peek_batch(self.name)),
                                np.int64).ravel() for n in idx_nodes]))
                        ps.prefetch_lookup(tid, ps.params[tid], nxt)
            else:
                for op, grad in zip(self.ps_comm_ops, ps_grads):
                    p = ps.params[id(op.ps_param_node)]
                    idx = self._push_idx(op, staged_idx)
                    ps.push_grad(p, grad, idx, step=step)

        with _tr.span(_tr.POSTSTEP, stamps):
            if self.training:
                for node, val in zip(ex.param_nodes, new_params):
                    ex.state["params"][id(node)] = val
                for node, val in zip(self.optimizer_nodes, new_slots):
                    ex.state["slots"][id(node)] = val
                for node, val in zip(self.stateful_nodes, new_opstate):
                    ex.state["op_state"][id(node)] = val
                for node, val in zip(self.qresid_nodes, qresid_out):
                    ex.state["qresid"][id(node)] = val
                ex.state["step"] = step + 1

            finite = True
            if self.anomaly_guard:
                # materializing the scalar syncs on the step — the
                # documented cost of the guard (callers reading the loss
                # sync anyway)
                finite = bool(np.asarray(finite_t))
                if finite:
                    ex.state["anomaly_streak"] = 0
                else:
                    ex.state["anomaly_streak"] += 1
                    ex.state["anomaly_total"] += 1
                    if tel is not None:
                        ex._tel_metrics["anomalies"].inc()
                ex.state["last_step_finite"] = finite

            # -- hetuscope: stats fetch, flight record, NaN/Inf provenance --
            prov = None
            if intro is not None:
                from ..telemetry import scope as _scope
                stats_host = None
                if self.anomaly_guard and not finite:
                    if introspect_now:
                        # the failing step WAS a stats step, and the
                        # guard's finite check already synced it: its own
                        # packed table localizes the culprit, no replay
                        stats_host = _scope.host_stats(self._scope_meta[2],
                                                       scope_stats_t)
                        order, inputs_map = self._scope_meta[:2]
                        prov = _scope.find_culprit(order, inputs_map,
                                                   stats_host, step)
                    else:
                        prov = self._provenance_replay(
                            step, base_key, feed_vals, batch_vals,
                            dl_cursors, res_data, ps_staged_vals,
                            ps_dense_vals, inject_nan, poison_scope)
                rec = {"sub": self.name, "step": int(step),
                       "step_ms": round((time.perf_counter()
                                         - stamps[_tr.STEP][0]) * 1e3, 4),
                       "finite": bool(finite), "seed": int(self.config.seed),
                       "lr": self._host_lr(),
                       "batch_crc32": _flight_crc(feed_dict, batch_host),
                       "cursors": self._flight_cursors()}
                intro.record_step(rec, stats=stats_host)
                if introspect_now and stats_host is None:
                    # DEFER the cadence fetch: materializing the packed
                    # vector now would block on this step's compute and
                    # stall the dispatch pipeline (measured: the stall, not
                    # the fused reductions, dominated the overhead). It
                    # resolves at the next step boundary / flush / first
                    # read, mutating the ring record in place and exporting
                    # the hetu_scope_* gauges + scope JSONL row then.
                    def _resolve(vec=scope_stats_t,
                                 spec=self._scope_meta[2], name=self.name,
                                 s=int(step), tel=tel, intro=intro):
                        stats = _scope.host_stats(spec, vec)
                        if tel is not None:
                            intro.export(tel, name, s, stats)
                        return stats

                    intro.defer(rec, _resolve)
                elif tel is not None and stats_host is not None:
                    intro.export(tel, self.name, step, stats_host)
                if prov is not None:
                    intro.on_anomaly(prov, telemetry=tel)

            # hetutrail step boundary: drain this step's client RPC spans
            # and advance the span step stamp (None writer when off — one
            # check)
            if ps is not None and self.training \
                    and ps.trail_writer is not None:
                ps.trail_step_boundary(step)
            if tel is not None:
                # recorded BEFORE supervisor post-step: an emergency flush on
                # the preemption path must already contain this step's record
                self._record_telemetry(tel, step, stamps, compiled_now,
                                       feed_vals, batch_vals)

            # post-step supervision LAST: a rollback rewrites ex.state, an
            # emergency save captures it, and Preempted aborts the return —
            # all only valid after the commit above. On a trip the anomaly
            # event carries the headline numbers (loss at trip; global grad
            # norm when provenance ran) so post-mortems don't need the
            # flight recorder for them.
            if sup is not None:
                extra = {}
                if self.anomaly_guard and not finite:
                    # the provenance stats already carry the at-trip loss —
                    # reuse them; the extra device fetch is only for guard-
                    # without-introspection runs
                    loss_v = prov.get("loss") if prov is not None else None
                    extra["loss"] = (loss_v if loss_v is not None
                                     else self._loss_at_trip(outputs))
                    if prov is not None:
                        extra["grad_norm"] = prov.get("grad_norm")
                sup.post_step(ex, self, step, finite=finite, **extra)

            # with convert_to_numpy_ret_vals the device wait lands here
            results = []
            wanted = (eval_node_list if eval_node_list is not None
                      else self.eval_nodes)
            out_by_node = {id(n): v
                           for n, v in zip(self.eval_nodes, outputs)}
            for node in wanted:
                if node.is_optimizer:
                    results.append(None)
                else:
                    if id(node) not in out_by_node:
                        raise ValueError(
                            f"Node {node.name!r} is not among subexecutor "
                            f"{self.name!r}'s eval nodes; include it in the "
                            "eval_node_dict at Executor construction")
                    v = out_by_node[id(node)]
                    results.append(np.asarray(v)
                                   if convert_to_numpy_ret_vals
                                   else NDArray(v))
            return results


class Executor:
    """User-facing executor (reference executor.py:301)."""

    def __init__(self, eval_node_dict, ctx=None, seed=None, comm_mode=None,
                 config=None, lint=None, **kwargs):
        if isinstance(eval_node_dict, (list, tuple)):
            eval_node_dict = {"default": list(eval_node_dict)}
        self.eval_node_dict = {k: list(v) for k, v in eval_node_dict.items()}
        all_nodes = [n for nodes in self.eval_node_dict.values() for n in nodes]
        if config is None:
            config = HetuConfig(eval_node_list=all_nodes, ctx=ctx, seed=seed,
                                comm_mode=comm_mode, **kwargs)
        self.config = config
        # -- hetuplan adoption (docs/ANALYSIS.md "Tier C: planning") --------
        # Runs BEFORE comm-op insertion so the adopted comm_mode drives the
        # same strategy rewrite a hand-declared one would. The planner only
        # fills fields the config left unset; a declared comm_mode is never
        # overridden (the plan-divergence lint reports the conflict).
        self.plan = None
        if getattr(config, "plan", None) is not None:
            from ..analysis.planner import Plan as _Plan, plan_graph
            if isinstance(config.plan, _Plan):
                self.plan = config.plan
            else:
                n_dev = (config.mesh.size if config.mesh is not None
                         else max(1, len(jax.devices())))
                self.plan = plan_graph(self.eval_node_dict, config=config,
                                       devices=n_dev)
            self.plan.apply(config)
        self.comm_mode = config.comm_mode

        # -- telemetry activation (docs/OBSERVABILITY.md) -------------------
        # Activated BEFORE the PS runtime spawns so its pull/push streams can
        # cache the handle. When off, self.telemetry is None and every
        # instrumented point in SubExecutor.run short-circuits on that one
        # None check — no timestamps, no allocations.
        from .. import telemetry as _tel_pkg
        self.telemetry = _tel_pkg.activate(config.telemetry)
        # HETU_XLA_TRACE=dir[:start[:n]] opens its jax.profiler window
        # whether or not telemetry is on (telemetry's own is advertised in
        # its JSONL and stopped by its abort-path flush)
        self.xla_window = (self.telemetry.xla_window
                           if self.telemetry is not None
                           else _tr.XlaTraceWindow.from_env())
        self._tel_metrics = None
        self._tel_recompile_mon = None
        if self.telemetry is not None:
            reg = self.telemetry.metrics
            self._tel_metrics = {
                "step_ms": reg.histogram("hetu_step_time_ms"),
                "eval_ms": reg.histogram("hetu_eval_time_ms"),
                "steps": reg.counter("hetu_steps_total"),
                "examples": reg.counter("hetu_examples_total"),
                "compiles": reg.counter("hetu_compiles_total"),
                "recompiles": reg.counter("hetu_recompiles_total"),
                "anomalies": reg.counter("hetu_anomaly_trips_total"),
                "flops": reg.gauge("hetu_flops_per_step"),
            }
            from ..analysis.lowered import RecompileMonitor
            self._tel_recompile_mon = RecompileMonitor(
                self, budget=int(os.environ.get("HETU_RECOMPILE_BUDGET",
                                                "3")))
            device_kind = str(jax.devices()[0].device_kind)
            # the peak comes from the one table keyed by device_kind; a
            # kind the table does not know gets no MFU downstream
            from ..telemetry.profiler import device_peaks
            peaks = device_peaks(device_kind)
            self.telemetry.record(
                "run_info", device_kind=device_kind,
                peak_tflops=peaks["tflops"] if peaks else None,
                peak=peaks["source"] if peaks else "unknown",
                comm_mode=str(config.comm_mode))

        # -- numeric-health introspection (hetuscope) -----------------------
        # Armed by HetuConfig(introspect=...) / HETU_INTROSPECT; None when
        # off, and every scope point in SubExecutor.run gates on that one
        # None check. The flight recorder shares the telemetry directory
        # (flight/ subdir) so bin/hetuscope reads one place post-mortem.
        self.introspector = None
        if config.introspect:
            from ..telemetry import scope as _scope
            scope_dir = (self.telemetry.dir if self.telemetry is not None
                         else os.environ.get("HETU_TELEMETRY_DIR",
                                             "hetu_telemetry"))
            self.introspector = _scope.Introspector(config.introspect,
                                                    scope_dir)

        # -- hetuwatch: plan stamp + divergence sentinel (pillar 6) ---------
        # The adopted plan's per-leg prediction is stamped into telemetry
        # unconditionally (one kind:"plan" record — the judge's denominator
        # and the run's layout provenance, which heturun's run_summary and
        # hetulint --calibrate both read back). The live sentinel arms only
        # when the watch cadence is set AND there is something to judge: a
        # plan to diverge from, or SLO budgets to enforce. Off, plan_watch
        # is None and the step-boundary hook is one attribute check.
        self.plan_watch = None
        if self.telemetry is not None:
            from ..telemetry import watch as _watch_mod
            plan_dict = None
            if self.plan is not None:
                plan_dict = self.plan.as_dict()
                self.telemetry.record(
                    "plan", **_watch_mod.stamp_fields(plan_dict))
            if config.watch and (plan_dict is not None or config.slo):
                self.plan_watch = _watch_mod.PlanWatch(
                    predicted=(_watch_mod.predicted_legs(
                        plan_dict.get("breakdown") or {})
                        if plan_dict is not None else None),
                    predicted_step_ms=(plan_dict or {}).get(
                        "predicted_step_ms"),
                    every=config.watch,
                    window=int(os.environ.get(
                        "HETU_WATCH_WINDOW",
                        str(_watch_mod.DEFAULT_WINDOW))),
                    k=int(os.environ.get("HETU_WATCH_K",
                                         str(_watch_mod.DEFAULT_K))),
                    ratio=float(os.environ.get(
                        "HETU_WATCH_RATIO", str(_watch_mod.DEFAULT_RATIO))),
                    min_ms=float(os.environ.get(
                        "HETU_WATCH_MIN_MS",
                        str(_watch_mod.DEFAULT_MIN_MS))),
                    slo=config.slo, plan=plan_dict)

        full_topo = find_topo_sort(all_nodes)
        # any variable read through an embedding lookup is a sparse embedding
        # for comm-strategy purposes (keeps insert_comm_ops and PSRuntime's
        # classification in agreement)
        if config.comm_mode in ("PS", "Hybrid"):
            for node in full_topo:
                embed = getattr(node, "embed_node", None)
                if embed is not None and getattr(embed, "trainable", False):
                    embed.is_embed = True
        # comm-op insertion (the reference's OptimizerOp.backward_hook,
        # optimizer.py:125-139) — rewrite optimizer grad inputs per strategy.
        for node in full_topo:
            if node.is_optimizer:
                node.insert_comm_ops(config)
        full_topo = find_topo_sort(all_nodes)

        # hetukern rows-mode reset: graph nodes are shared between
        # executors (the comm_quant re-assert idiom) — a grad op a
        # PREVIOUS executor flipped to rows mode must come back dense
        # BEFORE lint runs and before this build's own PS wiring
        # re-flips eligible ops; likewise a push op's ps_param_node /
        # staged_lookups from a previous wiring must not survive into a
        # build whose conditions no longer hold (a stale ps_param_node
        # would enroll the push in ps_comm_ops with a dense grad and no
        # indices).
        from .ops.ps import ParameterServerCommunicateOp as _PSPush
        for node in full_topo:
            if getattr(node, "rows_mode", False):
                node.to_dense()
            if isinstance(node, _PSPush):
                node.ps_param_node = None
                node.staged_lookups = None

        # -- define-time validation (hetulint Tier A, docs/ANALYSIS.md) -----
        # Runs over the post-comm-insertion graph — the graph that will
        # actually trace — and BEFORE any PS server spawns or parameter
        # materializes, so an invalid graph fails fast with op-level
        # provenance instead of a deep jit traceback at run time.
        self._lint(lint)

        # -- PS/Hybrid runtime (reference ParameterServerCommunicate.py) ----
        self.ps_runtime = None
        if config.comm_mode in ("PS", "Hybrid"):
            from .ps_runtime import PSRuntime
            self.ps_runtime = PSRuntime(config, full_topo)
            self._rewire_ps_gradients(full_topo)

        ps_resident = (set(self.ps_runtime.params.keys())
                       if self.ps_runtime else set())
        self.param_nodes = [n for n in full_topo
                            if n.is_placeholder and not getattr(n, "is_feed", True)
                            and id(n) not in ps_resident]
        self.rng_root = jax.random.PRNGKey(config.seed)

        # -- tensor-parallel parameter shardings ----------------------------
        # a dispatch marker directly on a trainable Variable pins that
        # parameter's layout for its whole lifetime (init, updates, ckpt) —
        # the weight is *stored* split over the model axis, never gathered
        if config.mesh is not None \
                and config.mp_axis in config.mesh.axis_names:
            for node in full_topo:
                if isinstance(node, DispatchOp) \
                        and getattr(node.inputs[0], "trainable", False):
                    config.param_specs[id(node.inputs[0])] = \
                        node.partition_spec(config.mesh, config.dp_axis,
                                            config.mp_axis)

        # -- parameter initialization (reference initializers.py) ----------
        params = {}
        for i, node in enumerate(self.param_nodes):
            init_rng = jax.random.fold_in(self.rng_root, 2**20 + i)
            value = node.instantiate(init_rng)
            value = jnp.asarray(value, dtype=node.dtype)
            if config.mesh is not None:
                spec = config.param_specs.get(id(node), P())
                value = jax.device_put(value, NamedSharding(config.mesh, spec))
            elif config.device is not None:
                value = jax.device_put(value, config.device)
            params[id(node)] = value
            config.placeholder_to_arr_map[node] = value

        # -- hetuq: quantized DP AllReduce eligibility (docs/COMM_QUANT.md) -
        # Marks the AllReduce ops whose gradient sync the policy compresses:
        # device-resident f32 params at/above the size threshold (or force-
        # listed), pure-DP only — tp-sharded params keep the exact path, as
        # does everything when comm_quant="off" (the marked-op check in
        # TraceContext.allreduce is the single branch point, so off mode is
        # bit-identical to pre-hetuq behavior). Error-feedback residuals are
        # executor state, committed/rolled back like optimizer slots.
        qpol = config.comm_quant_policy
        self.qar_ops = []
        qresid = {}
        for node in full_topo:
            if not isinstance(node, AllReduceCommunicateOp):
                continue
            # ALWAYS reset first: graph nodes are shared between executors
            # (A/B legs reuse a built graph), and a stale mark from a
            # previous quantized executor must never leak into this one —
            # off mode re-asserts the exact path on every node
            node.comm_quant = False
            if not qpol.active or config.mesh is None:
                continue
            pn = node.param_node
            val = params.get(id(pn)) if pn is not None else None
            if val is None or id(pn) in config.param_specs:
                continue
            if not jnp.issubdtype(val.dtype, jnp.floating):
                continue
            if qpol.applies(pn, int(np.prod(val.shape))):
                node.comm_quant = True
                self.qar_ops.append(node)
                if qpol.error_feedback:
                    qresid[id(node)] = jnp.zeros_like(
                        val, dtype=jnp.float32)
        self.comm_quant_report = None
        if self.qar_ops:
            from .. import comm_quant as _cq
            sizes = {n.param_node.name: int(np.prod(params[id(n.param_node)].shape))
                     for n in self.qar_ops}
            self.comm_quant_report = _cq.allreduce_wire_report(
                sizes, qpol, config.dp_size)
            if self.telemetry is not None:
                g = self.telemetry.metrics.gauge
                g("hetu_comm_quant_raw_bytes").set(
                    float(self.comm_quant_report["raw_bytes"]))
                g("hetu_comm_quant_wire_bytes").set(
                    float(self.comm_quant_report["wire_bytes"]))

        slots = {}
        op_state = {}
        for node in full_topo:
            if node.is_optimizer:
                # PS-resident params keep their optimizer state server-side
                slots[id(node)] = node.init_slots(
                    {id(v): params[id(v)] for v in node.vars
                     if id(v) in params})
            if node.stateful:
                op_state[id(node)] = jax.tree.map(jnp.asarray, node.state_init())
        self.state = {"params": params, "slots": slots, "op_state": op_state,
                      "qresid": qresid, "step": 0,
                      # resilience counters (anomaly_guard):
                      "anomaly_streak": 0, "anomaly_total": 0,
                      "last_step_finite": True}
        # total trainable parameter count — the N in the 6ND MFU denominator
        # (docs/ROOFLINE.md). PS-resident tables count too: their lookup/
        # update flops run per step even though the arrays live server-side.
        self.n_params_total = sum(
            int(np.prod(v.shape)) for v in params.values())
        if self.ps_runtime is not None:
            self.n_params_total += sum(
                int(np.prod(p.shape))
                for p in self.ps_runtime.params.values())
        if self.telemetry is not None:
            self.telemetry.metrics.gauge("hetu_params_total").set(
                float(self.n_params_total))
        # resilience.Supervisor hook point (attach_supervisor)
        self.supervisor = None
        # hetu-elastic membership agent (docs/FAULT_TOLERANCE.md "Elastic
        # membership"): armed below for PS/Hybrid runs under HETU_ELASTIC;
        # None otherwise — SubExecutor.run pays one None check per step
        self.elastic = None
        # hetupilot self-tuning controller (docs/FAULT_TOLERANCE.md
        # "Self-tuning with guardrails"): armed below for PS/Hybrid runs
        # under HETU_PILOT when the plan-divergence sentinel is watching
        self.pilot = None

        self.subexecutors = {}
        for name, nodes in self.eval_node_dict.items():
            if config.gpipe:
                # every target pipelines (forward-only for validation
                # entries): params commit to per-stage devices, so a plain
                # single-device SubExecutor could not touch them anyway
                from .gpipe import SubExecutor4Gpipe
                self.subexecutors[name] = SubExecutor4Gpipe(name, nodes, self)
            else:
                self.subexecutors[name] = SubExecutor(name, nodes, self)

        if self.ps_runtime is not None:
            from ..resilience import env_truthy
            if env_truthy("HETU_ELASTIC"):
                from ..elastic import ElasticAgent
                self.elastic = ElasticAgent.from_env(self)
                # after subexecutors exist: a late joiner's bootstrap
                # re-partitions their dataloaders from the world log
                self.elastic.bootstrap()
            # heturun --restore (docs/FAULT_TOLERANCE.md "Coordinated job
            # snapshots"): re-impose this rank's persisted state from the
            # newest committed job epoch and verify the update-counter
            # algebra against the manifest BEFORE any training step runs
            restore_dir = os.environ.get("HETU_RESTORE_DIR", "")
            if restore_dir:
                from ..recovery import restore_executor_from_env
                restore_executor_from_env(self, restore_dir)
            # hetupilot (heturun --pilot / HETU_PILOT=1): acts on the
            # sentinel's recommendations, so it needs the sentinel — armed
            # AFTER any restore so interrupted-era sealing sees the state
            # the run will actually continue from
            if env_truthy("HETU_PILOT"):
                if self.plan_watch is not None:
                    from ..pilot import Pilot
                    self.pilot = Pilot.from_env(self)
                else:
                    import sys as _sys
                    print("# hetupilot: HETU_PILOT set but the plan watch "
                          "is not armed (need HETU_WATCH plus an adopted "
                          "plan or SLO) — controller disabled",
                          file=_sys.stderr, flush=True)

    # ------------------------------------------------------------------
    def _lint(self, lint):
        """Tier A graph validation at build: ``lint`` is "error" (raise
        ``GraphValidationError`` on error-severity findings), "warn" (report
        everything as warnings, build anyway) or "off". Defaults to the
        ``HETU_LINT`` env var, else off."""
        if lint is None:
            lint = os.environ.get("HETU_LINT", "off") or "off"
        if lint == "off":
            return
        if lint not in ("error", "warn"):
            raise ValueError(
                f"lint must be 'error', 'warn' or 'off', got {lint!r}")
        from ..analysis import (GraphAnalyzer, GraphValidationError,
                                format_findings, ERROR)
        findings = GraphAnalyzer(self.eval_node_dict,
                                 config=self.config).run()
        if not findings:
            return
        errors = [f for f in findings if f.severity == ERROR]
        if errors and lint == "error":
            raise GraphValidationError(findings)
        import warnings
        warnings.warn(
            f"hetulint: {len(findings)} finding(s) on this graph:\n"
            + format_findings(findings), stacklevel=3)

    def _rewire_ps_gradients(self, topo):
        """Point each PS comm op's gradient at the lookup OUTPUT rather than
        the table variable, so the traced grad is (batch_rows, width) instead
        of a full-table scatter (the reference's IndexedSlices analogue)."""
        loss_topo_ids: dict[int, set] = {}  # per-loss memo for this pass
        ps_by_name = {p.node.name: p for p in self.ps_runtime.params.values()}
        consumers: dict[int, list] = {}
        for n in topo:
            for i in n.inputs:
                consumers.setdefault(id(i), []).append(n)
        eval_ids = {id(n) for ns in self.eval_node_dict.values() for n in ns}
        for node in topo:
            if not isinstance(node, ParameterServerCommunicateOp):
                continue
            grad_node = node.inputs[0]
            if not getattr(grad_node, "is_gradient", False):
                # hetukern satellite (docs/KERNELS.md): an explicit
                # embedding_lookup_gradient_op whose ONLY consumer is this
                # PS push flips into ROWS mode — the rows leave the device
                # anyway, so the (vocab, dim) zeros-table scatter the dense
                # form pays is pure waste on this route. The runtime trims
                # the sentinel tail and pushes (rows, grads) directly.
                # Another consumer (or the op itself as an eval target)
                # needs the dense table shape, so the op stays dense then.
                # Structural preconditions shared with hetulint's
                # ps-push-ignored mirror (embed_grad_push_routable) so the
                # lint and this rewire cannot drift.
                from .ops.embedding import embed_grad_push_routable
                if embed_grad_push_routable(node, grad_node, consumers,
                                            eval_ids) \
                        and node.ps_id in ps_by_name:
                    p = ps_by_name[node.ps_id]
                    if p.sparse and tuple(grad_node.embed_shape) == p.shape:
                        grad_node.to_rows()
                        node.ps_param_node = p.node
                continue
            var = grad_node.x
            p = self.ps_runtime.params.get(id(var))
            if p is None:
                continue
            node.ps_param_node = var
            if not p.sparse:
                continue  # dense PS params are fed whole; grad wrt var is fine
            # Scope to lookups on THIS gradient's loss graph: the table may
            # also feed other eval targets (a validate head with its own
            # lookup node) whose rows are staged by their own subexecutor and
            # never produce gradients. Inference-only sparse pulls are not
            # differentiation targets either (their zero grads would corrupt
            # stateful server-optimizer rows).
            loss = grad_node.gctx.loss
            loss_ids = loss_topo_ids.get(id(loss))
            if loss_ids is None:
                loss_ids = {id(n) for n in find_topo_sort([loss])}
                loss_topo_ids[id(loss)] = loss_ids
            lookups = [lk for lk in p.lookup_ops
                       if id(lk) in loss_ids
                       and not isinstance(lk, ParameterServerSparsePullOp)]
            if not lookups:
                raise ValueError(
                    f"PS-hosted embedding {var.name!r} has a gradient but no "
                    "lookup op reads it on the loss graph — sparse PS tables "
                    "are only trainable through embedding_lookup_op")
            node.staged_lookups = lookups
            xs = grad_node.gctx.xs
            if len(lookups) == 1:
                lookup = lookups[0]
                grad_node.x = lookup
                grad_node.inputs = [grad_node.gctx.loss, lookup]
                for i, x in enumerate(xs):
                    if x is var:
                        xs[i] = lookup
            else:
                # one table, k lookups (the reference accumulates the grads
                # as IndexedSlices, optimizer.py:64-82): differentiate wrt
                # EACH lookup output; the push path concatenates the per-
                # lookup (rows, width) grads and dedup-sums before the RPC
                grad_node.x = lookups[0]
                grad_node.multi_x = lookups
                grad_node.inputs = [grad_node.gctx.loss] + lookups
                for i, x in enumerate(xs):
                    if x is var:
                        xs[i] = lookups[0]
                for lk in lookups[1:]:
                    if all(x is not lk for x in xs):
                        xs.append(lk)

    def _prepare_input(self, value, batch=True):
        """Stage one host value onto the device/mesh.

        ``batch`` says whether dim 0 is a batch dimension to shard over the
        dp axis (feeds/dataloader batches: yes by default, overridable per
        placeholder via ``ht.Variable(..., batch=False)``; whole parameters:
        no). An earlier divisibility heuristic sharded any conveniently-
        shaped feed, silently corrupting non-batch inputs.
        """
        if isinstance(value, NDArray):
            value = value.handle
        if isinstance(value, ND_Sparse_Array):
            return SparseValue(value.data, value.row, value.col,
                               value.nrow, value.ncol)
        arr = np.asarray(value)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        mesh = self.config.mesh
        if mesh is not None:
            dp = self.config.dp_size
            if batch and arr.ndim >= 1 and dp > 1:
                if arr.shape[0] % dp == 0:
                    return jax.device_put(
                        arr, NamedSharding(mesh, P(self.config.dp_axis)))
                import warnings
                warnings.warn(
                    f"batch dim {arr.shape[0]} is not divisible by dp={dp}: "
                    "the feed is REPLICATED across the dp axis instead of "
                    "sharded (correct but slow) — pad the batch or use "
                    "drop_last", stacklevel=3)
            return jax.device_put(arr, NamedSharding(mesh, P()))
        if self.config.device is not None:
            return jax.device_put(arr, self.config.device)
        return jnp.asarray(arr)

    def remesh(self, new_mesh) -> dict:
        """hetu-elastic leg 2: LIVE dp re-mesh — rebuild the device world
        mid-run without losing a step. State round-trips through the
        existing checkpoint capture/restore machinery
        (``resilience.capture_executor_state`` — no new serialization
        format): params, optimizer slots, op state, and hetuq
        error-feedback residuals are captured to host, re-placed under the
        new mesh's shardings, and every compiled step program is
        invalidated (the shardings changed, so the old executables are
        wrong, not just stale). The step counter, RNG folds, and
        dataloader cursors survive, so training continues exactly where it
        left off — ``tests/test_elastic_executor.py`` pins loss parity
        against an uninterrupted run.

        Pure data-parallel meshes only: dispatch-pinned (tensor-parallel)
        parameter storage re-shards are not yet supported."""
        cfg = self.config
        if not isinstance(new_mesh, Mesh):
            raise ValueError(
                f"new_mesh must be a jax.sharding.Mesh, got "
                f"{type(new_mesh).__name__}")
        if cfg.gpipe:
            raise NotImplementedError(
                "remesh is not supported under gpipe: the pipeline "
                "executor owns per-stage placement")
        if cfg.mp_axis in new_mesh.axis_names or cfg.param_specs or (
                cfg.mesh is not None
                and cfg.mp_axis in cfg.mesh.axis_names):
            raise NotImplementedError(
                "remesh supports pure data-parallel meshes; model-parallel "
                "(dispatch-pinned) parameter storage does not re-shard yet")
        t0 = time.perf_counter()
        from ..resilience import capture_executor_state, load_executor_state
        state = capture_executor_state(self)
        qresid_host = {id(n): np.asarray(self.state["qresid"][id(n)])
                       for n in self._qresid_ordered()}
        cfg.mesh = new_mesh

        def place(x):
            return jax.device_put(jnp.asarray(x),
                                  NamedSharding(new_mesh, P()))

        # params re-place through the same path init/load use
        # (_place_param inside load_executor_state); slots/op-state/qresid
        # re-place replicated explicitly — like_current's bare jnp.asarray
        # would leave them on the default device, and donation across
        # mismatched placements is what a half-moved world trips over
        load_executor_state(self, state)
        for n in self._opt_nodes():
            self.state["slots"][id(n)] = jax.tree.map(
                place, self.state["slots"][id(n)])
        for n in self._stateful_nodes():
            self.state["op_state"][id(n)] = jax.tree.map(
                place, self.state["op_state"][id(n)])
        for nid, v in qresid_host.items():
            self.state["qresid"][nid] = place(v)
        for sub in self.subexecutors.values():
            sub._compiled.clear()
            sub._replay_compiled.clear()
            sub._exe_cache.clear()
            sub._base_sigs.clear()
            sub._last_call = None
            sub._dev_prefetch.clear()
            for nid in list(sub.resident_dl):
                node = next(n for n in sub.res_dl_nodes if id(n) == nid)
                dl = node.dataloaders.get(sub.name)
                # re-place the resident dataset (old-mesh arrays are no
                # longer addressable placements for the new programs) and
                # refresh geometry — an elastic repartition may have
                # changed it
                sub.resident_dl[nid] = (
                    self._prepare_input(dl._data, batch=False),
                    dl.batch_size, dl.batch_num)
        dur_ms = (time.perf_counter() - t0) * 1e3
        if self.telemetry is not None:
            g = self.telemetry.metrics.gauge
            g("hetu_dp_size").set(float(cfg.dp_size))
            g("hetu_resize_duration_ms").set(round(dur_ms, 2))
            self.telemetry.event("remesh", dp_size=cfg.dp_size,
                                 duration_ms=round(dur_ms, 1))
        return {"dp_size": cfg.dp_size, "duration_ms": round(dur_ms, 2),
                "step": int(self.state["step"])}

    def attach_supervisor(self, sup):
        """Attach a ``resilience.Supervisor``: its pre_step/post_step hooks
        then run at every training-step boundary (watchdog beat, fault
        injection, anomaly rollback, periodic + emergency checkpoints,
        preemption exit). Pass None to detach. Returns ``sup``."""
        self.supervisor = sup
        return sup

    @property
    def rank(self) -> int:
        """Reference examples gate printing on ``executor.rank``; the
        single-program TPU build is logically rank 0 of one process."""
        return jax.process_index()

    def run(self, name="default", eval_node_list=None, feed_dict=None,
            convert_to_numpy_ret_vals=False, **kwargs):
        if isinstance(name, (dict, list, tuple)):  # run(feed_dict) legacy form
            feed_dict, name = name, "default"
        sub = self.subexecutors[name]
        return sub.run(feed_dict=feed_dict,
                       convert_to_numpy_ret_vals=convert_to_numpy_ret_vals,
                       eval_node_list=eval_node_list)

    def get_batch_num(self, name="default"):
        """Batches per epoch for the target's dataloaders (min across
        them). Under dataloader-fed gpipe this counts STEPS per epoch:
        each gpipe run() consumes gpipe_microbatches batches per
        loader."""
        sub = self.subexecutors[name]
        dls = getattr(sub, "dataloader_nodes", None)
        if dls is None:
            dls = getattr(sub, "dl_nodes", [])
        nums = [n.get_batch_num(name) for n in dls]
        if not nums:
            return None
        num = min(nums)
        m = getattr(self.config, "gpipe_microbatches", None)
        if self.config.gpipe and m:
            if num < m:
                raise ValueError(
                    f"dataloader provides {num} batches/epoch but one "
                    f"gpipe step consumes gpipe_microbatches={m}; a "
                    f"0-step epoch loop would silently train nothing")
            num //= m
        return num

    def _param_file_names(self):
        """Stable, collision-free file name per parameter: duplicates get a
        deterministic __<k> suffix (construction order)."""
        counts: dict[str, int] = {}
        names = []
        for node in self.param_nodes:
            k = counts.get(node.name, 0)
            counts[node.name] = k + 1
            names.append(node.name if k == 0 else f"{node.name}__{k}")
        return names

    # -- checkpoint (reference executor.py:355-413; adds optimizer state) ---
    def save(self, file_path: str):
        tel = self.telemetry
        t0 = time.perf_counter() if tel is not None else 0.0
        self._save(file_path)
        if tel is not None:
            t1 = time.perf_counter()
            tel.metrics.histogram("hetu_checkpoint_save_ms").observe(
                (t1 - t0) * 1e3)
            if tel.tracer is not None:
                tel.tracer.complete("checkpoint_save", t0, t1, cat="ckpt")

    def _save(self, file_path: str):
        os.makedirs(file_path, exist_ok=True)
        if self.ps_runtime is not None:
            self.ps_runtime.save(file_path)
        for node, fname in zip(self.param_nodes, self._param_file_names()):
            np.save(os.path.join(file_path, fname + ".npy"),
                    np.asarray(self.state["params"][id(node)]))
        aux = {
            "step": self.state["step"],
            "slots": {str(i): jax.tree.map(np.asarray, self.state["slots"][id(n)])
                      for i, n in enumerate(self._opt_nodes())},
            "op_state": {str(i): jax.tree.map(np.asarray, self.state["op_state"][id(n)])
                         for i, n in enumerate(self._stateful_nodes())},
            # hetuq error-feedback residuals: without them a resumed run's
            # first quantized steps would re-pay the cold-start compression
            # error the residual had already absorbed
            "qresid": {str(i): np.asarray(self.state["qresid"][id(n)])
                       for i, n in enumerate(self._qresid_ordered())},
        }
        with open(os.path.join(file_path, "executor_state.pkl"), "wb") as f:
            pickle.dump(aux, f)

    def _place_param(self, node, value):
        """A host value as this parameter's device/mesh-resident array (the
        same placement rule as init/load; shared with resilience restore)."""
        value = jnp.asarray(value, dtype=node.dtype)
        if self.config.mesh is not None:
            spec = self.config.param_specs.get(id(node), P())
            value = jax.device_put(value, NamedSharding(self.config.mesh, spec))
        elif self.config.device is not None:
            value = jax.device_put(value, self.config.device)
        return value

    def load(self, file_path: str):
        if self.ps_runtime is not None:
            self.ps_runtime.load(file_path)
        for node, fname in zip(self.param_nodes, self._param_file_names()):
            path = os.path.join(file_path, fname + ".npy")
            if os.path.exists(path):
                self.state["params"][id(node)] = self._place_param(
                    node, np.load(path))
        aux_path = os.path.join(file_path, "executor_state.pkl")
        if os.path.exists(aux_path):
            with open(aux_path, "rb") as f:
                aux = pickle.load(f)
            self.state["step"] = aux.get("step", 0)
            for i, n in enumerate(self._opt_nodes()):
                if str(i) in aux.get("slots", {}):
                    self.state["slots"][id(n)] = jax.tree.map(
                        jnp.asarray, aux["slots"][str(i)])
            for i, n in enumerate(self._stateful_nodes()):
                if str(i) in aux.get("op_state", {}):
                    self.state["op_state"][id(n)] = jax.tree.map(
                        jnp.asarray, aux["op_state"][str(i)])
            for i, n in enumerate(self._qresid_ordered()):
                if str(i) in aux.get("qresid", {}):
                    v = jnp.asarray(aux["qresid"][str(i)], jnp.float32)
                    if self.config.mesh is not None:
                        v = jax.device_put(
                            v, NamedSharding(self.config.mesh, P()))
                    self.state["qresid"][id(n)] = v

    def _qresid_ordered(self):
        """Stable checkpoint order for the error-feedback residuals (the
        quantized-AllReduce op scan order)."""
        return [n for n in self.qar_ops if id(n) in self.state["qresid"]]

    def _opt_nodes(self):
        seen, out = set(), []
        for sub in self.subexecutors.values():
            for n in sub.optimizer_nodes:
                if id(n) not in seen:
                    seen.add(id(n))
                    out.append(n)
        return out

    def _stateful_nodes(self):
        seen, out = set(), []
        for sub in self.subexecutors.values():
            for n in sub.stateful_nodes:
                if id(n) not in seen:
                    seen.add(id(n))
                    out.append(n)
        return out

    def close(self):
        """Drain and stop the PS async I/O threads (reference worker
        Finalize). Safe to call more than once; training can resume on the
        synchronous path afterwards. Also detaches this executor's
        hetuscope introspector so later abort flushes don't rewrite a
        finished run's flight file."""
        if self.ps_runtime is not None:
            self.ps_runtime.drain()
            self.ps_runtime.shutdown()
        if self.introspector is not None:
            self.introspector.close()
        if self.telemetry is None and self.xla_window is not None:
            # a job that ends inside its HETU_XLA_TRACE window keeps the
            # capture (idempotent; telemetry's flush stops the one it owns)
            self.xla_window.stop()

    def fetch_dense_parameter_value(self, nodes):
        """Reference executor.py:1236 — current parameter values (PS-hosted
        dense params are pulled from the server)."""
        out = []
        for n in nodes:
            p = (self.ps_runtime.params.get(id(n))
                 if self.ps_runtime is not None else None)
            if p is not None:
                out.append(NDArray(self.ps_runtime.pull_dense_value(p)))
            else:
                out.append(NDArray(self.state["params"][id(n)]))
        return out


# ---------------------------------------------------------------------------
# distributed bootstrap shims (reference executor.py:38-100). Under JAX the
# runtime is initialized once per process via jax.distributed; these keep the
# reference's call sites working.
# ---------------------------------------------------------------------------

def wrapped_mpi_nccl_init(init_nccl=True, devices=None):
    import jax

    class _Comm:
        rank = jax.process_index()
        nrank = jax.process_count()

        def local_rank(self):
            return 0

    return _Comm()


def mpi_nccl_init():
    comm = wrapped_mpi_nccl_init()
    return comm, comm.rank


def mpi_nccl_finish(comm=None):
    return None


def new_group_comm(devices=None):
    return None


def scheduler_init():
    from .. import ps
    ps.scheduler_init()


def scheduler_finish():
    from .. import ps
    ps.scheduler_finish()


def server_init():
    from .. import ps
    ps.server_init()


def server_finish():
    from .. import ps
    ps.server_finish()


def worker_init():
    from .. import ps
    ps.worker_init()


def worker_finish():
    from .. import ps
    ps.worker_finish()


def get_worker_communicate():
    from .. import ps
    return ps.get_worker_communicate()
