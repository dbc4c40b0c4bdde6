"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

Reference mechanism: per-stage ``ht.context(...)`` blocks, auto-inserted
NCCL PipelineSend/Recv with a runtime shape handshake, and a Python microbatch
loop (``SubExecutor4Gpipe``, executor.py:435-767) that runs all forwards then
all backwards and applies the optimizer once.

TPU-native redesign: the whole pipeline — all stages, all microbatches,
forward AND backward — is ONE jitted program. Stage weights are stacked on a
leading axis sharded over ``pp``; inside a ``jax.shard_map`` (manual over
``pp``, GSPMD-auto over dp/tp/sp/ep) activations advance between stages with
``lax.ppermute`` over ICI. ``jax.grad`` differentiates straight through the
ppermute (its transpose is the reverse permute), so the 1F1B-ish reverse
schedule emerges from XLA's dataflow rather than host code, and the optimizer
applies once per step like GPipe. Shapes are static — the reference's dynamic
shape handshake (PipelineSend.py:30-44) is unnecessary by construction.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as tfm
from ..telemetry.tracing import SCOPE_FWD, SCOPE_OPT, scoped


def _stack_stages(params, pp: int):
    """Reshape per-layer stacked block params (L, ...) -> (pp, L//pp, ...)."""
    def reshape(x):
        L = x.shape[0]
        assert L % pp == 0, f"n_layers {L} not divisible by pp {pp}"
        return x.reshape(pp, L // pp, *x.shape[1:])
    return jax.tree.map(reshape, params)


def pipeline_spec(cfg: tfm.TransformerConfig, pp: int):
    """Sharding for pipeline params: blocks get a leading 'pp' dim; embed/pos/
    head/final-norm are replicated — they are consumed inside the manual-pp
    region, where a tp-sharded gather trips a CHECK in XLA's SPMD partitioner
    (observed on XLA@jax0.9: PartitionGatherTrivialSlicedOperandDimensions),
    and stage 0 / stage pp-1 need them everywhere anyway."""
    base = tfm.param_specs(cfg)
    blocks = {k: P("pp", *s) for k, s in base["blocks"].items()}
    replicated = {k: P() for k in base if k != "blocks"}
    return {**replicated, "blocks": blocks}


def _make_stage_fn(cfg: tfm.TransformerConfig, layers_per_stage: int):
    """One stage's forward: this device's layers over one microbatch
    activation — SHARED by the GPipe and 1F1B builders, so 'identical
    math between schedules' is true by construction, not by keeping two
    copies in sync.

    ``rng_mb``: this microbatch's dropout key (None when dropout is
    off). Each layer folds in its GLOBAL index, so key(mb, layer)
    matches the non-pipelined trunk's grad-accumulation schedule
    (make_train_step: fold_in(rng, mi) then encode's fold_in(·, li))."""
    if cfg.n_loops > 1:
        raise NotImplementedError(
            f"n_loops={cfg.n_loops}: the pipeline schedules run the stack "
            "once; a stage boundary inside a loop is undefined")
    if len(tfm.layer_runs(cfg)) > 1:
        raise NotImplementedError(
            f"layer_runs={tfm.layer_runs(cfg)}: no stage rule for layers "
            "of unequal kinds (mamba, conv, kda, gdn or window layers beside "
            "attention, a dense MLP beside experts); the pipeline stacks ONE "
            "kind of block a stage")

    kind = tfm.layer_runs(cfg)[0][0]
    if cfg.single_sublayer:
        raise NotImplementedError(
            f"single_sublayer=True (layer kind {kind!r}): a stage's body is "
            "the block of two halves; a layer that is a mixer alone, or an "
            "MLP half alone, has no stage rule")
    if cfg.router.input != "mlp":
        raise NotImplementedError(
            f"router.input={cfg.router.input!r}: a stage's body routes on "
            "the MLP half's input; routing issued ahead of the mixer has no "
            "stage rule")
    if tfm.mixer_of(kind) != "attention":
        raise NotImplementedError(
            f"layer kind {kind!r}: a stage's body is the attention block; "
            "latent attention (mla), learned sparse attention (dsa: a loss "
            "of its own a layer), window, mamba, conv, gdn and kda mixers have "
            "no stage rule")

    def stage_fn(h, stage_blocks, stage, rng_mb):
        block = functools.partial(tfm._block, cfg=cfg, mesh=None)
        if cfg.remat:
            block = jax.checkpoint(block)
        first_layer = stage * layers_per_stage

        def body(carry, xs):
            h, aux = carry
            layer_params, li = xs
            rng = (None if rng_mb is None
                   else jax.random.fold_in(rng_mb, first_layer + li))
            h, a = block(h, layer_params, dropout_rng=rng)
            return (h, aux + a), None

        aux0 = jax.lax.pcast(jnp.zeros((2,), jnp.float32), ("pp",),
                              to="varying")
        (h, aux), _ = jax.lax.scan(
            body, (h, aux0), (stage_blocks, jnp.arange(layers_per_stage)))
        return h, aux

    return stage_fn


@functools.lru_cache(maxsize=8)
def zero1_pipeline_opt_specs(cfg: tfm.TransformerConfig, mesh: Mesh):
    """ZeRO-1 slot layout for pipeline params: each AdamW m/v leaf is
    additionally sharded over ``dp`` on its first free, dp-divisible dim
    (blocks keep their leading ``pp`` dim). Same recipe — and the same
    GSPMD-materialized reduce-scatter/sharded-update/all-gather dataflow
    — as ``transformer.zero1_opt_specs``; memory for optimizer state
    drops ~dp x with bit-identical step math. Cached per (cfg, mesh):
    both the step builder and ``shard_pipeline_opt_state`` need it, and
    the abstract init trace is pure in its arguments."""
    pp, dp = mesh.shape["pp"], mesh.shape["dp"]
    specs = pipeline_spec(cfg, pp)
    shapes = jax.eval_shape(lambda: {
        **(p := tfm.init_params(jax.random.PRNGKey(0), cfg)),
        "blocks": _stack_stages(p["blocks"], pp)})
    return jax.tree.map(
        lambda s, sh: tfm.shard_first_free_dim(s, sh, dp), specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def shard_pipeline_opt_state(opt_state, cfg: tfm.TransformerConfig,
                             mesh: Mesh, zero1: bool = False):
    """Place a pipeline optimizer state on the mesh (the ZeRO-1 layout
    when ``zero1`` — jit pins committed input shardings, so place the
    state before the first step)."""
    specs = (zero1_pipeline_opt_specs(cfg, mesh) if zero1
             else pipeline_spec(cfg, mesh.shape["pp"]))
    return tfm.place_opt_state(opt_state, specs, mesh)


def _wrap_step(step, cfg: tfm.TransformerConfig, mesh: Mesh, pp: int,
               use_dropout: bool, zero1: bool = False):
    """Shared jit wrapper for both schedule builders: identical
    shardings, donation, and the dropout arity switch — the two steps
    stay drop-in interchangeable (same input layouts) by construction."""
    specs = pipeline_spec(cfg, pp)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    if zero1:
        oshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              zero1_pipeline_opt_specs(cfg, mesh),
                              is_leaf=lambda x: isinstance(x, P))
    else:
        oshard = pshard
    opt_shard = {"m": oshard, "v": oshard, "t": NamedSharding(mesh, P())}
    data_shard = NamedSharding(mesh, P(None, "dp", None))
    in_sh = [pshard, opt_shard, data_shard, data_shard]
    if use_dropout:
        step_fn = step
        in_sh.append(NamedSharding(mesh, P()))
    else:
        # keep the historical 4-arg signature for deterministic configs
        step_fn = lambda params, opt_state, tokens, targets: step(  # noqa: E731
            params, opt_state, tokens, targets)
    return jax.jit(
        step_fn,
        in_shardings=tuple(in_sh),
        out_shardings=(NamedSharding(mesh, P()), pshard, opt_shard),
        donate_argnums=(0, 1),
    )


def make_pipeline_train_step(cfg: tfm.TransformerConfig, mesh: Mesh,
                             num_microbatches: int, lr: float = 1e-3,
                             aux_weight: float = 0.01,
                             zero1: bool = False):
    """Build the jitted GPipe step.

    tokens/targets: (M, mb, T) — M microbatches. Returns
    (loss, params, opt_state). ``zero1``: shard AdamW m/v over dp
    (place the state with ``shard_pipeline_opt_state(..., zero1=True)``
    before the first step; step math is bit-identical).
    """
    pp = mesh.shape["pp"]
    M = num_microbatches
    assert cfg.n_layers % pp == 0
    layers_per_stage = cfg.n_layers // pp
    use_dropout = cfg.dropout_rate > 0.0
    stage_fn = _make_stage_fn(cfg, layers_per_stage)

    def fwd_loss(params, tokens, targets, dropout_rng=None):
        """Pipelined forward + loss, manual over pp via shard_map."""
        stage_blocks = params["blocks"]  # (1, L/pp, ...) local slice per stage
        other = {k: v for k, v in params.items() if k != "blocks"}
        B, T = tokens.shape[1], tokens.shape[2]
        state0 = jnp.zeros((B, T, cfg.d_model), cfg.dtype)

        def pipelined(stage_blocks, other, tokens, targets, state0,
                      dropout_rng=None):
            # inside: manual over 'pp' — axis_index tells us our stage
            stage = jax.lax.axis_index("pp")
            local_blocks = jax.tree.map(lambda x: x[0], stage_blocks)

            perm = [(i, (i + 1) % pp) for i in range(pp)]
            n_ticks = M + pp - 1
            # carries vary per pp-shard: mark them 'varying' for the vma type
            # system before entering the scan
            varying = lambda x: jax.lax.pcast(x, ("pp",), to="varying")
            state = varying(state0)
            loss_sum = varying(jnp.zeros((), jnp.float32))
            aux_sum = varying(jnp.zeros((2,), jnp.float32))

            def tick(carry, t):
                state, loss_sum, aux_sum = carry
                # stage 0 ingests microbatch t (if any); others use received
                mb_idx = jnp.clip(t, 0, M - 1)
                mb_tokens = jax.lax.dynamic_index_in_dim(
                    tokens, mb_idx, 0, keepdims=False)
                inject = tfm.embed_tokens(other, mb_tokens, cfg)
                state = jnp.where((stage == 0) & (t < M), inject, state)
                # the microbatch THIS stage is working on at tick t (garbage
                # outside the [stage, stage+M) window — its loss is never
                # taken, so the garbage dropout key is harmless)
                rng_mb = (None if dropout_rng is None
                          else jax.random.fold_in(
                              dropout_rng, jnp.clip(t - stage, 0, M - 1)))
                out, aux = stage_fn(state, local_blocks, stage, rng_mb)
                # this stage holds a real microbatch only during its window
                valid = (t >= stage) & (t < stage + M)
                aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
                # last stage computes loss for the microbatch that has now
                # passed through all stages: microbatch t-(pp-1)
                done_idx = jnp.clip(t - (pp - 1), 0, M - 1)
                mb_targets = jax.lax.dynamic_index_in_dim(
                    targets, done_idx, 0, keepdims=False)
                mb_loss = tfm.nll_loss(tfm.lm_head(other, out, cfg),
                                       mb_targets)
                take = (stage == pp - 1) & (t >= pp - 1)
                loss_sum = loss_sum + jnp.where(take, mb_loss, 0.0)
                # advance activations to the next stage
                state = jax.lax.ppermute(out, "pp", perm)
                return (state, loss_sum, aux_sum), None

            (state, loss_sum, aux_sum), _ = jax.lax.scan(
                tick, (state, loss_sum, aux_sum), jnp.arange(n_ticks))
            # NLL lives on the last stage, aux is spread over stages; combine
            loss = jax.lax.psum(loss_sum, "pp") / M
            aux = jax.lax.psum(aux_sum, "pp") / M
            return loss + tfm.aux_weights(aux_weight) @ aux

        block_in_spec = jax.tree.map(lambda _: P("pp"), stage_blocks)
        other_spec = jax.tree.map(lambda _: P(), other)
        in_specs = [block_in_spec, other_spec, P(), P(), P()]
        args = [stage_blocks, other, tokens, targets, state0]
        if dropout_rng is not None:
            in_specs.append(P())
            args.append(dropout_rng)
        return jax.shard_map(
            pipelined,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(),
            axis_names=frozenset({"pp"}),
        )(*args)

    def step(params, opt_state, tokens, targets, dropout_rng=None):
        if use_dropout:
            # a forgotten key must not silently train WITHOUT dropout
            assert dropout_rng is not None, (
                "cfg.dropout_rate > 0: pass dropout_rng to the pipeline step")
        loss, grads = jax.value_and_grad(scoped(SCOPE_FWD, fwd_loss))(
            params, tokens, targets, dropout_rng=dropout_rng)
        new_params, new_opt = scoped(SCOPE_OPT, tfm.adamw_update)(
            params, grads, opt_state, lr=lr)
        return loss, new_params, new_opt

    jitted = _wrap_step(step, cfg, mesh, pp, use_dropout, zero1=zero1)
    # the raw loss function, for grad-level parity tests against the 1F1B
    # twin (jax.grad(fwd_loss) is this schedule's exact gradient)
    jitted.fwd_loss = fwd_loss
    return jitted


def init_pipeline_params(rng, cfg: tfm.TransformerConfig, mesh: Mesh):
    pp = mesh.shape["pp"]
    params = tfm.init_params(rng, cfg)
    params = {**params, "blocks": _stack_stages(params["blocks"], pp)}
    specs = pipeline_spec(cfg, pp)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) schedule — beyond reference (the reference has only
# the GPipe all-forwards-then-all-backwards schedule, executor.py:675-746).
#
# Same math, different memory law: GPipe's one-scan forward stashes an
# activation per TICK for the outer jax.grad (peak ~ M + pp - 1 per stage);
# 1F1B hand-rolls the backward INSIDE the scan, so each stage keeps only a
# ring of at most ``pp`` stashed stage-INPUT activations and recomputes its
# block forward in the per-microbatch vjp (remat at stage granularity).
# Peak activation memory per stage drops from O(M) to O(pp) — the enabler
# for large microbatch counts, where GPipe's stash is the OOM.
# ---------------------------------------------------------------------------

def resolve_inflight_window(pp: int, max_inflight: int = None) -> int:
    """The one place the dual-slot window defaults to 2*pp — the
    simulator, the stats, and the step builder's ring depth must agree
    or the table and the activation ring drift apart. Only None means
    "default" (a former ``or`` silently turned an explicit 0 into 2*pp);
    sub-1 windows cannot schedule anything and are rejected."""
    window = 2 * pp if max_inflight is None else int(max_inflight)
    if window < 1:
        raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
    return window


def simulate_1f1b_schedule(pp: int, num_microbatches: int,
                           max_inflight: int = None):
    """Greedy dependency-driven 1F1B schedule table (host-side, static).

    DUAL-SLOT ticks: each tick, each stage may fire its next forward AND
    its next backward (the literal one-forward-one-backward) — the
    runtime tick body executes one fwd micro-op and one bwd micro-op
    anyway, so a denser table converts the masked lowering's idle halves
    into scheduled work and roughly halves the tick count
    (~M + 2(pp-1) ticks instead of ~2(M+pp-1)).

    Firing rules (producers move one hop per tick, so deps must be
    STRICTLY earlier; backpressure keeps the single-slot receive buffers
    and the pp-deep activation ring sound):
    - F(m) on stage s: upstream F(m) done earlier (s>0); downstream has
      consumed F(m-1) (send would overwrite its recv slot); in-flight
      microbatches (next_f - next_b) < max_inflight (default 2*pp —
      the activation-ring capacity).
    - B(m) on stage s: downstream B(m) done earlier (s<pp-1) or own F(m)
      done earlier (last stage); upstream has consumed B(m-1).

    ``max_inflight`` bounds each stage's un-backproped microbatches (its
    activation-ring depth). Default 2*pp: the backward round trip takes
    ~2*pp lockstep ticks, so a 2*pp window is what keeps BOTH slots busy
    in steady state — still O(pp) memory (vs GPipe's O(M)); pass pp for
    the classic minimum-memory 1F1B, which halves the steady-state duty
    cycle in this lockstep model.

    Returns ``table``: list over ticks of per-stage ``(fm, bm)`` pairs,
    each entry an int microbatch or None. Baked into the jitted step as
    constant arrays, so the runtime program is lockstep-static."""
    M = num_microbatches
    W = resolve_inflight_window(pp, max_inflight)
    next_f = [0] * pp
    next_b = [0] * pp
    fwd_done = [[None] * M for _ in range(pp)]
    bwd_done = [[None] * M for _ in range(pp)]
    table = []
    t = 0
    while any(next_b[s] < M for s in range(pp)):
        # Backpressure may be released by a SAME-tick consumption: the
        # receiver reads its single recv slot during its micro-op, and
        # the sender's replacement only lands at end-of-tick (ppermute) —
        # so "receiver consumed my previous send" includes this tick.
        # Evaluate receivers before senders so those credits are final:
        # B flows toward stage 0 (ascending order decides s-1 before s),
        # F flows toward stage pp-1 (descending decides s+1 before s).
        # B decisions also precede F: the runtime tick body runs the
        # backward micro-op FIRST, so a same-tick B frees its ring slot
        # (and its window unit) for the same-tick F.
        brow = [None] * pp
        for s in range(pp):
            m = next_b[s]
            if m < M:
                if s == pp - 1:
                    ready = (fwd_done[s][m] is not None
                             and fwd_done[s][m] < t)
                else:
                    ready = (bwd_done[s + 1][m] is not None
                             and bwd_done[s + 1][m] < t)
                if ready and s > 0 and m > 0:
                    ready = (brow[s - 1] == m - 1
                             or (bwd_done[s - 1][m - 1] is not None
                                 and bwd_done[s - 1][m - 1] <= t))
                if ready:
                    brow[s] = m
        frow = [None] * pp
        for s in range(pp - 1, -1, -1):
            m = next_f[s]
            inflight = (next_f[s] - next_b[s]
                        - (1 if brow[s] is not None else 0))
            if m < M and inflight < W:
                ready = s == 0 or (fwd_done[s - 1][m] is not None
                                   and fwd_done[s - 1][m] < t)
                if ready and s < pp - 1 and m > 0:
                    ready = (frow[s + 1] == m - 1
                             or (fwd_done[s + 1][m - 1] is not None
                                 and fwd_done[s + 1][m - 1] <= t))
                if ready:
                    frow[s] = m
        row = list(zip(frow, brow))
        fired = False
        for s, (fm, bm) in enumerate(row):
            if fm is not None:
                fwd_done[s][fm] = t
                next_f[s] += 1
                fired = True
            if bm is not None:
                bwd_done[s][bm] = t
                next_b[s] += 1
                fired = True
        assert fired, f"1F1B schedule deadlock at tick {t} (pp={pp}, M={M})"
        table.append(row)
        t += 1
    return table


def schedule_stats(pp: int, num_microbatches: int,
                   max_inflight: int = None) -> dict:
    """Per-stage bubble accounting for both schedules (printed by the
    dryrun; the numbers a pipeline tuning session starts from).

    - gpipe: one fwd wave of M+pp-1 ticks and its autodiff mirror; every
      stage is busy M of each wave -> bubble = (pp-1)/(M+pp-1). Peak
      activation stash per stage ~ one per TICK (the scan saves its
      carry for the outer grad): M + pp - 1.
    - 1f1b: measured on the simulated table; peak stash is the ring
      high-water mark of in-flight (forwarded, not-yet-backproped)
      microbatches — bounded by max_inflight (default 2*pp)."""
    M = num_microbatches
    table = simulate_1f1b_schedule(pp, M, max_inflight)
    n_ticks = len(table)
    busy = [0] * pp          # ops fired per stage (out of 2 slots/tick)
    inflight = [0] * pp
    peak = [0] * pp
    for row in table:
        for s, (fm, bm) in enumerate(row):
            # B first, like the runtime tick body: a same-tick B frees
            # its ring slot before the F stashes into it
            if bm is not None:
                busy[s] += 1
                inflight[s] -= 1
            if fm is not None:
                busy[s] += 1
                inflight[s] += 1
                peak[s] = max(peak[s], inflight[s])
    g_ticks = M + pp - 1
    return {
        "gpipe": {"ticks_per_wave": g_ticks,
                  "bubble_fraction": round((pp - 1) / g_ticks, 4),
                  "peak_act_stash_per_stage": g_ticks},
        "1f1b": {"ticks": n_ticks,
                 "per_stage_busy": busy,
                 # each tick offers an F and a B slot; unused slots are
                 # the bubble (what the masked lowering pays for)
                 "bubble_fraction": round(
                     1.0 - sum(busy) / (2.0 * pp * n_ticks), 4),
                 "peak_act_stash_per_stage": max(peak)},
    }


def make_pipeline_train_step_1f1b(cfg: tfm.TransformerConfig, mesh: Mesh,
                                  num_microbatches: int, lr: float = 1e-3,
                                  aux_weight: float = 0.01,
                                  zero1: bool = False,
                                  predication: str = "masked",
                                  max_inflight: int = None):
    """1F1B twin of ``make_pipeline_train_step`` — same signature plus
    the 1F1B-only ``predication`` knob, identical math (bit-matching
    dropout keys per (microbatch, layer)), different memory law (see
    module section comment).

    Mechanics: one ``lax.scan`` over the simulated schedule's ticks inside
    a ``shard_map`` manual over ``pp``. Each tick, each stage runs its
    scheduled micro-op (``predication``: "masked" default — computed
    everywhere, effects selected; "cond" opt-in — lax.cond branches,
    idle ticks free, but see the lowering comment below for why that is
    only sound when no GSPMD collective lands inside a branch), then
    activations hop forward and gradients hop backward via two
    unconditional ``ppermute``s. The backward micro-op re-runs the stage
    forward from the stashed stage INPUT under ``jax.vjp``
    (stage-granular remat) — the last stage differentiates through the
    head+NLL with cotangent 1/M, others seed with the grad received from
    downstream."""
    pp = mesh.shape["pp"]
    M = num_microbatches
    assert cfg.n_layers % pp == 0
    layers_per_stage = cfg.n_layers // pp
    use_dropout = cfg.dropout_rate > 0.0
    # Micro-op gating has two lowerings. "masked" (the default) computes
    # every micro-op on every device and selects effects by the schedule
    # — idle ticks cost FLOPs, but every GSPMD-inserted collective runs
    # on every device's path. "cond" puts the micro-ops behind lax.cond
    # (idle ticks free) but is UNSOUND whenever GSPMD lowers ANY inner
    # op to a collective, because stages diverge on the predicate and
    # the collective's peers never arrive: observed deadlocks include tp
    # all-reduces of the Megatron matmuls, AND — even on a pure dp x pp
    # mesh — a reshard collective-permute GSPMD inserted for the
    # pos-table gradient when max_seq_len > T. Since GSPMD's choices
    # aren't statically checkable here, cond is opt-in for configs the
    # caller has validated; it additionally refuses model axes outright.
    assert predication in ("masked", "cond"), predication
    use_cond = predication == "cond"
    if use_cond:
        assert (mesh.shape.get("tp", 1) * mesh.shape.get("sp", 1)
                * mesh.shape.get("ep", 1)) == 1, (
            "predication='cond' deadlocks with tp/sp/ep in the mesh "
            "(GSPMD collectives inside divergent branches)")

    W = resolve_inflight_window(pp, max_inflight)
    table = simulate_1f1b_schedule(pp, M, W)
    ring = min(W, M)   # activation stash depth per stage (the memory law)
    n_ticks = len(table)
    is_f = np.zeros((n_ticks, pp), np.bool_)
    f_mb = np.zeros((n_ticks, pp), np.int32)
    is_b = np.zeros((n_ticks, pp), np.bool_)
    b_mb = np.zeros((n_ticks, pp), np.int32)
    for t, row in enumerate(table):
        for s, (fm, bm) in enumerate(row):
            if fm is not None:
                is_f[t, s], f_mb[t, s] = True, fm
            if bm is not None:
                is_b[t, s], b_mb[t, s] = True, bm

    stage_fn = _make_stage_fn(cfg, layers_per_stage)

    def fwd_bwd(params, tokens, targets, dropout_rng=None):
        """Fused pipelined forward+backward: returns (loss, grads)."""
        stage_blocks = params["blocks"]
        other = {k: v for k, v in params.items() if k != "blocks"}
        B, T = tokens.shape[1], tokens.shape[2]
        # the second OBSERVED cond deadlock is checkable here: with
        # max_seq_len > T, GSPMD lowers the pos-table slice/grad to a
        # reshard collective-permute inside the stage-0 branch
        assert not (use_cond and cfg.use_pos_emb and cfg.max_seq_len > T), (
            "predication='cond' deadlocks when max_seq_len > T with a "
            "positional table (GSPMD reshard inside a divergent branch); "
            "use the masked default or set max_seq_len == T")

        tis_f, tf_mb = jnp.asarray(is_f), jnp.asarray(f_mb)
        tis_b, tb_mb = jnp.asarray(is_b), jnp.asarray(b_mb)

        def pipelined(stage_blocks, other, tokens, targets, dropout_rng=None):
            stage = jax.lax.axis_index("pp")
            local_blocks = jax.tree.map(lambda x: x[0], stage_blocks)
            perm_f = [(i, (i + 1) % pp) for i in range(pp)]
            perm_b = [(i, (i - 1) % pp) for i in range(pp)]
            varying = lambda x: jax.lax.pcast(x, ("pp",), to="varying")

            zero_act = jnp.zeros((B, T, cfg.d_model), cfg.dtype)
            carry0 = (
                varying(jnp.zeros((ring, B, T, cfg.d_model), cfg.dtype)),
                varying(zero_act),                       # recv_f
                varying(zero_act),                       # recv_b
                # zeros_like(local_blocks) is born varying (sliced from the
                # pp-sharded input); zeros_like(other) is born invariant
                jax.tree.map(jnp.zeros_like, local_blocks),   # g_blocks
                jax.tree.map(lambda x: varying(jnp.zeros_like(x)), other),
                varying(jnp.zeros((), jnp.float32)),     # loss_sum
                varying(jnp.zeros((2,), jnp.float32)),   # aux_sum
            )

            def mb_rng(m):
                return (None if dropout_rng is None
                        else jax.random.fold_in(dropout_rng, m))

            def tick(carry, t):
                act_buf, recv_f, recv_b, g_blocks, g_other, loss_sum, \
                    aux_sum = carry
                isf = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(tis_f, t, 0, False),
                    stage, 0, False)
                fm = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(tf_mb, t, 0, False),
                    stage, 0, False)
                isb = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(tis_b, t, 0, False),
                    stage, 0, False)
                bm = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(tb_mb, t, 0, False),
                    stage, 0, False)

                # ---- backward micro-op FIRST (stage-granular remat vjp):
                # it reads the ring slot its microbatch stashed earlier,
                # and the same-tick forward may REUSE that slot (the
                # schedule's window credit assumes this B-before-F order)
                # shared preamble: cheap ring/table reads and the ONE
                # function both lowerings differentiate — defined once so
                # the cond and masked paths cannot drift apart.
                # ``other_v``: differentiate wrt a VARYING copy of the
                # replicated params — the vjp of an invariant input would
                # insert a psum (a collective inside a cond branch, where
                # idle stages never arrive -> deadlock). The per-stage
                # partial grads are psum'd once, outside the scan.
                h_in_b = jax.lax.dynamic_index_in_dim(act_buf, bm % ring,
                                                      0, False)
                tgt_m = jax.lax.dynamic_index_in_dim(targets, bm, 0, False)
                tok_b = jax.lax.dynamic_index_in_dim(tokens, bm, 0, False)
                rng_b = mb_rng(bm)
                is_last = stage == pp - 1
                other_v = jax.tree.map(varying, other)

                def through_head(blocks_, other_, h_):
                    h2, aux2 = stage_fn(h_, blocks_, stage, rng_b)
                    nll = tfm.nll_loss(tfm.lm_head(other_, h2, cfg), tgt_m)
                    return h2, aux2, nll

                def embed_grads(dh):
                    """d(embed output)/d(other) applied to dh — stage 0's
                    dh is the grad of the embedding output."""
                    _, evjp = jax.vjp(
                        lambda o: tfm.embed_tokens(o, tok_b, cfg), other_v)
                    (de,) = evjp(dh)
                    return de

                def do_bwd(g_blocks, g_other, recv_b, loss_sum):
                    def mid_only(blocks_, other_, h_):
                        h2, aux2 = stage_fn(h_, blocks_, stage, rng_b)
                        # varying like through_head's nll, so both cond
                        # branches type-match and take the same cotangent
                        return h2, aux2, varying(jnp.zeros((), jnp.float32))

                    def run_vjp(fn, ct_h2, ct_nll):
                        (h2, aux2, nll), vjp = jax.vjp(fn, local_blocks,
                                                       other_v, h_in_b)
                        # cotangents must carry the same varying-over-pp
                        # vma type as the outputs they correspond to
                        db, dother, dh = vjp(
                            (ct_h2,
                             varying(tfm.aux_weights(aux_weight) / M),
                             varying(ct_nll)))
                        return db, dother, dh, nll

                    db, dother, dh, nll = jax.lax.cond(
                        is_last,
                        lambda: run_vjp(through_head,
                                        jnp.zeros_like(recv_b),
                                        jnp.full((), 1.0 / M, jnp.float32)),
                        lambda: run_vjp(mid_only, recv_b,
                                        jnp.zeros((), jnp.float32)))
                    dother = jax.lax.cond(
                        stage == 0,
                        lambda d: jax.tree.map(jnp.add, d, embed_grads(dh)),
                        lambda d: d, dother)
                    g_blocks = jax.tree.map(jnp.add, g_blocks, db)
                    g_other = jax.tree.map(jnp.add, g_other, dother)
                    loss_sum = loss_sum + jnp.where(is_last, nll / M, 0.0)
                    send_b = jnp.where(stage == 0, jnp.zeros_like(dh), dh)
                    return g_blocks, g_other, send_b, loss_sum

                def do_bwd_masked(g_blocks, g_other, recv_b, loss_sum):
                    """Branch-free twin of do_bwd: ONE vjp through the
                    head for every stage with where-selected cotangents
                    (vjp is linear in cotangents, so ct_nll=0 makes the
                    head contribution exactly zero for middle stages),
                    embedding vjp always computed, all effects masked by
                    isb/stage. Costs head FLOPs on every stage but keeps
                    every GSPMD-inserted tp/dp collective on every
                    device's path."""
                    (h2, aux2, nll), vjp = jax.vjp(through_head,
                                                   local_blocks, other_v,
                                                   h_in_b)
                    ct_h2 = jnp.where(is_last, jnp.zeros_like(recv_b),
                                      recv_b)
                    # already varying: is_last derives from axis_index
                    ct_nll = jnp.where(is_last, 1.0 / M,
                                       0.0).astype(jnp.float32)
                    db, dother, dh = vjp(
                        (ct_h2,
                         varying(tfm.aux_weights(aux_weight) / M),
                         ct_nll))
                    de = embed_grads(dh)
                    dother = jax.tree.map(
                        lambda a, e: a + jnp.where(stage == 0, e,
                                                   jnp.zeros_like(e)),
                        dother, de)
                    g_blocks = jax.tree.map(
                        lambda g, d: g + jnp.where(isb, d,
                                                   jnp.zeros_like(d)),
                        g_blocks, db)
                    g_other = jax.tree.map(
                        lambda g, d: g + jnp.where(isb, d,
                                                   jnp.zeros_like(d)),
                        g_other, dother)
                    loss_sum = loss_sum + jnp.where(isb & is_last,
                                                    nll / M, 0.0)
                    send_b = jnp.where(isb & (stage > 0), dh,
                                       jnp.zeros_like(dh))
                    return g_blocks, g_other, send_b, loss_sum

                if use_cond:
                    g_blocks, g_other, send_b, loss_sum = jax.lax.cond(
                        isb, do_bwd,
                        lambda gb, go, rb, ls: (gb, go, jnp.zeros_like(rb),
                                                ls),
                        g_blocks, g_other, recv_b, loss_sum)
                else:
                    g_blocks, g_other, send_b, loss_sum = do_bwd_masked(
                        g_blocks, g_other, recv_b, loss_sum)

                # ---- forward micro-op -------------------------------
                def do_fwd(act_buf, recv_f, aux_sum):
                    tok_m = jax.lax.dynamic_index_in_dim(tokens, fm, 0,
                                                         False)
                    h0 = tfm.embed_tokens(other, tok_m, cfg)
                    h_in = jnp.where(stage == 0, h0, recv_f)
                    h_out, aux = stage_fn(h_in, local_blocks, stage,
                                          mb_rng(fm))
                    act_buf = jax.lax.dynamic_update_index_in_dim(
                        act_buf, h_in, fm % ring, 0)
                    return act_buf, h_out, aux_sum + aux

                if use_cond:
                    # real branch: idle ticks are free
                    act_buf, send_f, aux_sum = jax.lax.cond(
                        isf, do_fwd,
                        lambda ab, rf, ax: (ab, jnp.zeros_like(rf), ax),
                        act_buf, recv_f, aux_sum)
                else:
                    # masked: compute unconditionally, select the effect
                    nb, h_out, na = do_fwd(act_buf, recv_f, aux_sum)
                    act_buf = jnp.where(isf, nb, act_buf)
                    send_f = jnp.where(isf, h_out, jnp.zeros_like(h_out))
                    aux_sum = jnp.where(isf, na, aux_sum)

                # ---- unconditional hops (collectives stay out of conds).
                # Receives are STICKY: a hop only replaces the buffer when
                # the sender actually sent this tick (flag rides along),
                # so an idle sender's zeros can't clobber an activation the
                # receiver consumes on a later tick. The schedule's
                # backpressure rule guarantees one slot suffices.
                sent_f = jnp.where(isf & (stage < pp - 1), 1.0, 0.0)
                sent_b = jnp.where(isb & (stage > 0), 1.0, 0.0)
                got_f = jax.lax.ppermute(sent_f, "pp", perm_f)
                got_b = jax.lax.ppermute(sent_b, "pp", perm_b)
                new_f = jax.lax.ppermute(send_f, "pp", perm_f)
                new_b = jax.lax.ppermute(send_b, "pp", perm_b)
                recv_f = jnp.where(got_f > 0, new_f, recv_f)
                recv_b = jnp.where(got_b > 0, new_b, recv_b)
                return (act_buf, recv_f, recv_b, g_blocks, g_other,
                        loss_sum, aux_sum), None

            carry, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
            _, _, _, g_blocks, g_other, loss_sum, aux_sum = carry
            loss = jax.lax.psum(loss_sum, "pp")        # lives on last stage
            aux = jax.lax.psum(aux_sum, "pp") / M
            g_other = jax.tree.map(lambda g: jax.lax.psum(g, "pp"), g_other)
            g_blocks = jax.tree.map(lambda g: g[None], g_blocks)
            return loss + tfm.aux_weights(aux_weight) @ aux, g_blocks, g_other

        block_in_spec = jax.tree.map(lambda _: P("pp"), stage_blocks)
        other_spec = jax.tree.map(lambda _: P(), other)
        in_specs = [block_in_spec, other_spec, P(), P()]
        args = [stage_blocks, other, tokens, targets]
        if dropout_rng is not None:
            in_specs.append(P())
            args.append(dropout_rng)
        loss, g_blocks, g_other = jax.shard_map(
            pipelined,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(), block_in_spec, other_spec),
            axis_names=frozenset({"pp"}),
        )(*args)
        return loss, {**g_other, "blocks": g_blocks}

    def step(params, opt_state, tokens, targets, dropout_rng=None):
        if use_dropout:
            assert dropout_rng is not None, (
                "cfg.dropout_rate > 0: pass dropout_rng to the pipeline step")
        # the 1F1B schedule interleaves hand-rolled forward and backward
        # stages: only the optimizer phase has a scope here
        loss, grads = fwd_bwd(params, tokens, targets,
                              dropout_rng=dropout_rng)
        new_params, new_opt = scoped(SCOPE_OPT, tfm.adamw_update)(
            params, grads, opt_state, lr=lr)
        return loss, new_params, new_opt

    jitted = _wrap_step(step, cfg, mesh, pp, use_dropout, zero1=zero1)
    # the hand-rolled (loss, grads) function, for grad-level parity tests
    # against jax.grad of the GPipe twin's fwd_loss
    jitted.fwd_bwd = fwd_bwd
    return jitted
