"""The ppermute pipeline's train steps, GPipe and 1F1B, on a virtual mesh.

Correctness oracle: the pipeline must match the non-pipelined forward within
fp tolerance, and the 1F1B step its GPipe twin loss for loss and gradient
for gradient.

Every (engine, config, mesh, microbatches, options) is built once a process
(`_step`) and its gradient function jitted once (`_grads`): the tests share
the steps they compare, each with fresh parameters (a step donates what it
is given, never itself).
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib
from hetu_tpu.parallel import pipeline as pplib
from test_transformer import tiny_cfg

ENGINES = {"gpipe": pplib.make_pipeline_train_step,
           "1f1b": pplib.make_pipeline_train_step_1f1b}


@functools.lru_cache(maxsize=None)
def _step(engine, cfg, mesh, M, **options):
    return ENGINES[engine](cfg, mesh, num_microbatches=M, lr=1e-2, **options)


@functools.lru_cache(maxsize=None)
def _grads(engine, cfg, mesh, M, **options):
    """-> jitted (params, tokens, targets) -> (loss, gradients): jax's own
    of the GPipe loss, the hand-rolled backward of 1F1B."""
    step = _step(engine, cfg, mesh, M, **options)
    return jax.jit(jax.value_and_grad(step.fwd_loss) if engine == "gpipe"
                   else step.fwd_bwd)


def _batches(cfg, M, mb, seed):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, (M, mb, 16)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, axis=2))


def _train(engine, cfg, mesh, M, data, seed, steps, key=None):
    """-> (losses, params) of `steps` steps from `init_pipeline_params` at
    `seed`; with `key`, step i drops out under fold_in(key, i)."""
    p = pplib.init_pipeline_params(jax.random.PRNGKey(seed), cfg, mesh)
    o = tfm.init_opt_state(p)
    step = _step(engine, cfg, mesh, M)
    losses = []
    for i in range(steps):
        rng = () if key is None else (jax.random.fold_in(key, i),)
        loss, p, o = step(p, o, *data, *rng)
        losses.append(float(loss))
    return losses, p


def _assert_same_grads(got, want):
    got = dict(jax.tree.flatten_with_path(got)[0])
    for path, ref in jax.tree.flatten_with_path(want)[0]:
        scale = float(np.max(np.abs(np.asarray(ref)))) or 1.0
        np.testing.assert_allclose(np.asarray(got[path]), np.asarray(ref),
                                   atol=5e-6 * max(scale, 1.0), rtol=2e-4,
                                   err_msg=str(path))


def _dense_loss(cfg, data, seed):
    """The oracle: the plain loss on the flat batch (same data, same init)."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    tokens, targets = (x.reshape(-1, x.shape[-1]) for x in data)
    return float(tfm.loss_fn(params, tokens, targets, cfg, None))


def test_pipeline_matches_dense():
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=2, pp=4, tp=1, sp=1, ep=1)
    data = _batches(cfg, 4, 4, seed=2)
    losses, _ = _train("gpipe", cfg, mesh, 4, data, seed=3, steps=6)
    np.testing.assert_allclose(losses[0], _dense_loss(cfg, data, 3),
                               rtol=2e-4)
    # and training progresses
    assert losses[-1] < losses[0], losses


def test_pipeline_dropout_matches_trunk():
    """pp2 training WITH dropout must match the single-device trunk running
    grad accumulation with the same key: the pipeline folds key(mb, global
    layer) exactly like make_train_step's fold_in(rng, mi) -> encode's
    fold_in(·, li), so losses and updated params agree step for step."""
    cfg = tiny_cfg(dropout_rate=0.25)
    mesh = meshlib.make_mesh(dp=4, pp=2, tp=1, sp=1, ep=1)
    M = 2
    data = _batches(cfg, M, 4, seed=11)

    p0 = tfm.init_params(jax.random.PRNGKey(7), cfg)
    trunk = tfm.make_train_step(cfg, lr=1e-2, accum_steps=M)
    tparams, topt = jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0)
    key = jax.random.PRNGKey(42)
    tlosses = []
    for step in range(3):
        tl, tparams, topt = trunk(tparams, topt, *data,
                                  jax.random.fold_in(key, step))
        tlosses.append(float(tl))
    plosses, pparams = _train("gpipe", cfg, mesh, M, data, seed=7, steps=3,
                              key=key)
    np.testing.assert_allclose(plosses, tlosses, rtol=2e-4)
    # updated params agree (pipeline blocks are (pp, L/pp, ...) stacked)
    for k, v in tparams["blocks"].items():
        np.testing.assert_allclose(
            np.asarray(pparams["blocks"][k]),
            np.asarray(v.reshape(pparams["blocks"][k].shape)), atol=2e-4,
            err_msg=k)
    # a forgotten key fails loudly (jit arity or the explicit assert)
    with pytest.raises((AssertionError, ValueError)):
        _step("gpipe", cfg, mesh, M)(pparams, tfm.init_opt_state(pparams),
                                     *data)


def test_1f1b_schedule_is_dependency_valid_and_stash_bounded():
    """Every stage runs M forwards + M backwards; activations/grads move
    one hop per tick (producer strictly earlier); in-flight microbatches
    per stage never exceed pp (the memory law 1F1B exists for); the
    dual-slot table keeps the tick count near M + 2(pp-1) — the masked
    lowering's per-tick fwd+bwd execution is then almost fully used."""
    for pp, M in [(2, 1), (2, 4), (4, 3), (4, 8), (8, 16)]:
        table = pplib.simulate_1f1b_schedule(pp, M)
        fwd_t = [[None] * M for _ in range(pp)]
        bwd_t = [[None] * M for _ in range(pp)]
        for t, row in enumerate(table):
            for s, (fm, bm) in enumerate(row):
                if fm is not None:
                    fwd_t[s][fm] = t
                if bm is not None:
                    bwd_t[s][bm] = t
        # dual slots keep the schedule dense: fill + M + drain, not 2M
        assert len(table) <= M + 2 * pp + 2, (pp, M, len(table))
        for s in range(pp):
            assert all(v is not None for v in fwd_t[s] + bwd_t[s])
            for m in range(M):
                if s > 0:
                    assert fwd_t[s][m] > fwd_t[s - 1][m]
                if s < pp - 1:
                    assert bwd_t[s][m] > bwd_t[s + 1][m]
                else:
                    assert bwd_t[s][m] > fwd_t[s][m]
                # single-slot receive buffers suffice: a stage consumes
                # each activation/grad no later than the tick its producer
                # sends the NEXT one (the runtime's sticky flagged
                # receives depend on this backpressure property)
                if s > 0 and m + 1 < M:
                    assert fwd_t[s][m] <= fwd_t[s - 1][m + 1]
                if s < pp - 1 and m + 1 < M:
                    assert bwd_t[s][m] <= bwd_t[s + 1][m + 1]
        stats = pplib.schedule_stats(pp, M)
        # default window 2*pp keeps both tick slots busy in steady state
        # while the stash stays O(pp) — far under GPipe's O(M)
        assert stats["1f1b"]["peak_act_stash_per_stage"] <= min(2 * pp, M)
        assert stats["gpipe"]["peak_act_stash_per_stage"] == M + pp - 1
        # the classic minimum-memory window still schedules validly
        lo = pplib.schedule_stats(pp, M, max_inflight=pp)
        assert lo["1f1b"]["peak_act_stash_per_stage"] <= min(pp, M)
    # exact tick counts: a greedy-simulator regression that loosens the
    # schedule shows up here before it shows up as lost throughput
    assert {(pp, M): pplib.schedule_stats(pp, M)["1f1b"]["ticks"]
            for pp, M in [(2, 1), (2, 4), (4, 3), (4, 8), (8, 16)]} == {
        (2, 1): 4, (2, 4): 7, (4, 3): 10, (4, 8): 15, (8, 16): 31}
    # the steady state really densifies: at M >> pp the slot bubble
    # approaches 2(pp-1)/M (measured 9.9% at pp4/M64)
    assert pplib.schedule_stats(4, 64)["1f1b"]["bubble_fraction"] < 0.12


def test_1f1b_matches_gpipe_and_dense():
    """The 1F1B step is the GPipe step's drop-in twin: same loss as the
    dense oracle on the flat batch, same losses as GPipe across steps,
    and gradient-for-gradient equality with jax.grad(GPipe loss) —
    grads, not post-AdamW params, are the noise-free place to pin."""
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=2, pp=4, tp=1, sp=1, ep=1)
    M = 4
    data = _batches(cfg, M, 4, seed=2)

    g_losses, _ = _train("gpipe", cfg, mesh, M, data, seed=3, steps=3)
    f_losses, _ = _train("1f1b", cfg, mesh, M, data, seed=3, steps=3)
    np.testing.assert_allclose(f_losses[0], _dense_loss(cfg, data, 3),
                               rtol=2e-4)
    np.testing.assert_allclose(f_losses, g_losses, rtol=2e-5)

    # grad-level parity: the 1F1B hand-rolled backward equals
    # jax.grad(GPipe fwd_loss) exactly (this is the noise-free pin —
    # params-after-AdamW comparisons amplify last-bit grad differences to
    # ~lr near sign flips, so grads are the right place to assert)
    p = pplib.init_pipeline_params(jax.random.PRNGKey(3), cfg, mesh)
    _, g_ref = _grads("gpipe", cfg, mesh, M)(p, *data)
    _, g_f1b = _grads("1f1b", cfg, mesh, M)(p, *data)
    _assert_same_grads(g_f1b, g_ref)


def test_1f1b_cond_predication_matches_and_guards_model_axes():
    """The opt-in cond lowering (idle ticks free) matches the masked
    default on a validated dp x pp config, and refuses model axes
    outright (GSPMD collectives inside divergent branches deadlock)."""
    cfg = tiny_cfg(max_seq_len=16)   # T == max_seq_len: no pos reshard
    mesh = meshlib.make_mesh(dp=2, pp=4)
    M = 4
    data = _batches(cfg, M, 4, seed=2)
    p = pplib.init_pipeline_params(jax.random.PRNGKey(3), cfg, mesh)
    lm, _ = _grads("1f1b", cfg, mesh, M)(p, *data)
    lc, _ = _grads("1f1b", cfg, mesh, M, predication="cond")(p, *data)
    np.testing.assert_allclose(float(lc), float(lm), rtol=1e-6)

    with pytest.raises(AssertionError, match="cond"):
        pplib.make_pipeline_train_step_1f1b(
            cfg, meshlib.make_mesh(dp=2, pp=2, tp=2),
            num_microbatches=M, predication="cond")

    # the pos-table reshard deadlock (max_seq_len > T) is refused at
    # trace time instead of hanging at runtime
    cfg32 = tiny_cfg()   # max_seq_len 32 > T 16
    p32 = pplib.init_pipeline_params(jax.random.PRNGKey(3), cfg32, mesh)
    with pytest.raises(AssertionError, match="max_seq_len"):
        _grads("1f1b", cfg32, mesh, M, predication="cond")(p32, *data)


def test_1f1b_grads_match_gpipe_on_tp_mesh():
    """With tp in the mesh the 1F1B step runs its MASKED lowering (cond
    branches would put GSPMD's tp collectives on divergent paths); grads
    must still equal jax.grad of the GPipe loss."""
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=2, pp=2, tp=2, sp=1, ep=1)
    M = 3
    data = _batches(cfg, M, 4, seed=5)
    p = pplib.init_pipeline_params(jax.random.PRNGKey(3), cfg, mesh)
    _, g_ref = _grads("gpipe", cfg, mesh, M)(p, *data)
    loss, g_f1b = _grads("1f1b", cfg, mesh, M)(p, *data)
    assert np.isfinite(float(loss))
    _assert_same_grads(g_f1b, g_ref)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_pipeline_zero1_matches_replicated_and_shards_state(engine):
    """ZeRO-1 on the pipeline steps: same grads -> same update (the
    trunk's zero1 recipe applied to pp-stacked params), slots genuinely
    dp-sharded, donated sharded state round-trips a second step."""
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=4, pp=2, tp=1, sp=1, ep=1)
    M = 2
    data = _batches(cfg, M, 4, seed=3)
    p0 = pplib.init_pipeline_params(jax.random.PRNGKey(5), cfg, mesh)

    base = _step(engine, cfg, mesh, M)
    lb, pb, ob = base(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                      *data)

    z1 = _step(engine, cfg, mesh, M, zero1=True)
    oz0 = pplib.shard_pipeline_opt_state(tfm.init_opt_state(p0), cfg, mesh,
                                         zero1=True)
    lz, pz, oz = z1(jax.tree.map(jnp.copy, p0), oz0, *data)

    np.testing.assert_allclose(float(lz), float(lb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(pz), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(oz["m"]), jax.tree.leaves(ob["m"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # the slots really shard over dp (embed m: replicated param, dp slot)
    emb_m = oz["m"]["embed"]
    assert "dp" in tuple(emb_m.sharding.spec), emb_m.sharding
    shard_rows = emb_m.addressable_shards[0].data.shape[0]
    assert shard_rows * 4 == emb_m.shape[0], (shard_rows, emb_m.shape)
    # second step keeps working (donated sharded state round-trips)
    lz2, _, _ = z1(pz, oz, *data)
    assert np.isfinite(float(lz2))


def test_1f1b_dropout_matches_gpipe():
    """Dropout keys are per (microbatch, global layer) in both schedules,
    so 1F1B with dropout matches GPipe loss- and param-wise step for
    step (the backward recompute re-draws the identical masks)."""
    cfg = tiny_cfg(dropout_rate=0.25)
    mesh = meshlib.make_mesh(dp=4, pp=2, tp=1, sp=1, ep=1)
    M = 2
    data = _batches(cfg, M, 4, seed=11)
    key = jax.random.PRNGKey(42)
    g_losses, g_params = _train("gpipe", cfg, mesh, M, data, 7, 3, key)
    f_losses, f_params = _train("1f1b", cfg, mesh, M, data, 7, 3, key)
    np.testing.assert_allclose(f_losses, g_losses, rtol=2e-5)
    for k in f_params["blocks"]:
        np.testing.assert_allclose(np.asarray(f_params["blocks"][k]),
                                   np.asarray(g_params["blocks"][k]),
                                   atol=1e-5, err_msg=k)


def test_pipeline_with_moe_and_remat():
    """pp x ep x dp with remat — the combination that exercises pcast on
    every scan carry in the manual region."""
    cfg = tiny_cfg(n_experts=2, d_ff=32, remat=True)
    mesh = meshlib.make_mesh(dp=2, pp=2, tp=1, sp=1, ep=2)
    losses, _ = _train("gpipe", cfg, mesh, 4, _batches(cfg, 4, 4, seed=5),
                       seed=4, steps=4)
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
