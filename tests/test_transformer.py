"""Flagship transformer: dp/tp/sp/ep GSPMD step (the ppermute pipeline's
steps are in test_pipeline_steps.py).

Correctness oracle: the sharded run must match the single-device run on the
same data (f32, no dropout).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib


def tiny_cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
                max_seq_len=32, dtype=jnp.float32, remat=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def make_data(cfg, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, (batch, 16)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(targets)


def test_single_device_step_decreases_loss():
    cfg = tiny_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, mesh=None, lr=1e-2)
    tokens, targets = make_data(cfg)
    losses = []
    for _ in range(10):
        loss, params, opt = step(params, opt, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_dp_tp_sp_matches_single_device():
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=2, pp=1, tp=2, sp=2, ep=1)
    tokens, targets = make_data(cfg)

    params1 = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt1 = tfm.init_opt_state(params1)
    step1 = tfm.make_train_step(cfg, mesh=None, lr=1e-2)

    params8 = tfm.shard_params(tfm.init_params(jax.random.PRNGKey(0), cfg),
                               cfg, mesh)
    opt8 = tfm.init_opt_state(params8)
    step8 = tfm.make_train_step(cfg, mesh=mesh, lr=1e-2)

    for i in range(3):
        l1, params1, opt1 = step1(params1, opt1, tokens, targets)
        l8, params8, opt8 = step8(params8, opt8, tokens, targets)
        np.testing.assert_allclose(float(l1), float(l8), rtol=2e-4,
                                   err_msg=f"step {i}")


def test_moe_ep_step_runs():
    cfg = tiny_cfg(n_experts=4, d_ff=32)
    mesh = meshlib.make_mesh(dp=2, pp=1, tp=1, sp=1, ep=4)
    params = tfm.shard_params(tfm.init_params(jax.random.PRNGKey(1), cfg),
                              cfg, mesh)
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, mesh=mesh, lr=1e-2)
    tokens, targets = make_data(cfg)
    losses = []
    for _ in range(6):
        loss, params, opt = step(params, opt, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_zero1_matches_replicated_and_shards_state():
    """ZeRO-1: AdamW m/v shard over dp; the step is numerically identical
    to the replicated-optimizer step and the slots are ACTUALLY smaller
    per device."""
    cfg = tiny_cfg()
    mesh = meshlib.make_mesh(dp=4, pp=1, tp=2, sp=1, ep=1)
    tok, tgt = make_data(cfg, batch=8, seed=9)
    p0 = tfm.shard_params(tfm.init_params(jax.random.PRNGKey(2), cfg), cfg,
                          mesh)

    base = tfm.make_train_step(cfg, mesh=mesh, lr=1e-2)
    lb, pb, ob = base(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                      tok, tgt)

    z1 = tfm.make_train_step(cfg, mesh=mesh, lr=1e-2, zero1=True)
    oz0 = tfm.shard_opt_state(tfm.init_opt_state(p0), cfg, mesh, zero1=True)
    lz, pz, oz = z1(jax.tree.map(jnp.copy, p0), oz0, tok, tgt)

    np.testing.assert_allclose(float(lz), float(lb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(pz), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(oz["m"]), jax.tree.leaves(ob["m"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # the slots really shard over dp: some leaf's addressable shard is
    # smaller than the global array by the dp factor
    emb_m = oz["m"]["embed"]
    assert "dp" in tuple(emb_m.sharding.spec), emb_m.sharding
    shard_rows = emb_m.addressable_shards[0].data.shape[0]
    assert shard_rows * 4 <= emb_m.shape[0] * 2, (
        shard_rows, emb_m.shape)  # dp=4 sharding (tp may co-shard axis 1)
    # second step keeps working (donated sharded state round-trips)
    lz2, _, _ = z1(pz, oz, tok, tgt)
    assert np.isfinite(float(lz2))


def test_fused_lm_ce_matches_materializing_form():
    """The fused linear+CE flagship loss (forced on) must equal the
    logits-materializing form — loss and grads — and make_train_step must
    train with it."""
    import dataclasses
    cfg_on = tiny_cfg(fused_lm_ce=True)
    cfg_off = dataclasses.replace(cfg_on, fused_lm_ce=False)
    params = tfm.init_params(jax.random.PRNGKey(5), cfg_on)
    tok, tgt = make_data(cfg_on, batch=4, seed=6)

    # loss and `jax.grad` in one compiled program a form: run eagerly, each
    # is compiled op by op, the gradient a second time
    def loss_and_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tok, tgt, cfg, None)))(params)

    lf, gf = loss_and_grads(cfg_on)
    lo, go = loss_and_grads(cfg_off)
    np.testing.assert_allclose(float(lf), float(lo), rtol=1e-5)
    for k in ("head", "embed", "lnf_scale"):
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(go[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)

    step = tfm.make_train_step(cfg_on, lr=1e-2)
    opt = tfm.init_opt_state(params)
    l0, params, opt = step(params, opt, tok, tgt)
    l1, params, opt = step(params, opt, tok, tgt)
    assert float(l1) < float(l0)


def test_grad_accumulation_matches_big_batch():
    """accum_steps=4 over (4, 2, T) microbatches == one batch of 8 — the
    scan-accumulated grads and the big-batch grads drive identical updates
    (mean loss is linear in the batch)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=8,
                                dtype=jnp.float32, remat=False)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 64, (8, 8)), jnp.int32)
    tgt = jnp.roll(tok, -1, 1)

    big = tfm.make_train_step(cfg, lr=1e-2)
    p0 = tfm.init_params(jax.random.PRNGKey(0), cfg)
    loss_a, pa, _ = big(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                        tok, tgt)

    acc = tfm.make_train_step(cfg, lr=1e-2, accum_steps=4)
    loss_b, pb, _ = acc(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                        tok.reshape(4, 2, 8), tgt.reshape(4, 2, 8))

    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-5)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_dropout_trains_and_eval_is_deterministic():
    """cfg.dropout_rate > 0: the step takes a dropout_rng; same key -> same
    loss, different keys -> different losses; eval (no rng) is
    deterministic and ignores the rate; rate=0 path keeps the historical
    4-arg signature."""
    cfg = tiny_cfg(n_layers=2, max_seq_len=8, remat=True, dropout_rate=0.3)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 64, (4, 8)), jnp.int32)
    tgt = jnp.roll(tok, -1, 1)
    p0 = tfm.init_params(jax.random.PRNGKey(0), cfg)

    step = tfm.make_train_step(cfg, lr=1e-2)
    la, _, _ = step(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                    tok, tgt, jax.random.PRNGKey(1))
    lb, _, _ = step(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                    tok, tgt, jax.random.PRNGKey(1))
    lc, _, _ = step(jax.tree.map(jnp.copy, p0), tfm.init_opt_state(p0),
                    tok, tgt, jax.random.PRNGKey(2))
    assert float(la) == float(lb)          # same mask
    assert float(la) != float(lc)          # different mask

    # eval: no rng -> deterministic, identical to the rate=0 model
    e1, _ = tfm.forward(p0, tok, cfg)
    e2, _ = tfm.forward(p0, tok, tiny_cfg(n_layers=2, max_seq_len=8,
                                          remat=True))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-6)

    # a short dropout-on training run still learns
    params, opt = p0, tfm.init_opt_state(p0)
    key = jax.random.PRNGKey(3)
    first = None
    for i in range(30):
        key, sub = jax.random.split(key)
        loss, params, opt = step(params, opt, tok, tgt, sub)
        if i == 0:
            first = float(loss)
    assert float(loss) < first
