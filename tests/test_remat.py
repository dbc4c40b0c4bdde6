"""What the trunk's ``jax.checkpoint`` keeps (``transformer._remat_names``,
``transformer.encode``): the rule as a pure function of shapes, parameter
bytes, the block and a device limit passed in, and the mechanism read from
the backward scan's jaxpr. CPU: counts and gradients, no time."""
import dataclasses
import functools
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import bert, hf_granite, hf_keye, hf_olmoe, hf_ouro
from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib
from hetu_tpu.telemetry.tracing import (REMAT_ATTN_K, REMAT_ATTN_LSE,
                                        REMAT_ATTN_O, REMAT_ATTN_Q,
                                        REMAT_ATTN_V, REMAT_CANDIDATES,
                                        REMAT_DSA_GRADS, REMAT_DSA_MASK,
                                        REMAT_NORM1_IN, REMAT_NORM2_IN,
                                        REMAT_X1, REMAT_X2)

from model_harness import sub_jaxprs
from test_transformer import tiny_cfg

GiB = 2 ** 30
ALL = (REMAT_X1, REMAT_X2, REMAT_ATTN_O, REMAT_ATTN_LSE)
# what the split path (RoPE, QK-norm, a multiplier, grouped heads) and the
# sandwich norms add (PR 36): BERT writes neither
QKV = (REMAT_ATTN_Q, REMAT_ATTN_K, REMAT_ATTN_V)
SANDWICH = (REMAT_NORM1_IN, REMAT_NORM2_IN)
# a dsa stack's own two, ahead of the ordered ones (PRs 48 and 64)
DSA = (REMAT_DSA_GRADS, REMAT_DSA_MASK)
EVERY = ALL + QKV + SANDWICH


def _published(config):
    """The `config.json` a benchmark cell of ``config`` runs."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", config, "config.json")
    with open(path) as f:
        return json.load(f)


def _bert():
    """BERT-base as the benchmark's adapter builds it, flash attention as
    the TPU resolves it."""
    cfg = bert.BertConfig.hf(dtype=jnp.bfloat16, attn_impl="flash")
    return cfg.trunk(), jax.eval_shape(
        lambda: bert.init_params(jax.random.PRNGKey(0), cfg))


def _olmoe():
    """The cell olmoe-1b-7b.pretrain-seq4096: one layer of 64 experts."""
    cfg = hf_olmoe.config_from_hf(_published("olmoe-1b-7b"),
                                  dtype=jnp.bfloat16, attn_impl="flash")
    assert (cfg.n_layers, cfg.n_experts, cfg.n_experts_per_tok) == (1, 64, 8)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert 620e6 < tfm.count_params(params) < 632e6
    return cfg, params


def _ouro(**overrides):
    """The cell ouro-2.6b.pretrain-seq4096-b1: 6 layers applied 4 times."""
    cfg = hf_ouro.config_from_hf(_published("ouro-2.6b"), dtype=jnp.bfloat16,
                                 attn_impl="flash")
    assert (cfg.n_layers, cfg.n_loops, cfg.sandwich_norm) == (6, 4, True)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    return dataclasses.replace(cfg, **overrides), params


def _granite():
    """The cell granite-4.0-h-micro.pretrain-seq8192-b1: five Mamba-2 layers,
    then ONE attention layer of 32 query heads on 8 k/v heads, no RoPE (the
    split path by its multiplier and its grouped heads)."""
    cfg = hf_granite.config_from_hf(_published("granite-4.0-h-micro"),
                                    dtype=jnp.bfloat16, attn_impl="flash")
    assert (cfg.n_heads, cfg.kv_heads, cfg.rope) == (32, 8, False)
    assert tfm.layer_runs(cfg) == (("mamba", 5), ("attention", 1))
    return cfg, jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))


def _keye():
    """The cell keye-vl-2.0-30b-a3b.pretrain-seq16384-ep8share: four layers
    of learned sparse attention, an indexer of five leaves each."""
    cfg = hf_keye.config_from_hf(_published("keye-vl-2.0-30b-a3b"),
                                 dtype=jnp.bfloat16, attn_impl="flash")
    assert tfm.layer_runs(cfg) == (("dsa", 4),)
    return cfg, jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))


def _rule(model, batch, seq, dp, limit_gib, bias=True):
    cfg, params = model()
    mesh = (meshlib.make_mesh(dp=dp, devices=jax.devices()[:dp])
            if dp > 1 else None)
    h = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype)
    attn_bias = (jax.ShapeDtypeStruct((batch, 1, 1, seq), jnp.float32)
                 if bias else None)
    return tfm._remat_names(
        cfg, params, h, mesh, attn_bias,
        bytes_limit=None if limit_gib is None else int(limit_gib * GiB))


@pytest.mark.parametrize("model,batch,seq,dp,limit_gib,names,held_gib", [
    # BERT reads its projection in place and has no sandwich norm: the four
    # names and the 3,492 MiB they were before PR 36 (24 x 96 + 12 x 99)
    pytest.param(_bert, 128, 512, 1, 16, ALL, (3.41, 3.4102),
                 id="bert-seq512"),
    pytest.param(_bert, 512, 128, 1, 16, ALL, (3.41, 3.4102),
                 id="bert-seq128"),
    pytest.param(_bert, 512, 512, 4, 16, ALL, (3.41, 3.4102),
                 id="bert-seq512-dp4"),
    # the limit a v5e reports: what the chip runs are held to
    pytest.param(_bert, 128, 512, 1, 15.75, ALL, (3.41, 3.4102),
                 id="bert-seq512-v5e-limit"),
    pytest.param(_bert, 512, 128, 1, 15.75, ALL, (3.41, 3.4102),
                 id="bert-seq128-v5e-limit"),
    pytest.param(_olmoe, 8, 4096, 1, 16, (), (0, 0), id="olmoe-seq4096"),
    pytest.param(_olmoe, 8, 4096, 1, 15.75, (), (0, 0),
                 id="olmoe-seq4096-v5e-limit"),
    # between the two: 7.6 GiB of state, 24 block applications of 16 MiB.
    # The split path under sandwich norms: 0.76 GiB of x1, o, lse, then q, k,
    # v (1.125) and the two norm inputs (0.75)
    pytest.param(_ouro, 1, 4096, 1, 15.75, EVERY, (2.62, 2.66),
                 id="ouro-seq4096-b1-v5e-limit"),
    # under water by the Mamba block's residuals: nothing, as before PR 36
    pytest.param(_granite, 1, 8192, 1, 15.75, (), (0, 0),
                 id="granite-seq8192-b1-v5e-limit"),
    # given room: six x1 of 32 MiB, ONE attention layer's o + lse (33), and
    # its q (32) with k and v at 8 of 32 heads (8 each): 273 MiB. At
    # `n_heads` k and v would make it 321
    pytest.param(_granite, 1, 8192, 1, 24, ALL + QKV, (0.2666, 0.2667),
                 id="granite-seq8192-b1-room"),
    pytest.param(_ouro, 1, 4096, 1, None, (), (0, 0), id="ouro-no-limit"),
    pytest.param(_bert, 128, 512, 1, None, (), (0, 0), id="bert-no-limit"),
    pytest.param(_olmoe, 8, 4096, 1, None, (), (0, 0), id="olmoe-no-limit"),
    # a dsa stack keeps its indexers' gradient at ANY limit: four layers x
    # (2,048 x (1,024 + 64 + 16) + 2 x 64) float32 = 34.5 MiB. The selection's
    # bits packed by query next (PR 64), four layers x 2 x 16,384 x 512 words
    # = 0.25 GiB, while the limit less its margin, 6.94 GiB of state and four
    # layer inputs of 128 MiB has room: the v5e's has 7.8 GiB, though the
    # budget the ordered names are held to reads negative there; 1 GiB has
    # none; 7.9 GiB has 0.21, the gradients' 0.03 and not 0.25 more. The
    # ordered names behind them see the bits taken: four x1 of 128 MiB, o +
    # lse (256 + 4) and q, k, v (256 + 2 x 32) a layer; at 17.5 GiB the
    # budget's 0.70 would hold x1's 0.5 without the bits and does not with
    pytest.param(_keye, 2, 16384, 1, 15.75, DSA, (0.2836, 0.2838),
                 id="keye-seq16384-v5e-limit"),
    pytest.param(_keye, 2, 16384, 1, 1, (REMAT_DSA_GRADS,),
                 (0.03369, 0.0337), id="keye-seq16384-no-room"),
    pytest.param(_keye, 2, 16384, 1, 7.9, (REMAT_DSA_GRADS,),
                 (0.03369, 0.0337), id="keye-seq16384-no-room-for-the-bits"),
    pytest.param(_keye, 2, 16384, 1, 17.5, DSA, (0.2836, 0.2838),
                 id="keye-seq16384-bits-ahead-of-x1"),
    pytest.param(_keye, 2, 16384, 1, 24, DSA + ALL + QKV, (3.049, 3.05),
                 id="keye-seq16384-room"),
    pytest.param(_keye, 2, 16384, 1, None, (), (0, 0), id="keye-no-limit"),
])
def test_remat_names_by_bytes(model, batch, seq, dp, limit_gib, names,
                              held_gib):
    got, held, budget = _rule(model, batch, seq, dp, limit_gib,
                              bias=model is _bert)
    assert got == names
    assert held_gib[0] * GiB <= held <= held_gib[1] * GiB
    if model is _keye:
        # the two names no budget is asked for: on a full chip they are alone
        assert not set(DSA) & set(sum(REMAT_CANDIDATES, ()))
        ordered = tuple(n for n in got if n not in DSA)
        assert not ordered or 0 <= held <= budget
        if limit_gib:
            # the bits against the limit less state and layer inputs, the
            # block's estimated residuals left out
            cfg, params = model()
            room = (limit_gib * GiB * (1 - tfm._REMAT_MARGIN)
                    - tfm._state_bytes(cfg, params, None)
                    - cfg.n_layers * batch * seq * cfg.d_model * 2)
            grads = 4 * (2048 * (1024 + 64 + 16) + 2 * 64) * 4
            assert (REMAT_DSA_MASK in got) == (grads + GiB // 4 <= room)
        return
    assert not set(DSA) & set(got)
    assert held <= max(budget, 0)
    if model is _olmoe and limit_gib:
        # not by a hair: the block's own residuals put it far under water
        assert budget < -1.5 * GiB
    if names and limit_gib:
        assert budget - held > 1.5 * GiB


def test_remat_names_count_every_application_of_a_looped_model():
    """n_layers x n_loops block applications a step: the stack of layer
    inputs and each kept name, against the same weights run once."""
    act = 1 * 4096 * 2048 * 2                # one (B, T, D) bf16 activation
    lse = 4096 * 16 * 4
    names4, held4, budget4 = _rule(_ouro, 1, 4096, 1, 15.75, bias=False)
    names1, held1, budget1 = _rule(functools.partial(_ouro, n_loops=1),
                                   1, 4096, 1, 15.75, bias=False)
    assert names4 == names1 == EVERY
    # x1; o and lse; q, k, v (k and v at all 16 heads); the two norms' inputs
    assert held1 == 6 * (act + (act + lse) + 3 * act + 2 * act)
    assert held4 == 24 * (7 * act + lse) == 4 * held1
    # the same state (the params handed in are the looped model's): the
    # budgets differ by the 18 more layer inputs the scans keep
    assert budget1 - budget4 == 18 * act
    # limits at which one pass keeps more of the chain than four passes do
    for limit_gib, looped, once in ((12, ALL + QKV, EVERY),
                                    (10, ALL[:2], EVERY), (9.5, (), ALL)):
        assert _rule(_ouro, 1, 4096, 1, limit_gib, bias=False)[0] == looped
        assert _rule(functools.partial(_ouro, n_loops=1), 1, 4096, 1,
                     limit_gib, bias=False)[0] == once


# the chain a model can write, shortest first: BERT (in place, no sandwich
# norm) skips the last two groups, Ouro (RoPE under sandwich norms) none
CHAINS = {"bert": (_bert, 128, 512, [(), ALL[:2], ALL]),
          "ouro": (_ouro, 1, 4096, [(), ALL[:2], ALL, ALL + QKV, EVERY])}


@pytest.mark.parametrize("limit_gib", [16, 14, 12, 11, 10.5, 10, 9.5, 9,
                                       8.5, 8, 6, 2])
@pytest.mark.parametrize("chain", list(CHAINS))
def test_remat_names_shrink_as_a_prefix(chain, limit_gib):
    """As the limit falls the set shrinks in the stated order, {x1, x2},
    o and lse, then (where the model writes them) q, k, v and the sandwich
    norms' inputs: never the later candidate without the earlier."""
    assert sum(REMAT_CANDIDATES, ()) == EVERY
    model, batch, seq, prefixes = CHAINS[chain]
    rule = functools.partial(_rule, model, batch, seq, 1,
                             bias=model is _bert)
    got, held, budget = rule(limit_gib)
    assert got in prefixes
    more, held_more, budget_more = rule(limit_gib + 1)
    assert len(more) >= len(got) and held_more >= held
    assert budget_more - budget == pytest.approx(
        GiB * (1 - tfm._REMAT_MARGIN), abs=2)
    if limit_gib == 2:
        assert got == ()


@pytest.mark.parametrize("chain", list(CHAINS))
def test_remat_names_reach_every_prefix(chain):
    model, batch, seq, prefixes = CHAINS[chain]
    seen = {_rule(model, batch, seq, 1, g / 4, bias=model is _bert)[0]
            for g in range(8, 65)}
    assert seen == set(prefixes)


# ---------------------------------------------------------------------------
# the mechanism: what the backward scan of `encode` holds
# ---------------------------------------------------------------------------

def _count(jaxpr, into):
    """Matmuls and kernel calls of a jaxpr, nested calls included (not the
    kernels' own bodies)."""
    for e in jaxpr.eqns:
        if e.primitive.name in ("dot_general", "pallas_call"):
            into[e.primitive.name] = into.get(e.primitive.name, 0) + 1
        if e.primitive.name != "pallas_call":
            for sub in sub_jaxprs(e):
                _count(sub, into)
    return into


def _backward_scan_counts(fn, *args):
    """{primitive: count} inside the reversed scan over the layers of
    ``jax.grad(fn)``: one layer's backward pass and what it recomputes."""
    def scans(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "scan" and e.params["reverse"]:
                yield e
            else:
                for sub in sub_jaxprs(e):
                    yield from scans(sub)

    found = list(scans(jax.make_jaxpr(jax.grad(fn))(*args).jaxpr))
    assert len(found) == 1, found
    return _count(found[0].params["jaxpr"].jaxpr,
                  {"dot_general": 0, "pallas_call": 0})


def _post_ln_dense():
    return tiny_cfg(d_model=128, n_heads=2, n_layers=2, d_ff=256,
                    max_seq_len=128, attn_impl="flash", post_ln=True,
                    attn_proj_bias=True, causal=False, gelu_exact=True)


def _pre_ln_rope_qknorm():
    return tiny_cfg(d_model=128, n_heads=2, n_layers=2, d_ff=256,
                    max_seq_len=128, attn_impl="flash", norm="rmsnorm",
                    rope=True, qk_norm=True, mlp="swiglu", use_pos_emb=False)


def _sandwich_rope():
    """Ouro's block: RoPE (so the split path), a norm on either side of each
    sublayer, SwiGLU."""
    return tiny_cfg(d_model=128, n_heads=2, n_layers=2, d_ff=256,
                    max_seq_len=128, attn_impl="flash", norm="rmsnorm",
                    rope=True, mlp="swiglu", use_pos_emb=False,
                    sandwich_norm=True)


@pytest.mark.parametrize("dp", [1, 4], ids=["one-device", "dp4-shard_map"])
@pytest.mark.parametrize("config,bare,recomputed,kept", [
    # the bare checkpoint runs wqkv, wo, w1, w2 (ln2 reads its sum) again;
    # named: wqkv (the kernels read it in place: not a candidate) and w1
    pytest.param(_post_ln_dense, 4, 2, ALL, id="post-ln-dense"),
    # bare: wqkv, wo, w1, w3. Named: w1, w3 and STILL wqkv: q, k and v are
    # kept, so RoPE does not run again, but QK-norm's backward pass reads
    # the q and k the projection wrote, which no name covers
    pytest.param(_pre_ln_rope_qknorm, 4, 3, ALL + QKV,
                 id="pre-ln-rope-qknorm-swiglu"),
    # bare: wqkv, wo, w1, w3, w2 (a norm reads wo's and w2's outputs).
    # Named: w1 and w3 alone
    pytest.param(_sandwich_rope, 5, 2, EVERY, id="sandwich-rope-swiglu"),
])
def test_named_checkpoint_skips_kernel_and_output_matmuls(monkeypatch, config,
                                                bare, recomputed, kept, dp):
    cfg = config()
    mesh = (meshlib.make_mesh(dp=dp, devices=jax.devices()[:dp])
            if dp > 1 else None)
    params = tfm.init_trunk_params(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model),
                          cfg.dtype)

    def loss(params, h, cfg):
        out, _ = tfm.encode(params, h, cfg, mesh)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    plain = functools.partial(loss, cfg=cfg)              # remat=False
    remat = functools.partial(loss, cfg=dataclasses.replace(cfg, remat=True))
    # the CPU reports no limit: nothing is named, the bare checkpoint
    assert tfm._remat_names(remat.keywords["cfg"], params, h, mesh)[0] == ()
    counts = {"plain": _backward_scan_counts(plain, params, h),
              "bare": _backward_scan_counts(remat, params, h)}
    grads = {"plain": jax.jit(jax.grad(plain, argnums=(0, 1)))(params, h),
             "bare": jax.jit(jax.grad(remat, argnums=(0, 1)))(params, h)}
    monkeypatch.setattr(tfm, "_device_bytes_limit", lambda: 64 * GiB)
    assert tfm._remat_names(remat.keywords["cfg"], params, h, mesh)[0] == kept
    counts["named"] = _backward_scan_counts(remat, params, h)
    grads["named"] = jax.jit(jax.grad(remat, argnums=(0, 1)))(params, h)

    # the bare checkpoint runs the layer's forward again, kernel and all
    assert counts["bare"]["pallas_call"] == counts["plain"]["pallas_call"] + 1
    assert (counts["bare"]["dot_general"]
            == counts["plain"]["dot_general"] + bare)
    # with every name the config writes kept: no attention kernel, no wo,
    # no w2, and on the split path without QK-norm no wqkv
    assert counts["named"]["pallas_call"] == counts["plain"]["pallas_call"]
    assert (counts["named"]["dot_general"]
            == counts["plain"]["dot_general"] + recomputed)
    for other in ("bare", "named"):
        for got, want in zip(jax.tree.leaves(grads[other]),
                             jax.tree.leaves(grads["plain"])):
            np.testing.assert_allclose(
                got, want, rtol=1e-4,
                atol=1e-5 * float(jnp.max(jnp.abs(want))), err_msg=other)


@pytest.mark.parametrize("config", [
    pytest.param(_post_ln_dense, id="post-ln-dense"),
    pytest.param(_sandwich_rope, id="sandwich-rope-swiglu"),
])
def test_names_are_the_identity_outside_a_checkpoint(config):
    """`remat=False` callers see the same program with or without names."""
    cfg = config()
    params = tfm.init_trunk_params(jax.random.PRNGKey(0), cfg)
    h = jnp.ones((2, 128, cfg.d_model), cfg.dtype)
    text = jax.jit(lambda p, h: tfm.encode(p, h, cfg)[0]).lower(
        params, h).as_text()
    assert not [name for name in EVERY if name in text]
