"""LFM2-8B-A1B on the flagship trunk (ISSUE 37), on the CPU at a small size
with the real structure (1 dense layer + a period of 4: conv + dense,
attention + experts, 3 x conv + experts; 8 experts, top 2, two query heads a
key-value head): the system against the float32 reference
(benchmark/configs/lfm2-8b-a1b/reference.py), the shares of an expert layer
adding up to the whole, the selection bias's rule, a skewed router, and the
refusals by name. A share's row loops (ISSUE 38) are in
test_lfm2_share_rows.py, the reference's mixer, attention and dense MLP
against `transformers`' `lfm2` modules in
test_references_against_transformers.py, and the other flagship cells' trees
and lowered programs in test_cell_digests.py."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import generate, hf_lfm2, transformer as tfm
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, grads_of_loss, jitted, load_reference,
                           loss_and_grads, refuses, rel, round_trip,
                           seeded_params, seeded_tokens)

reference = load_reference("lfm2-8b-a1b")

# layers 1-5 of the published order, every expert held
HF = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=64, intermediate_size=128,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv"],
    max_position_embeddings=64, model_type="lfm2_moe",
    moe_intermediate_size=48, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=4, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, num_hidden_layers=5, num_key_value_heads=2,
    rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
    vocab_size=256, first_layer=1)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "num_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hf_lfm2.config_from_hf(SHARE, router_bias_rate=1e-3)
    assert tfm.layer_runs(cfg) == (("conv" + tfm.DENSE, 1), ("attention", 1),
                                   ("conv", 3))
    assert tfm.layer_kinds(cfg)[0] == "conv+dense"
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.conv_width) == (128, 48, 3)
    assert (cfg.n_heads, cfg.kv_heads, cfg.qk_norm) == (4, 2, "head")
    assert cfg.rope and cfg.rope_theta == 1e6 and cfg.tied_head
    assert cfg.router == tfm.Router(
        score="sigmoid", bias=True, normalize=True, scale=1.0,
        aux_losses=False, bias_rate=1e-3, width=8, first_held=2)
    assert (cfg.n_experts, cfg.n_experts_per_tok) == (2, 2)
    whole = hf_lfm2.config_from_hf(HF)
    assert whole.router.width == 0 and whole.n_experts == 8
    # the published file itself: layers 1-5, 8 of 32 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/lfm2-8b-a1b/config.json")) as f:
        cell = hf_lfm2.config_from_hf(json.load(f))
    assert tfm.layer_runs(cell) == (("conv+dense", 1), ("attention", 1),
                                    ("conv", 3))
    assert (cell.router.width, cell.router.first_held, cell.n_experts) == (
        32, 0, 8)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    moe = shapes["blocks"][1]
    assert moe["router"].shape == (1, 2048, 32)
    assert moe[tfm.ROUTER_BIAS].shape == (1, 32)
    assert moe["w1"].shape == (1, 8, 2048, 1792)
    assert moe["q_norm"].shape == (1, 64)
    assert shapes["blocks"][0]["w1"].shape == (1, 2048, 7168)
    assert shapes["blocks"][2]["w_in"].shape == (3, 2048, 6144)
    assert shapes["blocks"][2]["conv_w"].shape == (3, 3, 2048)
    assert shapes["embed"].shape == (16384, 2048) and "head" not in shapes


def test_state_dict_round_trip():
    cfg = hf_lfm2.config_from_hf(SHARE)
    sd = round_trip(hf_lfm2, seeded_params(cfg), cfg,
                    back=hf_lfm2.params_from_hf)
    # the held experts under the MODEL's indices
    assert "model.layers.1.feed_forward.experts.2.w1.weight" in sd
    assert "model.layers.1.feed_forward.experts.0.w1.weight" not in sd
    assert sd["model.layers.1.feed_forward.expert_bias"].shape == (8,)
    assert sd["model.layers.0.feed_forward.w1.weight"].shape == (128, 64)
    assert sd["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)


# -- the system against the reference -------------------------------------------

@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_equals_reference(which):
    """Hidden states after every run, loss, picks, counts and every
    gradient leaf, float32 on both sides."""
    hf = CONFIGS[which]
    cfg = hf_lfm2.config_from_hf(hf)
    params = seeded_params(cfg)
    tokens, targets = seeded_tokens(hf, 1)
    sd = hf_lfm2.state_dict_from_params(params, cfg)
    want_loss, want = reference.loss_terms(sd, tokens, targets, hf)

    loss, grads = jitted(loss_and_grads, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    hidden, aux = jitted(tfm.forward_hidden, cfg)(params, tokens)
    assert rel(hidden, want["hidden"][-1]) < 2e-5
    np.testing.assert_array_equal(np.asarray(aux), 0.0)   # no auxiliary loss
    stats = jitted(tfm.moe_routing_stats, cfg)(params, tokens)
    np.testing.assert_array_equal(np.asarray(stats["experts"]),
                                  np.asarray(want["experts"]))
    np.testing.assert_array_equal(np.asarray(stats["picks"]),
                                  np.asarray(want["counts"]))
    first, n = cfg.router.first_held, cfg.n_experts
    np.testing.assert_array_equal(
        np.asarray(stats["held"]),
        np.asarray(want["counts"])[:, first:first + n].sum(-1))
    assert int(np.asarray(stats["dropped"]).sum()) == 0
    if which == "whole":
        assert np.asarray(stats["held"]).tolist() == [2 * 32 * 2] * 4
    else:
        assert 0 < int(np.asarray(stats["held"]).sum()) < 4 * 2 * 32 * 2

    got = hf_lfm2.state_dict_from_params(grads, cfg)
    # the tied head IS the embedding: one leaf, under the embedding's name
    names = sorted(n for n in sd
                   if "expert_bias" not in n and n != "lm_head.weight")
    _, want_grads = reference.grads_of(names)(sd, tokens, targets, hf)
    for name in names:
        scale = max(float(np.sqrt(np.mean(np.square(want_grads[name])))),
                    1e-7)
        err = float(np.max(np.abs(np.asarray(got[name])
                                  - np.asarray(want_grads[name]))))
        assert err < 2e-3 * scale + 1e-8, (name, err, scale)
    # the bias's "gradient" is the picks its layer's experts took
    for i, layer in enumerate(range(1, 5)):
        np.testing.assert_array_equal(
            np.asarray(got[f"model.layers.{layer}.feed_forward.expert_bias"]),
            np.asarray(want["counts"][i]))


def test_reference_grads_of_is_jax_grad_of_its_plain_loss():
    hf = SHARE
    cfg = hf_lfm2.config_from_hf(hf)
    tokens, targets = seeded_tokens(hf, 2, B=1, T=16)
    sd = hf_lfm2.state_dict_from_params(seeded_params(cfg), cfg)
    names = ["model.layers.1.feed_forward.gate.weight",
             "model.layers.0.conv.conv.weight",
             "model.layers.1.self_attn.q_layernorm.weight",
             "model.embed_tokens.weight"]
    _, lean = reference.grads_of(names)(sd, tokens, targets, hf)
    rest = {n: v for n, v in sd.items() if n not in names}
    plain = jax.grad(lambda part: reference.loss_terms(
        {**rest, **part}, tokens, targets, hf)[0])({n: sd[n] for n in names})
    for n in names:
        np.testing.assert_allclose(np.asarray(lean[n]), np.asarray(plain[n]),
                                   rtol=1e-4, atol=1e-7, err_msg=n)


def test_bias_enters_the_selection_only():
    """A bias that changes which experts a token takes leaves the weights
    those of the SCORES: on the tokens whose picks did not change, the layer's
    output is what it was."""
    cfg = hf_lfm2.config_from_hf(HF)
    tokens, _ = seeded_tokens(HF, 3)
    base = seeded_params(cfg, bias=0.0)
    moved = seeded_params(cfg, bias=0.2)
    run = lambda p: (tfm.router_terms(p, tokens, cfg))
    a, b = run(base), run(moved)
    np.testing.assert_array_equal(np.asarray(a["scores"]),
                                  np.asarray(b["scores"]))
    changed = np.any(np.sort(np.asarray(a["experts"]), -1)
                     != np.sort(np.asarray(b["experts"]), -1), -1)
    assert 0 < changed.sum() < changed.size
    # weights = the picks' scores over their sum, whatever the bias
    for t in (a, b):
        s = np.take_along_axis(np.asarray(t["scores"], np.float64),
                               np.asarray(t["experts"]), -1)
        np.testing.assert_allclose(
            np.asarray(t["weights"]), s / (s.sum(-1, keepdims=True) + 1e-6),
            rtol=1e-6)


# -- the shares add up -------------------------------------------------------------

def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer, 8 experts over 4 chips, 2 a chip: every chip routes
    over all 8 and normalises over both picks; the four partial results
    (the system's `_moe_mlp` told its share) sum to the UNCUT reference's
    output for the whole layer. No part is computed alike on every chip (no
    shared expert), so nothing is counted once."""
    whole_cfg = hf_lfm2.config_from_hf(HF)
    p = jax.tree.map(lambda x: x[0], tfm.run_blocks(
        whole_cfg, seeded_params(whole_cfg)["blocks"])[1])
    m = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))
    # the uncut reference on HF names
    w = {"feed_forward.gate.weight": p["router"].T,
         "feed_forward.expert_bias": p[tfm.ROUTER_BIAS]}
    for e in range(8):
        for name in ("w1", "w3", "w2"):
            w[f"feed_forward.experts.{e}.{name}.weight"] = p[name][e].T
    c = {**HF}
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts_math(m.reshape(-1, 64), w, c, 0)
        parts, held = [], 0
        for chip in range(4):
            hf = {**HF, "num_experts": 2, "num_routed_experts": 8,
                  "first_expert_held": 2 * chip}
            cfg = hf_lfm2.config_from_hf(hf)
            mine = {**p, **{n: p[n][2 * chip:2 * chip + 2]
                            for n in ("w1", "w3", "w2", "b1", "b2")}}
            out, aux = tfm._moe_mlp(m, mine, cfg, None)
            parts.append(out.reshape(-1, 64))
            # and the reference given the same share says the same
            np.testing.assert_allclose(
                np.asarray(parts[-1]),
                np.asarray(reference._experts_math(
                    m.reshape(-1, 64), w, {**c, "num_experts": 2},
                    2 * chip)[0]),
                rtol=1e-4, atol=1e-7)
            assert rel(parts[-1], want) > 0.3       # a part, not the whole
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-4, atol=1e-7)
    whole, _ = tfm._moe_mlp(m, p, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, 64)),
                               np.asarray(want), rtol=1e-4, atol=1e-7)


def test_a_skewed_router_computes_every_held_pick():
    """Every token picks the two held experts: all S*k rows lie in the
    groups, none is dropped, and the layer's output is the reference's."""
    cfg = hf_lfm2.config_from_hf(SHARE)
    params = seeded_params(cfg, bias=0.0)
    skew = jnp.zeros((8,)).at[2:4].set(5.0)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + skew if tfm._is_router_bias(path) else x, params)
    tokens, targets = seeded_tokens(SHARE, 4)
    routing = jitted(tfm.moe_routing_stats, cfg)
    hidden_of = jitted(tfm.forward_hidden, cfg)
    stats = routing(params, tokens)
    S_k = 2 * 32 * 2
    assert np.asarray(stats["held"]).tolist() == [S_k] * 4
    assert np.asarray(stats["dropped"]).tolist() == [0] * 4
    assert np.asarray(stats["max_over_mean"]).tolist() == [4.0] * 4
    sd = hf_lfm2.state_dict_from_params(params, cfg)
    want_loss, want = reference.loss_terms(sd, tokens, targets, SHARE)
    hidden, _ = hidden_of(params, tokens)
    assert rel(hidden, want["hidden"][-1]) < 2e-5
    assert abs(float(jitted(tfm.loss_fn, cfg)(params, tokens, targets))
               - float(want_loss)) < 2e-5
    # and the other way: no pick lands here, the expert block adds nothing
    away = jax.tree_util.tree_map_with_path(
        lambda path, x: x - 2 * skew if tfm._is_router_bias(path) else x,
        params)
    stats = routing(away, tokens)
    assert np.asarray(stats["held"]).tolist() == [0] * 4
    sd = hf_lfm2.state_dict_from_params(away, cfg)
    _, want = reference.loss_terms(sd, tokens, targets, SHARE)
    hidden, _ = hidden_of(away, tokens)
    assert rel(hidden, want["hidden"][-1]) < 2e-5
    g = jitted(grads_of_loss, cfg)(away, tokens, targets)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
    assert float(jnp.abs(g["blocks"][2]["w1"]).max()) == 0.0


# -- the bias's rule ------------------------------------------------------------

def test_the_bias_follows_its_rule_and_no_other_leaf_moves_otherwise():
    cfg0 = hf_lfm2.config_from_hf(SHARE)                     # rate 0
    cfg = hf_lfm2.config_from_hf(SHARE, router_bias_rate=1e-2)
    tokens, targets = seeded_tokens(SHARE, 5)
    fresh = lambda: (lambda p: (p, tfm.init_opt_state(p)))(seeded_params(cfg))
    before = seeded_params(cfg)
    counts = np.asarray(jitted(tfm.moe_routing_stats, cfg)(
        before, tokens)["picks"], np.float64)
    loss0, still, opt0 = tfm.make_train_step(cfg0, lr=1e-3)(
        *fresh(), tokens, targets)
    loss, moved, opt = tfm.make_train_step(cfg, lr=1e-3)(
        *fresh(), tokens, targets)
    assert float(loss) == float(loss0)
    bias = lambda p: np.concatenate(
        [np.asarray(b[tfm.ROUTER_BIAS]) for b in p["blocks"][1:]])
    # the rule, from the picks the step's own forward pass counted
    want = bias(before) + 1e-2 * np.sign(
        counts.mean(-1, keepdims=True) - counts)
    np.testing.assert_allclose(bias(moved), want, rtol=0, atol=1e-7)
    np.testing.assert_array_equal(bias(still), bias(before))
    assert np.any(bias(moved) != bias(before))
    # the counter: the step's picks in the bias's first slot, nothing in
    # the second
    np.testing.assert_array_equal(bias(opt["m"]), counts)
    np.testing.assert_array_equal(bias(opt["v"]), 0.0)
    # every other leaf and slot: the update it had without the rule
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(
            (moved, opt["v"], opt["t"])), jax.tree.leaves(
                (still, opt0["v"], opt0["t"]))):
        if not tfm._is_router_bias(path):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=jax.tree_util.keystr(path))
    # and AdamW itself is what it is on a tree without such a leaf: one leaf
    # by hand
    g = jitted(grads_of_loss, cfg)(before, tokens, targets)["lnf_scale"]
    m, v = 0.1 * g, 0.001 * g * g
    want = before["lnf_scale"] - 1e-3 * (
        (m / 0.1) / (jnp.sqrt(v / 0.001) + 1e-8) + 0.01 * before["lnf_scale"])
    np.testing.assert_allclose(np.asarray(moved["lnf_scale"]),
                               np.asarray(want), rtol=1e-5)


def test_the_bias_evens_a_skewed_load_over_steps():
    cfg = hf_lfm2.config_from_hf(HF, router_bias_rate=5e-3)
    params = seeded_params(cfg, bias=0.0)
    # a router whose scores spread (logits of std 1.3), so loads start uneven
    params["blocks"][1]["router"] = 20.0 * params["blocks"][1]["router"]
    opt = tfm.init_opt_state(params)
    tokens, targets = seeded_tokens(HF, 6, B=4)
    step = tfm.make_train_step(cfg, lr=0.0)    # the weights stand still
    loads = []
    for _ in range(60):
        _, params, opt = step(params, opt, tokens, targets)
        picks = np.asarray(opt["m"]["blocks"][1][tfm.ROUTER_BIAS][0])
        assert picks.sum() == 4 * 32 * 2
        loads.append(picks.max() / picks.mean())
    # 1.41 at first, 1.06-1.09 once the bias has settled (a rate of 2e-2
    # swings between 1.16 and 1.25 instead: the step is the rule's floor)
    assert loads[0] > 1.3
    assert np.mean(loads[-10:]) < 1.15 < np.mean(loads[:3])


# -- the defaults -------------------------------------------------------------

def test_default_router_and_kinds_leave_configs_what_they_were():
    cfg = tfm.TransformerConfig(n_layers=3)
    assert tfm.layer_kinds(cfg) == ("attention",) * 3
    assert cfg.router == tfm.Router() and cfg.qk_norm is False
    olmoe = tfm.TransformerConfig(n_experts=4, n_experts_per_tok=2,
                                  n_layers=2)
    assert tfm.layer_runs(olmoe) == (("attention", 2),)
    assert tfm.experts_of(olmoe, "attention") == 4
    assert tfm.ROUTER_BIAS not in jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), olmoe))["blocks"]


# -- scopes ----------------------------------------------------------------------

def test_scopes_of_the_conv_mixer_and_the_share_in_the_compiled_step():
    cfg = hf_lfm2.config_from_hf(SHARE, router_bias_rate=1e-3)
    params = seeded_params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope, outer in ((tracing.SCOPE_SCONV_PROJ, tracing.SCOPE_SSM_PROJ),
                         (tracing.SCOPE_SCONV_CONV, tracing.SCOPE_SSM_CONV)):
        under = [n for n in names if f"/{outer}/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
        # never outside the mamba mixer's scope of the same part
        assert not [n for n in names if f"/{scope}/" in n
                    and f"/{outer}/{scope}/" not in n]
    for scope in tracing.MOE_SCOPES + (tracing.SCOPE_BLK_MLP_UP,
                                       tracing.SCOPE_BLK_ATTN,
                                       tracing.SCOPE_HEAD):
        assert any(f"/{scope}/" in n for n in names), scope
    assert tracing.SCONV_SCOPES == ("hetu_sconv_proj", "hetu_sconv_conv")


# -- refusals by name -----------------------------------------------------------

def test_decode_refuses_the_conv_kind_the_router_and_the_share_by_name():
    cfg = hf_lfm2.config_from_hf(SHARE)
    decode = lambda c: lambda: generate._check_decode_args(c, 16, 0)
    refuses(decode(cfg), "n_experts=2")
    dense = dataclasses.replace(cfg, n_experts=0, n_experts_per_tok=1,
                                n_dense_layers=0, d_ff_expert=0,
                                router=tfm.Router(), qk_norm=False)
    refuses(decode(dense), "layer_types=('conv', 'attention'")
    attention = dataclasses.replace(dense, layer_types=())
    refuses(decode(dataclasses.replace(
        attention, router=tfm.Router(score="sigmoid"))),
        "router=Router(score='sigmoid'")
    refuses(decode(dataclasses.replace(
        attention, router=tfm.Router(width=8))), "width=8")
    decode(attention)()


def test_pipeline_refuses_runs_of_unequal_kinds_by_name():
    cfg = hf_lfm2.config_from_hf(SHARE)
    with pytest.raises(NotImplementedError, match="conv"):
        pipeline._make_stage_fn(cfg, 1)


def test_a_share_on_an_expert_parallel_mesh_is_refused_by_name():
    from hetu_tpu.parallel import mesh as meshlib
    cfg = hf_lfm2.config_from_hf(SHARE)
    p = jax.tree.map(lambda x: x[0], tfm.run_blocks(
        cfg, seeded_params(cfg)["blocks"])[1])
    mesh = meshlib.make_mesh(ep=2, devices=jax.devices()[:2])
    with pytest.raises(tfm.MoEConfigError, match="share"):
        tfm._moe_mlp(jnp.zeros((2, 8, 64)), p, cfg, mesh)


def test_config_refuses_what_the_trunk_does_not_run():
    with pytest.raises(NotImplementedError, match="conv_bias"):
        hf_lfm2.config_from_hf({**HF, "conv_bias": True})
    with pytest.raises(NotImplementedError, match="layer_types"):
        hf_lfm2.config_from_hf({**HF, "layer_types": ["conv", "swa"] * 4})
    with pytest.raises(tfm.MoEConfigError, match="share"):
        hf_lfm2.config_from_hf({**SHARE, "first_expert_held": 7})
    with pytest.raises(tfm.MoEConfigError, match="bias_rate"):
        tfm.TransformerConfig(n_experts=2, router=tfm.Router(bias_rate=0.1))
    with pytest.raises(ValueError, match="qk_norm"):
        tfm.TransformerConfig(qk_norm="heads")
    with pytest.raises(tfm.MoEConfigError, match="n_dense_layers"):
        tfm.TransformerConfig(n_dense_layers=1)
    with pytest.raises(NotImplementedError, match="attention bias"):
        tfm._short_conv(jnp.zeros((1, 4, 8)), {}, tfm.TransformerConfig(),
                        None, attn_bias=jnp.zeros((1, 1, 1, 4)))
