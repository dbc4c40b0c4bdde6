"""smallthinker-21b-a3b on the flagship trunk (ISSUE 63), on the CPU at a
small size with the real structure (one period [global NoPE, window, window,
window]; a window SMALLER than T; 8 ReGLU experts, top 2, no shared one; the
router on the layer's INPUT): the system against the float32 reference
(benchmark/configs/smallthinker-21b-a3b/reference.py), the router's input,
ReGLU whole and under the share's row loop, the two rotary forms in one
stack, the published head grouping, the four shares of an expert layer, the
name map, the scopes, and the refusals by name."""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from hetu_tpu.models import generate, hf_smallthinker as hs, transformer as tfm
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, hidden_after_runs, jitted, load_reference,
                           loss_and_grads, refuses, rel, round_trip,
                           seeded_params, seeded_tokens)

reference = load_reference("smallthinker-21b-a3b")

ASSUMED = {"router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}
# the published keys at a small size, every expert held, two periods' worth
# of layout of which the first is read: T = 32 is past the window of 8
HF = dict(
    model_name="smallthinker_toy", vocab_size=256, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
    moe_ffn_hidden_size=48, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rope_layout=[0, 1, 1, 1, 0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 1], sliding_window_size=8,
    rope_scaling=None, rope_theta=100, tie_word_embeddings=False,
    assumed=ASSUMED)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "moe_num_primary_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}

# seeded weights with the norms' scales off one
_params = functools.partial(seeded_params, bias=None,
                            noisy=("ln1_scale", "ln2_scale"))


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hs.config_from_hf(SHARE)
    assert tfm.layer_runs(cfg) == (("attention", 1), ("window", 3))
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    assert cfg.window == tfm.WindowConfig(window=8, n_heads=4,
                                          rope_theta=100.0)
    assert not cfg.rope and not cfg.use_pos_emb and cfg.mlp == "reglu"
    assert cfg.router == tfm.Router(
        score="softmax", normalize=True, normalize_eps=0.0, aux_losses=True,
        input="block", width=8, first_held=2)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_ff_expert) == (
        2, 2, 48)
    assert hs.config_from_hf(HF).router.width == 0
    # the published file itself: layers 0-3, 16 of 64 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/smallthinker-21b-a3b/config.json")) as f:
        cell = hs.config_from_hf(json.load(f))
    assert tfm.layer_runs(cell) == (("attention", 1), ("window", 3))
    assert cell.window == tfm.WindowConfig(4096, 28, 1.5e6)
    assert (cell.n_heads, cell.kv_heads, cell.head_dim, cell.d_model,
            cell.rope) == (28, 4, 128, 2560, False)
    assert (cell.n_experts, cell.router.width, cell.router.first_held,
            cell.n_experts_per_tok, cell.d_ff_expert, cell.d_ff_shared) == (
        16, 64, 0, 6, 768, 0)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    full, window = shapes["blocks"]
    assert full["wqkv"].shape == (1, 2560, (28 + 8) * 128)
    assert window["wo"].shape == (3, 28 * 128, 2560)
    assert window["w1"].shape == window["w3"].shape == (3, 16, 2560, 768)
    assert window["w2"].shape == (3, 16, 768, 2560)
    assert window["router"].shape == (3, 2560, 64)
    assert shapes["head"].shape == (2560, 38016) and "pos" not in shapes
    # the ISSUE's count, 656.7M parameters, plus the trunk's unused bias
    # leaves (0.2M): 9.79 GiB of state at 16 bytes
    n = tfm.count_params(shapes)
    assert round(n / 1e6, 1) == 656.9 and round(n * 16 / 2**30, 2) == 9.79


@pytest.mark.parametrize("change,named", [
    ({"moe_num_secondary_experts": 4}, "secondary experts"),
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, "rope_scaling="),
    ({"num_hidden_layers": 3}, "not whole periods"),
    ({"norm_topk_prob": False}, "softmax over the picks' logits"),
    ({"moe_primary_router_apply_softmax": False}, "softmax over the picks"),
    ({"rope_layout": [0, 1, 0, 1, 0, 1, 0, 1]}, "a window layer rotates"),
    ({"rope_layout": [0, 1, 1, 1, 1, 1, 1, 1],
      "num_hidden_layers": 8}, "the global layers rotate, or none does"),
    ({"rope_layout": [0, 1, 1, 1]}, "one entry each")])
def test_loader_refuses_by_name(change, named):
    refuses(lambda: hs.config_from_hf({**HF, **change}), named,
            NotImplementedError)


def test_state_dict_round_trip_under_the_models_names():
    cfg = hs.config_from_hf(SHARE)
    params = _params(cfg)
    sd = round_trip(hs, params, cfg)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (4 * 16, 64)
    assert sd["model.layers.2.self_attn.k_proj.weight"].shape == (2 * 16, 64)
    assert sd["model.layers.3.self_attn.o_proj.weight"].shape == (64, 4 * 16)
    assert sd["model.layers.1.block_sparse_moe.primary_router.weight"
              ].shape == (8, 64)
    assert sd["model.layers.1.block_sparse_moe.experts.2.gate.weight"
              ].shape == (48, 64)
    assert sd["model.layers.1.block_sparse_moe.experts.3.down.weight"
              ].shape == (64, 48)
    assert "model.layers.1.block_sparse_moe.experts.0.up.weight" not in sd
    bare = {k.removeprefix("model."): np.asarray(v) for k, v in sd.items()}
    again = hs.params_from_hf(bare, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the system against the reference ---------------------------------------------

@functools.lru_cache(maxsize=None)
def _both_sides(which):
    """-> (params, tokens, targets, state dict, the reference's loss and
    terms) of CONFIGS[which] at seed 1."""
    hf = CONFIGS[which]
    cfg = hs.config_from_hf(hf)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 1)
    sd = hs.state_dict_from_params(params, cfg)
    return (params, tokens, targets, sd,
            *reference.loss_terms(sd, tokens, targets, hf))


@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_matches_reference_hidden_loss_picks_and_gradients(which):
    hf = CONFIGS[which]
    cfg = hs.config_from_hf(hf)
    params, tokens, targets, sd, want_loss, want = _both_sides(which)
    # the stream after each RUN: layer 0 (global NoPE), layers 1-3 (window)
    got = jitted(hidden_after_runs, cfg)(params, tokens)
    for h, layer in zip(got, (0, 3)):
        assert rel(h, want["hidden"][layer]) < 2e-5
    stats = jitted(functools.partial(tfm.moe_routing_stats, terms=True),
                   cfg)(params, tokens)
    np.testing.assert_array_equal(stats["experts"], want["experts"])
    np.testing.assert_array_equal(stats["picks"], want["counts"])
    assert rel(stats["weights"], want["weights"]) < 1e-5
    np.testing.assert_allclose(np.sum(stats["weights"], -1), 1.0, atol=1e-6)
    loss, grads = jitted(loss_and_grads, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert float(want["balance"]) > 0 and float(want["z"]) > 0
    got_g = hs.state_dict_from_params(grads, cfg)
    value, hidden, want_g = reference.grads_of(sorted(sd))(
        sd, tokens, targets, hf)
    assert abs(float(value) - float(want_loss)) < 1e-6
    assert rel(hidden[3], want["hidden"][3]) < 1e-6
    assert set(got_g) == set(want_g) == set(sd)
    for name in sorted(sd):                         # EVERY leaf
        assert rel(got_g[name], want_g[name]) < 5e-4, name
        assert float(jnp.max(jnp.abs(want_g[name]))) > 0, name


def test_one_adamw_step_is_the_references():
    hf, lr = SHARE, 1e-3
    cfg = hs.config_from_hf(hf)
    params, tokens, targets, sd, _, _ = _both_sides("share")
    adamw = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}
    _, _, want_g = reference.grads_of(sorted(sd))(sd, tokens, targets, hf)
    copy = jax.tree.map(jnp.array, params)
    _, after, opt = tfm.make_train_step(cfg, lr=lr)(
        copy, tfm.init_opt_state(copy), tokens, targets)
    after = hs.state_dict_from_params(after, cfg)
    assert float(opt["t"]) == 1.0
    for name in sorted(sd):
        p = np.asarray(sd[name], np.float64)
        want = reference.adamw_after_step(p, 0 * p, 0 * p, want_g[name], 1,
                                          lr, adamw)
        assert rel(np.asarray(after[name]) - p, want - p) < 2e-3, name


# -- the router's input -----------------------------------------------------------

def test_the_router_reads_the_layers_input_and_not_the_mlp_halfs():
    cfg = hs.config_from_hf(HF)
    params, tokens, targets, sd, want_loss, want = _both_sides("whole")
    # the rows the program says its first router read ARE the embeddings
    terms = tfm.router_terms(params, tokens, cfg)
    np.testing.assert_array_equal(
        terms["x"], tfm.embed_tokens(params, tokens, cfg).reshape(-1, 64))
    np.testing.assert_array_equal(terms["experts"], want["experts"][0])
    # and the same weights routed on `mlp_in` are another model: its picks
    # and its loss leave the reference's
    late = dataclasses.replace(cfg, router=dataclasses.replace(
        cfg.router, input="mlp"))
    stats = tfm.moe_routing_stats(params, tokens, late)
    assert np.mean(np.asarray(stats["experts"]) != np.asarray(
        want["experts"])) > 0.1
    assert abs(float(tfm.loss_fn(params, tokens, targets, late))
               - float(want_loss)) > 1e-3


def test_the_routing_stands_before_the_mixer_in_program_order():
    """`_block` issues the whole routing (`_plan_routing`) under
    `hetu_moe_route_early` / `hetu_moe_route` BEFORE the attention's first
    equation, and nothing of the router is left behind it."""
    cfg = hs.config_from_hf(SHARE)
    params = _params(cfg)
    layer = jax.tree.map(lambda x: x[0],
                         tfm.run_blocks(cfg, params["blocks"])[1])
    h = jnp.zeros((1, 32, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda h, p: tfm._block(h, p, cfg, None, kind="window"))(h, layer)
    stacks = [str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns]
    early = [i for i, s in enumerate(stacks)
             if tracing.SCOPE_MOE_ROUTE_EARLY in s]
    mixer = [i for i, s in enumerate(stacks) if tracing.SCOPE_BLK_QKV in s]
    assert early and mixer and max(early) < min(mixer)
    route = [s for s in stacks if tracing.SCOPE_MOE_ROUTE in s.split("/")]
    assert route and all(tracing.SCOPE_MOE_ROUTE_EARLY in s for s in route)
    # a model that routes on the MLP half's input keeps its one scope
    late = dataclasses.replace(cfg, router=dataclasses.replace(
        cfg.router, input="mlp"))
    text = str(jax.make_jaxpr(lambda h, p: tfm._block(
        h, p, late, None, kind="window"))(h, layer))
    assert tracing.SCOPE_MOE_ROUTE_EARLY not in text


# -- ReGLU ----------------------------------------------------------------------

def test_reglu_is_relu_of_the_gate_times_up_whole_and_in_the_row_loop():
    gate = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    up = jax.random.normal(jax.random.PRNGKey(1), (64, 48))
    want = np.maximum(np.asarray(gate), 0.0) * np.asarray(up)
    np.testing.assert_allclose(tfm._reglu(gate, up), want, rtol=1e-6)
    # the share's loop: the first 24 rows in chunks of 8, the VJP by jax.vjp
    rows = jnp.int32(24)
    out = tfm._act_rows(tfm._reglu, (gate, up), rows, 8)
    np.testing.assert_allclose(out[:24], want[:24], rtol=1e-6)
    g = jax.random.normal(jax.random.PRNGKey(2), (64, 48))
    d_gate, d_up = jax.grad(lambda a, b: jnp.sum(
        tfm._act_rows(tfm._reglu, (a, b), rows, 8)[:24] * g[:24]),
        (0, 1))(gate, up)
    np.testing.assert_allclose(
        d_gate[:24], ((np.asarray(gate) > 0) * np.asarray(up) * g)[:24],
        rtol=1e-6)
    np.testing.assert_allclose(d_up[:24], (np.maximum(gate, 0.0) * g)[:24],
                               rtol=1e-6)
    # the dense MLP of the form, the three leaves of SwiGLU's
    cfg = tfm.TransformerConfig(d_model=16, d_ff=24, mlp="reglu",
                                dtype=jnp.float32)
    p = {"w1": gate[:16, :24], "w3": up[:16, :24], "w2": gate[:24, :16]}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16))
    np.testing.assert_allclose(
        tfm._dense_mlp(x, p, cfg, None),
        (np.maximum(x @ p["w1"], 0.0) * (x @ p["w3"])) @ p["w2"], rtol=2e-5,
        atol=1e-6)
    swiglu = dataclasses.replace(cfg, mlp="swiglu")
    assert jax.tree.structure(jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))) == \
        jax.tree.structure(jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), swiglu)))
    assert tfm.param_specs(cfg) == tfm.param_specs(swiglu)
    refuses(lambda: tfm.TransformerConfig(mlp="geglu"), "mlp='geglu'",
            ValueError)


# -- two rotary forms in one stack ----------------------------------------------

def test_nope_attention_beside_a_rotary_window_in_one_stack():
    cfg = hs.config_from_hf(HF)
    params, tokens, *_ = _both_sides("whole")
    full = tfm.attention_terms(params, tokens, cfg, "attention")
    window = tfm.attention_terms(params, tokens, cfg, "window")
    # the global layer: q and k reach the kernels as the projection wrote them
    np.testing.assert_array_equal(full["q"], full["q_raw"])
    kv = full["k"].reshape(2, 32, 4, 16)[:, :, ::2].reshape(2, 32, 32)
    np.testing.assert_array_equal(kv, full["k_raw"])
    # the window layer: rotated, position 0 alone left as it was
    np.testing.assert_array_equal(window["q"][:, 0], window["q_raw"][:, 0])
    assert rel(window["q"][:, 1:], window["q_raw"][:, 1:]) > 0.1
    # the view a window layer reads rotates whatever `cfg.rope` says
    assert not cfg.rope and tfm._mixer_view(cfg, "window").rope
    assert tfm._mixer_view(cfg, "attention") is cfg
    # no position signal at all: a global layer's last row does not see the
    # order of the rows before it; a window layer's does
    layer = {k: jax.tree.map(lambda x: x[0], b) for k, b in zip(
        ("attention", "window"), tfm.run_blocks(cfg, params["blocks"]))}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 64))
    swapped = x.at[:, [1, 2]].set(x[:, [2, 1]])
    for kind, moved in (("attention", False), ("window", True)):
        a, b = (tfm._KINDS[kind].mixer(v, layer[kind], cfg, None, None)[:, -1]
                for v in (x, swapped))
        assert (rel(a, b) > 1e-3) == moved, kind


def test_the_rotary_scopes_open_in_the_window_layers_alone():
    cfg = hs.config_from_hf(SHARE)
    params, tokens, targets, *_ = _both_sides("share")
    runs = dict(zip(("attention", "window"),
                    tfm.run_blocks(cfg, params["blocks"])))
    h = jnp.zeros((1, 32, 64), jnp.float32)

    def scopes_of(kind):
        layer = jax.tree.map(lambda x: x[0], runs[kind])
        jaxpr = jax.make_jaxpr(lambda h, p: tfm._block(
            h, p, cfg, None, kind=kind))(h, layer)
        return {seg for e in jaxpr.jaxpr.eqns
                for seg in str(e.source_info.name_stack).split("/")}

    full, window = scopes_of("attention"), scopes_of("window")
    assert tracing.SCOPE_BLK_ATTN in full
    assert tracing.SCOPE_SWA_ATTN not in full
    assert tracing.SCOPE_SWA_ATTN in window
    assert tracing.SCOPE_BLK_ATTN not in window
    for scopes in (full, window):
        assert {tracing.SCOPE_MOE_ROUTE_EARLY, tracing.SCOPE_MOE_ROUTE,
                tracing.SCOPE_MOE_ACT, tracing.SCOPE_MOE_EXPERTS} <= scopes


def test_rope_scope_wraps_the_kernel_in_a_window_layer_only(monkeypatch):
    """On a TPU the rotation is the `rope_halves` kernel under
    `hetu_attn_rope`: a window layer's q and k take it, a NoPE layer
    none."""
    from hetu_tpu.kernels import rope as rope_kernel
    seen = []
    monkeypatch.setattr(rope_kernel, "takes", lambda *a, **k: True)
    monkeypatch.setattr(
        rope_kernel, "rope_halves",
        lambda x, *rest, at=None: (seen.append(at), x if at is None else
                                   x[..., at[0]:at[0] + at[1]])[1])
    cfg = hs.config_from_hf(HF)
    params, tokens, *_ = _both_sides("whole")
    tfm.attention_terms(params, tokens, cfg, "attention")
    assert seen == []
    tfm.attention_terms(params, tokens, cfg, "window")
    assert len(seen) == 4          # q and k, the terms' call and the mixer's


# -- the published grouping -------------------------------------------------------

def test_28_heads_on_4_kv_heads_of_128_columns_group_by_seven():
    hf = {**HF, "num_attention_heads": 28, "num_key_value_heads": 4,
          "head_dim": 128, "num_hidden_layers": 4,
          "moe_num_primary_experts": 4, "vocab_size": 64}
    cfg = hs.config_from_hf(hf)
    params = _params(cfg, seed=3)
    tokens, targets = seeded_tokens(hf, 3, B=1, T=16)
    sd = hs.state_dict_from_params(params, cfg)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (3584, 64)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (512, 64)
    want_loss, want = reference.loss_terms(sd, tokens, targets, hf)
    got = hidden_after_runs(params, tokens, cfg)
    assert rel(got[0], want["hidden"][0]) < 2e-5
    assert rel(got[1], want["hidden"][3]) < 2e-5
    # head j reads k/v head j // 7: k as the kernels take it repeats a k/v
    # head seven times side by side
    terms = tfm.attention_terms(params, tokens, cfg, "attention")
    k = np.asarray(terms["k"]).reshape(16, 28, 128)
    raw = np.asarray(terms["k_raw"]).reshape(16, 4, 128)
    for j in range(28):
        np.testing.assert_array_equal(k[:, j], raw[:, j // 7])


# -- the share ------------------------------------------------------------------

def test_the_four_members_parts_add_up_to_the_uncut_layer():
    """THE SHARE TEST: a window layer of the uncut model (8 experts) against
    the four members of a group that hold 2 each: the router scores all 8
    and weighs both picks in every member; what the members' experts ADD to
    h sums to what the uncut reference's layer adds; and the SYSTEM's share
    of each member is the reference's part."""
    whole = hs.config_from_hf(HF)
    params, tokens, *_ = _both_sides("whole")
    sd = hs.state_dict_from_params(params, whole)
    x = jitted(hidden_after_runs, whole)(params, tokens)[0]   # enters layer 1
    scope = "model.layers.1."
    w = {n[len(scope):]: jnp.asarray(v) for n, v in sd.items()
         if n.startswith(scope)}
    kind = reference.kinds_of(HF)[1]
    y, added, h = reference.layer_part(x, w, HF, kind, 0, 8)
    layer = jax.tree.map(lambda a: a[0],
                         tfm.run_blocks(whole, params["blocks"])[1])
    got, _ = tfm._block(x, layer, whole, None, kind="window")
    assert rel(got, y) < 2e-5
    parts = []
    for first in (0, 2, 4, 6):
        member = hs.config_from_hf({**HF, "moe_num_primary_experts": 2,
                                    "num_routed_experts": 8,
                                    "first_expert_held": first})
        held = {k: (v[first:first + 2] if k in ("w1", "w2", "w3", "b1", "b2")
                    else v) for k, v in layer.items()}
        y_m, added_m, h_m = reference.layer_part(x, w, HF, kind, first, 2)
        np.testing.assert_array_equal(h_m, h)       # computed by all alike
        got_m, _ = tfm._block(x, held, member, None, kind="window")
        assert rel(got_m - h, added_m) < 2e-4, first
        parts.append(added_m)
    assert rel(sum(parts), added) < 1e-5
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)


# -- refusals by name -------------------------------------------------------------

def test_decode_pipeline_and_an_ep_mesh_refuse_the_new_fields_by_name():
    cfg = hs.config_from_hf(SHARE)
    refuses(lambda: generate._check_decode_args(cfg, 32, 0), "router=")
    refuses(lambda: generate._check_decode_args(cfg, 32, 0), "mlp='reglu'")
    refuses(lambda: generate._check_decode_args(cfg, 32, 0), "window=")
    refuses(lambda: pipeline._make_stage_fn(cfg, 1), "layer_runs=",
            NotImplementedError)
    one_kind = dataclasses.replace(cfg, layer_types=("attention",) * 4,
                                   window=None)
    refuses(lambda: pipeline._make_stage_fn(one_kind, 1),
            "router.input='block'", NotImplementedError)
    whole = hs.config_from_hf(HF)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 1, 2, 1),
                ("dp", "sp", "tp", "ep", "pp"))
    params = _params(whole)
    layer = jax.tree.map(lambda a: a[0],
                         tfm.run_blocks(whole, params["blocks"])[1])
    refuses(lambda: tfm._block(jnp.zeros((2, 32, 64)), layer, whole, mesh,
                               kind="window"),
            "router.input='block'", tfm.MoEConfigError)
    refuses(lambda: tfm.TransformerConfig(
        n_experts=4, router=tfm.Router(input="attention")),
        "input is 'mlp' or 'block'", tfm.MoEConfigError)
    refuses(lambda: tfm.TransformerConfig(
        n_experts=4, post_ln=True, router=tfm.Router(input="block")),
        "a pre-LN layer of two halves", tfm.MoEConfigError)
    refuses(lambda: tfm.TransformerConfig(rope_dim=8), "rope_dim=8",
            ValueError)
