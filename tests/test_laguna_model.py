"""laguna-xs.2 on the flagship trunk (ISSUE 49), on the CPU at a small size
with the real structure (full attention + dense MLP; two window layers with
their own head count; a full layer; 8 experts, top 2, a shared one; a window
SMALLER than T and T past YaRN's original length, so that both bite): the
system against the float32 reference
(benchmark/configs/laguna-xs.2/reference.py), the window kind against the
attention kind, the rotation against the one it was and against float64, the
eight shares of an expert layer, the name map, the pair counters against
their closed forms, the scopes, and the refusals by name. The kernels' own
window cases are in test_flash_window.py."""
import dataclasses
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from hetu_tpu.kernels import flash_attention as fa
from hetu_tpu.kernels import rope as rope_kernel
from hetu_tpu.models import generate, hf_laguna as hl, transformer as tfm
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, hidden_after_runs, jitted, load_reference,
                           grads_of_loss, loss_and_grads, refuses, rel,
                           rope_kernel_taken, round_trip,  # noqa: F401
                           seeded_params, seeded_tokens)


reference = load_reference("laguna-xs.2")

FULL = {"rope_theta": 10000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 0.01,
        "beta_fast": 1, "attention_factor": 1.2079441541679836,
        "partial_rotary_factor": 0.5}
# the published keys at a small size, every expert held: T = 32 is past the
# original 16 positions and the window of 8
HF = dict(
    model_type="laguna", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, max_position_embeddings=64,
    attention_bias=False, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=48,
    shared_expert_intermediate_size=40, tie_word_embeddings=False,
    gating=True, sliding_window=8,
    rope_parameters={
        "full_attention": FULL,
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "full_attention"],
    moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 4])
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "num_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}
# the cell's config.json at a width a CPU test can take (head_dim stays 128)
SIZES_OF_A_TOY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 40, "num_experts": 2,
    "num_routed_experts": 8, "first_expert_held": 2,
    "num_experts_per_tok": 2, "vocab_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_attention_heads_per_layer": [4, 6, 6, 6, 4]}


# seeded weights, the selection bias moved off zero so that it matters to the
# picks, the norms' scales off one, the gate's weights large enough that the
# gate is no constant half
_params = functools.partial(
    seeded_params, noisy=("ln1_scale", "ln2_scale"), tenfold=("wg",))


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hl.config_from_hf(SHARE, router_bias_rate=1e-3)
    assert tfm.layer_runs(cfg) == (("attention" + tfm.DENSE, 1),
                                   ("window", 2), ("attention", 1))
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    assert cfg.window == tfm.WindowConfig(window=8, n_heads=6,
                                          rope_theta=100.0)
    assert (cfg.rope_theta, cfg.rope_dim) == (1e4, 8) and cfg.attn_gate
    assert cfg.rope_yarn == tfm.YarnConfig(
        factor=8.0, original_max_len=16, beta_fast=1.0, beta_slow=0.01,
        attention_factor=FULL["attention_factor"])
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.d_ff_shared) == (128, 48, 40)
    assert cfg.router == tfm.Router(
        score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
        scale=2.5, aux_losses=False, bias_rate=1e-3, width=8, first_held=2)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.n_dense_layers) == (
        2, 2, 1)
    assert hl.config_from_hf(HF).router.width == 0
    # the published file itself: layers 0-4, 32 of 256 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/laguna-xs.2/config.json")) as f:
        cell = hl.config_from_hf(json.load(f))
    assert tfm.layer_runs(cell) == (("attention+dense", 1), ("window", 3),
                                    ("attention", 1))
    assert cell.window == tfm.WindowConfig(512, 64, 1e4)
    assert (cell.n_heads, cell.kv_heads, cell.head_dim, cell.rope_dim,
            cell.rope_theta) == (48, 8, 128, 64, 5e5)
    assert (cell.rope_yarn.factor, cell.rope_yarn.original_max_len,
            cell.rope_yarn.beta_fast) == (64.0, 4096, 64.0)
    assert (cell.n_experts, cell.router.width, cell.router.first_held,
            cell.n_experts_per_tok, cell.d_ff_shared, cell.router.scale) == (
        32, 256, 0, 8, 512, 2.5)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    dense, window, full = shapes["blocks"]
    assert dense["wqkv"].shape == (1, 2048, (48 + 16) * 128)
    assert window["wqkv"].shape == (3, 2048, (64 + 16) * 128)
    assert window["wo"].shape == (3, 64 * 128, 2048)
    assert (window["wg"].shape, full["wg"].shape) == ((3, 2048, 64),
                                                      (1, 2048, 48))
    assert dense["w1"].shape == (1, 2048, 8192) and "ws1" not in dense
    assert window["w1"].shape == (3, 32, 2048, 512)
    assert window["router"].shape == (3, 2048, 256)
    assert full["ws2"].shape == (1, 512, 2048)
    assert shapes["head"].shape == (2048, 12544)
    # the ISSUE's count: 691.6M parameters, 10.31 GiB of state at 16 bytes
    n = tfm.count_params(shapes)
    assert round(n / 1e6, 1) == 692.0 and round(n * 16 / 2**30, 2) == 10.31


@pytest.mark.parametrize("change,named", [
    ({"mlp_layer_types": ["dense", "sparse", "dense", "sparse"]},
     "not leading"),
    ({"num_attention_heads_per_layer": [4, 6, 8, 4]}, "ONE head count"),
    ({"attention_bias": True}, "projection"),
    ({"moe_apply_router_weight_on_input": True}, "expert's input"),
    ({"gating": "elementwise"}, "per-head gate"),
    ({"layer_types": ["full_attention", "linear_attention",
                      "sliding_attention", "full_attention"]},
     "layer_types="),
    ({"rope_parameters": {**HF["rope_parameters"], "sliding_attention": {
        "rope_type": "default", "rope_theta": 100,
        "partial_rotary_factor": 0.5}}}, "all of a head's columns"),
    ({"rope_parameters": {**HF["rope_parameters"], "full_attention": {
        **FULL, "mscale": 1.0}}}, "no mscale"),
    ({"rope_parameters": {**HF["rope_parameters"], "full_attention": {
        **FULL, "truncate": False}}}, "truncate true"),
    ({"rope_parameters": {**HF["rope_parameters"], "full_attention": {
        **FULL, "rope_type": "llama3"}}}, "YaRN")])
def test_loader_refuses_by_name(change, named):
    refuses(lambda: hl.config_from_hf({**HF, **change}), named,
            NotImplementedError)


def test_state_dict_round_trip_four_and_six_head_layers_in_one_checkpoint():
    cfg = hl.config_from_hf(SHARE)
    params = _params(cfg)
    sd = round_trip(hl, params, cfg)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (4 * 16, 64)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (6 * 16, 64)
    assert sd["model.layers.2.self_attn.k_proj.weight"].shape == (2 * 16, 64)
    assert sd["model.layers.1.self_attn.g_proj.weight"].shape == (6, 64)
    assert sd["model.layers.3.self_attn.g_proj.weight"].shape == (4, 64)
    assert sd["model.layers.3.self_attn.o_proj.weight"].shape == (64, 4 * 16)
    assert sd["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (8,)
    assert sd["model.layers.2.mlp.shared_expert.down_proj.weight"].shape == (
        64, 40)
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in sd
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    # through the scope-less names a checkpoint may carry, as float32 jnp
    bare = {k.removeprefix("model."): np.asarray(v) for k, v in sd.items()}
    again = hl.params_from_hf(bare, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the system against the reference ---------------------------------------------

@functools.lru_cache(maxsize=None)
def _both_sides(which):
    """-> (params, tokens, targets, state dict, the reference's loss and
    terms) of CONFIGS[which] at seed 1: the reference side of the comparison,
    run once for the cases that share it."""
    hf = CONFIGS[which]
    cfg = hl.config_from_hf(hf)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 1)
    sd = hl.state_dict_from_params(params, cfg)
    return (params, tokens, targets, sd,
            *reference.loss_terms(sd, tokens, targets, hf))


@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_matches_reference_hidden_loss_picks_and_gradients(which):
    hf = CONFIGS[which]
    cfg = hl.config_from_hf(hf, router_bias_rate=1e-3)
    params, tokens, targets, sd, want_loss, want = _both_sides(which)
    loss = jitted(tfm.loss_fn, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    # after each run of layers: the dense full layer, the window run, the
    # full expert layer
    for got, last in zip(jitted(hidden_after_runs, cfg)(params, tokens),
                         (0, 2, 3)):
        assert rel(got, want["hidden"][last]) < 2e-6, last
    stats = jitted(tfm.moe_routing_stats, cfg)(params, tokens)
    np.testing.assert_array_equal(
        np.sort(np.asarray(stats["experts"]), -1),
        np.sort(np.asarray(want["experts"]), -1))
    np.testing.assert_array_equal(np.asarray(stats["picks"]),
                                  np.asarray(want["counts"]))
    assert int(stats["dropped"].sum()) == 0
    # the picks handed in are taken as they are
    same_loss, same = reference.loss_terms(
        sd, tokens, targets, hf, picks=list(stats["experts"]))
    assert abs(float(same_loss) - float(want_loss)) < 1e-6
    np.testing.assert_array_equal(np.asarray(same["experts"]),
                                  np.asarray(stats["experts"]))
    grads = hl.state_dict_from_params(
        jitted(grads_of_loss, cfg)(params, tokens, targets), cfg)
    names = [n for n in sd if "e_score" not in n]
    _, want_grads = reference.grads_of(names)(sd, tokens, targets, hf)
    for n in names:
        assert rel(grads[n], want_grads[n]) < 2e-5, n
    assert float(jnp.max(jnp.abs(
        want_grads["model.layers.1.self_attn.g_proj.weight"]))) > 1e-6
    # the lean gradient is jax.grad of the plain forward
    few = ["model.layers.1.self_attn.g_proj.weight",
           "model.layers.2.mlp.gate.weight",
           "model.layers.3.self_attn.k_proj.weight"]
    plain = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, tokens, targets, hf)[0])({n: sd[n] for n in few})
    for n in few:
        assert rel(want_grads[n], plain[n]) < 1e-5, n


@pytest.mark.parametrize("wrong,first", [
    ("window off by one", 1), ("no YaRN", 0), ("no attention factor", 0),
    ("rotary on every column", 0), ("no gate", 0)])
def test_the_comparison_sees_each_mechanism(wrong, first):
    """Each mechanism left out of the SYSTEM moves the residual stream off
    the reference's by far more than the comparison allows, from the first
    run of layers that has it on (`first`: layer 0 is a full layer, the
    window run is the second)."""
    cfg = hl.config_from_hf(HF)
    params, tokens, _, _, _, terms = _both_sides("whole")
    want = terms["hidden"]
    y = cfg.rope_yarn
    off = {"window off by one": dict(window=dataclasses.replace(
               cfg.window, window=9)),
           "no YaRN": dict(rope_yarn=dataclasses.replace(y, factor=1.0)),
           "no attention factor": dict(rope_yarn=dataclasses.replace(
               y, attention_factor=1.0)),
           "rotary on every column": dict(rope_dim=0),
           "no gate": dict(attn_gate=False)}[wrong]
    got = jitted(hidden_after_runs, dataclasses.replace(cfg, **off))(
        params, tokens)
    errs = [rel(g, want[last]) for g, last in zip(got, (0, 2, 3))]
    assert all(e < 2e-6 for e in errs[:first]) and errs[first] > 1e-4, errs


def test_flash_path_is_the_dot_path():
    """The trunk with the kernels forced on (interpreted here; one tile at
    this size, so `flash_fwd` and `flash_bwd` under the window): the same
    loss and gradients."""
    cfg = hl.config_from_hf(SHARE)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 2)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    a, ga = jitted(loss_and_grads, cfg)(params, tokens, targets)
    b, gb = jitted(loss_and_grads, flash)(params, tokens, targets)
    assert abs(float(a) - float(b)) < 1e-6
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)


def test_the_rotation_kernel_in_the_trunk_is_rope(rope_kernel_taken,
                                                   monkeypatch):
    """The trunk at heads of 128 on a TPU (the fixture's patch: the kernel
    interpreted), `remat` on: q and k of every layer turn in the kernel,
    read out of the projection, forward, recomputed and transposed, both
    rotary forms; loss and gradients are `_rope`'s."""
    hf = {**SHARE, "head_dim": 128}
    cfg = dataclasses.replace(hl.config_from_hf(hf), remat=True)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 2)
    b, gb = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets, cfg)
    widths = {(2, 32, (h + 4) * 128) for h in (4, 6)}   # [q | k | v]
    assert len(rope_kernel_taken) >= 2 * 2 * cfg.n_layers
    assert widths == {s for s in rope_kernel_taken if s[-1] > 6 * 128}
    taken = len(rope_kernel_taken)
    monkeypatch.setattr(rope_kernel, "_on_tpu", lambda: False)
    a, ga = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets, cfg)
    assert len(rope_kernel_taken) == taken
    assert abs(float(a) - float(b)) < 1e-6
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)


def test_bias_moves_by_the_sign_rule_and_adamw_leaves_it():
    cfg = hl.config_from_hf(HF, router_bias_rate=1e-2)
    params = _params(cfg, bias=0.0)
    tokens, targets = seeded_tokens(HF, 3)
    opt = tfm.init_opt_state(params)
    want = reference.loss_terms(hl.state_dict_from_params(params, cfg),
                                tokens, targets, HF)[1]["counts"]
    _, new, opt = tfm.make_train_step(cfg, lr=1e-3)(
        jax.tree.map(jnp.copy, params), opt, tokens, targets)
    got = np.concatenate([np.asarray(new["blocks"][r][tfm.ROUTER_BIAS])
                          for r in (1, 2)])
    np.testing.assert_allclose(
        got, reference.bias_after_step(np.zeros((3, 8)), want, 1e-2),
        atol=1e-7)


# -- (i) the window kind against the attention kind ---------------------------------

@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_a_window_of_the_whole_sequence_is_the_attention_kind_to_the_bit(
        impl):
    base = dict(vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2,
                d_head=16, n_layers=2, d_ff=96, max_seq_len=64,
                norm="rmsnorm", rope=True, rope_theta=100.0, mlp="swiglu",
                use_pos_emb=False, attn_gate=True, dtype=jnp.float32,
                attn_impl=impl)
    full = tfm.TransformerConfig(**base)
    windowed = tfm.TransformerConfig(
        **base, layer_types=("window",) * 2,
        window=tfm.WindowConfig(window=32, n_heads=4, rope_theta=100.0))
    params = _params(full)
    assert jax.tree.structure(params) == jax.tree.structure(
        tfm.init_params(jax.random.PRNGKey(0), windowed))
    tokens, targets = seeded_tokens({"vocab_size": 128}, 5)
    a, ga = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets, full)
    b, gb = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets,
                                            windowed)
    assert float(a) == float(b)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and a window that bites does not
    short = dataclasses.replace(
        windowed, window=dataclasses.replace(windowed.window, window=8))
    assert float(tfm.loss_fn(params, tokens, targets, short)) != float(a)


# -- (ii), (iii) the rotation -------------------------------------------------------

def _rope_as_it_was(x, pos0, theta, hd):
    """`transformer._rope` at this PR's parent, copied."""
    B, T, W = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    t = pos0 + jnp.arange(T, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    cos = jnp.tile(jnp.concatenate([cos, cos], -1), W // hd)
    sin = jnp.tile(jnp.concatenate([-sin, sin], -1), W // hd)
    x32 = x.astype(jnp.float32)
    first = jnp.arange(W) % hd < hd // 2
    partner = jnp.where(first, jnp.roll(x32, -(hd // 2), -1),
                        jnp.roll(x32, hd // 2, -1))
    return (x32 * cos + partner * sin).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_default_rotation_is_the_one_it_was_to_the_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 4 * 32)).astype(
        dtype)
    want = _rope_as_it_was(x, 3, 1e4, 32)
    for kw in ({}, {"rot": 32}, {"rot": 32, "yarn": tfm.YarnConfig()},
               {"yarn": tfm.YarnConfig(factor=1.0, original_max_len=16)}):
        np.testing.assert_array_equal(
            np.asarray(tfm._rope(x, 3, 1e4, 32, **kw), np.float32),
            np.asarray(want, np.float32))
    same = lambda f: str(jax.make_jaxpr(f)(x))
    assert same(lambda x: tfm._rope(x, 0, 1e4, 32)) == same(
        lambda x: _rope_as_it_was(x, 0, 1e4, 32))


def test_yarn_table_is_the_float64_formulas_and_half_a_head_passes():
    """At the published numbers: dim 64, theta 5e5, factor 64 over 4,096,
    beta_fast 64, beta_slow 1: low 5, high 16."""
    r = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
         "original_max_position_embeddings": 4096, "beta_slow": 1,
         "beta_fast": 64, "attention_factor": 1.4158883083359672,
         "partial_rotary_factor": 0.5}
    c = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (
        2 * math.log(500000))
    assert (math.floor(c(64)), math.ceil(c(1))) == (5, 16)
    assert abs(c(64) - 5.66) < 5e-3 and abs(c(1) - 15.80) < 5e-3
    i = np.arange(32, dtype=np.float64)
    f = 500000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 5) / 11, 0, 1)
    want = (1 - ramp) * f + ramp * f / 64
    yarn = tfm.YarnConfig(64.0, 4096, 64.0, 1.0, r["attention_factor"])
    np.testing.assert_allclose(rope_kernel.yarn_inv_freq(5e5, 64, yarn), want,
                               rtol=1e-15)
    np.testing.assert_allclose(reference.yarn_table(r, 64)[0], want,
                               rtol=1e-15)
    assert abs(r["attention_factor"] - (0.1 * math.log(64) + 1)) < 1e-12
    # frequencies 0-5 stay, 16-31 are divided by 64
    assert (want[:6] == f[:6]).all() and np.allclose(want[16:], f[16:] / 64)
    # ends that meet (every frequency turns more than beta_fast times in an
    # original length this long) are refused, not divided by
    with pytest.raises(ValueError, match="no ramp"):
        rope_kernel.yarn_inv_freq(5e5, 64, dataclasses.replace(
            yarn, original_max_len=10 ** 15))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 3 * 128))
    got = np.asarray(tfm._rope(x, 0, 5e5, 128, 64, yarn)).reshape(
        24, 3, 128)
    x = np.asarray(x).reshape(24, 3, 128)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # the 64 that turn, against float64: a pair (i, i + 32) by the angle
    # t inv_i, times the attention factor
    t = np.arange(24, dtype=np.float64)[:, None, None]
    cos, sin = (fn(t * want) * r["attention_factor"]
                for fn in (np.cos, np.sin))
    a, b = x[..., :32].astype(np.float64), x[..., 32:64].astype(np.float64)
    np.testing.assert_allclose(got[..., :32], a * cos - b * sin, atol=2e-5)
    np.testing.assert_allclose(got[..., 32:64], b * cos + a * sin, atol=2e-5)
    # a rotary pair's part of q . k carries the factor's square, the
    # columns that pass none
    one = tfm._rope(x.reshape(1, 24, -1), 0, 5e5, 128, 64,
                    dataclasses.replace(yarn, attention_factor=1.0))
    one = np.asarray(one).reshape(24, 3, 128)
    np.testing.assert_allclose(
        (got[..., :64] ** 2).sum(), r["attention_factor"] ** 2
        * (one[..., :64] ** 2).sum(), rtol=1e-5)


# -- (iv) the shares add up ---------------------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer of 16 experts cut in EIGHT shares of 2: the routed
    parts of the eight and the shared expert ONCE sum to the UNCUT
    reference's layer; the reference given the same share says the same."""
    hf = {**HF, "num_experts": 16}
    whole_cfg = hl.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[0], params["blocks"][1])
    sd = hl.state_dict_from_params(params, whole_cfg)
    w = {n[len("model.layers.1."):]: v for n, v in sd.items()
         if n.startswith("model.layers.1.")}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    rows = m.reshape(-1, 64)
    want, _ = reference._experts_math(rows, w, hf, 0, None)
    whole, _ = tfm._moe_mlp(m, p, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, 64)),
                               np.asarray(want), atol=2e-6)
    shared = reference._swiglu(rows, w, "mlp.shared_expert.")
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = []
    for first in range(0, 16, 2):
        share = {**hf, "num_experts": 2, "num_routed_experts": 16,
                 "first_expert_held": first}
        cfg = hl.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 2] for k in
                        ("w1", "w3", "w2", "b1", "b2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _ = reference._experts_math(rows, w, share, first, None)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                                   np.asarray(same), atol=2e-6)
        part, _ = tfm._moe_mlp(m, held, dataclasses.replace(
            cfg, d_ff_shared=0), None)
        routed.append(part.reshape(-1, 64))
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(want), atol=4e-6)


# -- the pair counters --------------------------------------------------------------

@pytest.mark.parametrize("T,W", [(32, 8), (512, 128), (2048, 512),
                                 (16384, 512)])
def test_pair_counters_against_their_closed_forms(T, W):
    with open(os.path.join(
            ROOT, "benchmark/configs/laguna-xs.2/config.json")) as f:
        c = json.load(f)
    cfg = hl.config_from_hf({**c, "sliding_window": W}, dtype=jnp.bfloat16)
    kept = sum(min(t + 1, W) for t in range(T))
    dot = tfm.attention_pairs(cfg, T)
    assert dot["window"]["kept"] == kept
    assert dot["attention"]["kept"] == dot["attention"]["causal"] == (
        T * (T + 1) // 2)
    assert (dot["window"]["layers"], dot["window"]["heads"],
            dot["attention"]["layers"], dot["attention"]["heads"]) == (
        3, 64, 2, 48)
    assert dot["window"]["computed"] == T * T       # dense under a mask
    got = tfm.attention_pairs(dataclasses.replace(cfg, attn_impl="flash"), T)
    for kind, window in (("window", W), ("attention", None)):
        bq, bk = got[kind]["tiles"]
        assert (bq, bk) == fa._choose_tiles(
            T, 128, jnp.bfloat16, True, got[kind]["heads"],
            window=window)[:2]
        pos = np.arange(T)
        keep = pos[None, :] <= pos[:, None]
        if window:
            keep &= pos[None, :] > pos[:, None] - window
        tiles = keep.reshape(T // bq, bq, T // bk, bk).any((1, 3)).sum()
        assert got[kind]["computed"] == tiles * bq * bk
        assert got[kind]["kept"] == keep.sum()
    if (T, W) == (16384, 512):
        w = got["window"]
        assert w["tiles"] == (512, 512)
        assert round(100 * w["kept"] / w["causal"], 2) == 6.15
        assert w["kept"] == 8257792 and w["causal"] == 134225920
        # two key blocks a query block but the first: 200 % less a tile
        assert w["computed"] == 63 * 512 * 512
        assert 199 < 100 * w["computed"] / w["kept"] <= 200


def test_measured_visits_are_the_plans_and_see_a_kernel_without_its_bound(
        monkeypatch):
    """`attention_visits` MEASURES the pairs a layer's own forward computes
    (a chunk of keys made NaN, the rows that come out NaN counted): on the
    flash path (interpreted here) what `window_bounds` plans, tile by tile,
    for the window layer and for the full one; on the dot path every pair;
    and with the forward kernel's lower bound taken away, the causal tiles:
    the plan is arithmetic, the measurement is the kernel's."""
    with open(os.path.join(
            ROOT, "benchmark/configs/laguna-xs.2/config.json")) as f:
        c = json.load(f)
    T, W, chunk = 2048, 200, 128
    cfg = hl.config_from_hf(
        {**c, **SIZES_OF_A_TOY, "sliding_window": W}, dtype=jnp.bfloat16)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = seeded_tokens({"vocab_size": cfg.vocab_size}, 3, B=1, T=T)[0]
    visits = lambda cfg, mixer: int(np.asarray(tfm.attention_visits(
        params, tokens, cfg, mixer, chunk)).sum()) * chunk
    plan = tfm.attention_pairs(flash, T)
    bq, bk = plan["window"]["tiles"]
    assert T // bq >= 4 and bk % chunk == 0, plan
    assert visits(flash, "window") == plan["window"]["computed"] < (
        visits(flash, "attention")) == plan["attention"]["computed"] < T * T
    assert visits(cfg, "window") == T * T
    real = fa._window_lower_kb
    monkeypatch.setattr(fa, "_window_lower_kb", lambda q_start, w, bk: (
        real(q_start, w, bk) if isinstance(q_start, int) else 0))
    assert tfm.attention_pairs(flash, T) == plan
    assert visits(flash, "window") == plan["attention"]["computed"]


# -- scopes ----------------------------------------------------------------------

def test_scopes_of_the_window_the_rotation_and_the_gate_in_the_step(
        monkeypatch):
    # the rotation's scope opens with its grouped form, which tables past
    # `ROPE_TABLE_BYTES` select: at this size, a bound of nothing
    monkeypatch.setattr(tfm, "ROPE_TABLE_BYTES", 0)
    cfg = dataclasses.replace(hl.config_from_hf(SHARE, router_bias_rate=1e-3),
                              attn_impl="flash")
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in tracing.SWA_SCOPES:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
    # the rotation INSIDE the projection scope; the window's kernels under
    # their own scope and in no run under the block's; a full layer's there
    assert all(f"{tracing.SCOPE_BLK_QKV}/{tracing.SCOPE_ATTN_ROPE}/" in n
               for n in names if f"/{tracing.SCOPE_ATTN_ROPE}/" in n)
    swa = [n for n in names if f"/{tracing.SCOPE_SWA_ATTN}/" in n]
    # (off the chip the backward pass is the blockwise XLA form: no
    # backward kernel's name to find; its ops are the transposed ones above)
    assert any(f"/{fa.FLASH_FWD}" in n for n in swa)
    assert not [n for n in swa if f"/{tracing.SCOPE_BLK_ATTN}/" in n]
    assert any(f"/{tracing.SCOPE_BLK_ATTN}/" in n and f"/{fa.FLASH_FWD}" in n
               for n in names)
    assert tracing.SWA_SCOPES == ("hetu_swa_attn", "hetu_attn_rope",
                                  "hetu_attn_gate")
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    old = ((tracing.STEP, tracing.SCOPE_FWD, tracing.SCOPE_OPT,
            tracing.SCOPE_EXIT) + tracing.MOE_SCOPES + tracing.SSM_SCOPES
           + tracing.SCONV_SCOPES + tracing.SSD_SCOPES + tracing.BLOCK_SCOPES
           + tracing.MLA_SCOPES + tracing.DSA_SCOPES
           + (tracing.SCOPE_EMBED, tracing.SCOPE_HEAD,
              tracing.SCOPE_MOE_SHARED)
           + sum(tracing.REMAT_CANDIDATES, ()))
    for name in tracing.SWA_SCOPES:
        assert f"`{name}`" in doc, name
        for other in old + tracing.SWA_SCOPES:
            assert other == name or (name not in other
                                     and other not in name), (name, other)


def test_small_tables_keep_the_rotation_in_the_projection_scope(monkeypatch):
    """The grouped rotation and its scope are chosen by what the q-wide
    tables would hold through the step, for each kind of layer by its own
    sizes: under `ROPE_TABLE_BYTES` (every rotary cell the benchmark had,
    and this model at a toy size) no `hetu_attn_rope` in the step; past it
    q turns in k-wide groups, bit for bit what the whole-width call gives."""
    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=1,
        d_ff=96, max_seq_len=64, norm="rmsnorm", rope=True, mlp="swiglu",
        use_pos_emb=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = seeded_tokens({"vocab_size": 128}, 8)
    lowered = lambda cfg, params, data: tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), *data).as_text(debug_info=True)
    toy = hl.config_from_hf(SHARE)
    assert tracing.SCOPE_ATTN_ROPE not in lowered(cfg, params,
                                                  (tokens, targets))
    assert tracing.SCOPE_ATTN_ROPE not in lowered(toy, _params(toy),
                                                  seeded_tokens(SHARE, 8))
    whole = tfm.forward_hidden(params, tokens, cfg)[0]
    monkeypatch.setattr(tfm, "ROPE_TABLE_BYTES", 0)
    assert tracing.SCOPE_ATTN_ROPE in lowered(cfg, params, (tokens, targets))
    np.testing.assert_array_equal(
        np.asarray(whole),
        np.asarray(tfm.forward_hidden(params, tokens, cfg)[0]))
    monkeypatch.undo()
    # the cell's sizes are past the bound in both kinds, the widest rotary
    # cell the benchmark had before it not
    tables = lambda T, heads, hd: 2 * 4 * T * heads * hd
    assert min(tables(16384, 64, 128), tables(16384, 48, 128)) > (
        tfm.ROPE_TABLE_BYTES) >= tables(16384, 32, 128)


# -- (vi) refusals by name ----------------------------------------------------------

def test_decode_and_pipeline_refuse_by_name():
    cfg = hl.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(cfg, 16, 0),
            "window=WindowConfig(window=8")
    refuses(lambda: generate._check_decode_args(
        tfm.TransformerConfig(attn_gate=True), 16, 0), "attn_gate=True")
    with pytest.raises(NotImplementedError, match="unequal kinds"):
        pipeline._make_stage_fn(cfg, 1)
    one_kind = dataclasses.replace(
        cfg, n_dense_layers=0, layer_types=("window",) * 4)
    assert tfm.layer_runs(one_kind) == (("window", 4),)
    with pytest.raises(NotImplementedError, match="window, mamba"):
        pipeline._make_stage_fn(one_kind, 1)


def test_a_window_refuses_the_ring_and_a_share_an_ep_mesh():
    cfg = dataclasses.replace(hl.config_from_hf(SHARE), attn_impl="ring")
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"][1])
    with pytest.raises(NotImplementedError, match="window layer"):
        tfm._window(jnp.zeros((1, 8, 64)), p, cfg, None)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "ep"))
    with pytest.raises(tfm.MoEConfigError):
        tfm._moe_mlp(jnp.zeros((1, 8, 64)), p, hl.config_from_hf(SHARE),
                     mesh)


@pytest.mark.parametrize("kw,named", [
    (dict(layer_types=("window",), n_layers=1), "window layer"),
    (dict(layer_types=("window",), n_layers=1, rope=True, n_kv_heads=4,
          window=tfm.WindowConfig(8, 6, 1e4)), "window layer"),
    (dict(rope_dim=7), "rope_dim=7"),
    (dict(attn_gate=True, layer_types=("mla",), n_layers=1,
          mla=tfm.MLAConfig()), "attn_gate=True")])
def test_config_refuses_by_name(kw, named):
    with pytest.raises(ValueError, match=re.escape(named)):
        tfm.TransformerConfig(**kw)
