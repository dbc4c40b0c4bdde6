"""kanana-2-30b-a3b (a DeepSeek-V3 dialect) on the flagship trunk (ISSUE 39),
on the CPU at a small size with the real structure (latent attention whose
keys are wider than its values, 1 dense layer + 2 expert layers with a shared
expert; 8 experts, top 2): the system against the float32 reference
(benchmark/configs/kanana-2-30b-a3b/reference.py), the eight shares of an
expert layer adding up to the whole with the shared expert counted once, the
scopes, and the refusals by name. The reference against `transformers`'
`DeepseekV3ForCausalLM` is in test_references_against_transformers.py, the
other cells' lowered steps in test_cell_digests.py, and its kernels
(rotation, flash at two widths, remat's names), which need no trained
system, in test_kanana_kernels.py."""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import rope as rope_kernel
from hetu_tpu.models import (generate, hf_deepseek_v3 as hd,
                             transformer as tfm)
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, grads_of_loss, jitted, load_reference,
                           refuses, rel, rope_kernel_taken,  # noqa: F401
                           round_trip, seeded_params, seeded_tokens)

reference = load_reference("kanana-2-30b-a3b")

# the published keys at a small size, every expert held
HF = dict(
    attention_bias=False, first_k_dense_replace=1, head_dim=16,
    hidden_act="silu", hidden_size=64, intermediate_size=128,
    kv_lora_rank=32, max_position_embeddings=64, model_type="deepseek_v3",
    moe_intermediate_size=48, moe_layer_freq=1, n_group=1,
    n_routed_experts=8, n_shared_experts=2, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=2, num_hidden_layers=3,
    num_key_value_heads=4, q_lora_rank=None, qk_head_dim=48,
    qk_nope_head_dim=32, qk_rope_head_dim=16, rms_norm_eps=1e-6,
    rope_interleave=True, rope_scaling=None, rope_theta=1000000,
    routed_scaling_factor=2.448, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=24, vocab_size=256)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "n_routed_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
# the share at the published head widths, two heads of 128 + 64: a width the
# one-pass rotation of q serves (one period of 384 columns); run with it
# taken (interpreted here), where a TPU would take it
ROTATED = {**SHARE, "num_attention_heads": 2, "num_key_value_heads": 2,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "head_dim": 64,
           "qk_head_dim": 192}
CONFIGS = {"whole": HF, "share": SHARE, "rope-kernel": ROTATED}


# seeded weights, the selection bias moved off zero so that it matters to the
# picks, the norms' scales off one
_params = functools.partial(
    seeded_params, noisy=("kv_norm", "ln1_scale", "ln2_scale"))


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hd.config_from_hf(SHARE, router_bias_rate=1e-3)
    assert tfm.layer_runs(cfg) == (("mla" + tfm.DENSE, 1), ("mla", 2))
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.d_ff_shared) == (128, 48, 96)
    assert cfg.mla == tfm.MLAConfig(kv_rank=32, nope_dim=32, rope_dim=16,
                                    v_dim=24) and cfg.mla.qk_dim == 48
    assert cfg.rope and cfg.rope_theta == 1e6 and not cfg.tied_head
    assert cfg.ln_eps == 1e-6 and cfg.norm == "rmsnorm"
    assert cfg.router == tfm.Router(
        score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
        scale=2.448, aux_losses=False, bias_rate=1e-3, width=8, first_held=2)
    assert (cfg.n_experts, cfg.n_experts_per_tok) == (2, 2)
    whole = hd.config_from_hf(HF)
    assert whole.router.width == 0 and whole.n_experts == 8
    # the published file itself: layers 0-4, 16 of 128 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/kanana-2-30b-a3b/config.json")) as f:
        cell = hd.config_from_hf(json.load(f))
    assert tfm.layer_runs(cell) == (("mla+dense", 1), ("mla", 4))
    assert (cell.n_heads, cell.mla.qk_dim, cell.mla.v_dim,
            cell.mla.kv_rank) == (32, 192, 128, 512)
    assert (cell.n_experts, cell.router.width, cell.router.first_held,
            cell.n_experts_per_tok, cell.d_ff_shared) == (16, 128, 0, 6, 1536)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    moe = shapes["blocks"][1]
    assert moe["wq"].shape == (4, 2048, 32 * 192)
    assert moe["wkv_a"].shape == (4, 2048, 576)
    assert moe["wkv_b"].shape == (4, 512, 32 * 256)
    assert moe["wo"].shape == (4, 32 * 128, 2048)
    assert moe["router"].shape == (4, 2048, 128)
    assert moe["w1"].shape == (4, 16, 2048, 768)
    assert moe["ws1"].shape == (4, 2048, 1536)
    assert moe["ws2"].shape == (4, 1536, 2048)
    assert shapes["blocks"][0]["w1"].shape == (1, 2048, 6144)
    assert "ws1" not in shapes["blocks"][0]
    assert shapes["head"].shape == (2048, 16128)
    # the ISSUE's count: 576.4M parameters beside 640 selection-bias entries
    assert round(tfm.count_params(shapes) / 1e6, 1) == 576.6


@pytest.mark.parametrize("key,value,named", [
    ("n_group", 2, "group-limited"), ("topk_group", 2, "group-limited"),
    ("q_lora_rank", 1536, "low-rank q"), ("rope_scaling", {"factor": 4},
                                         "scaled rotary"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("scoring_func", "softmax", "sigmoid"),
    ("rope_interleave", False, "interleaved"),
    ("num_key_value_heads", 2, "every head's own"),
    ("qk_head_dim", 64, "qk_nope_head_dim +")])
def test_loader_refuses_by_name(key, value, named):
    refuses(lambda: hd.config_from_hf({**HF, key: value}), named,
            NotImplementedError)


def test_state_dict_round_trip_and_names():
    cfg = hd.config_from_hf(SHARE)
    params = _params(cfg)
    sd = round_trip(hd, params, cfg)
    assert sd["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (8,)
    assert sd["model.layers.0.self_attn.kv_b_proj.weight"].shape == (
        4 * (32 + 24), 32)
    assert sd["model.layers.2.mlp.shared_experts.down_proj.weight"].shape == (
        64, 96)
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in sd
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    # kv_b_proj's rows are a head [k_nope | v]; the trunk's columns are
    # [every head's k_nope | every head's v]
    w = np.asarray(sd["model.layers.0.self_attn.kv_b_proj.weight"])
    ours = np.asarray(params["blocks"][0]["wkv_b"][0])
    np.testing.assert_array_equal(ours[:, 32:64], w[56:88].T)     # head 1 k
    np.testing.assert_array_equal(ours[:, 128 + 24:128 + 48],
                                  w[56 + 32:112].T)               # head 1 v


# -- the system against the reference ---------------------------------------------

@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_matches_reference_loss_hidden_picks_and_gradients(
        which, request):
    hf = CONFIGS[which]
    taken = (request.getfixturevalue("rope_kernel_taken")
             if which == "rope-kernel" else None)
    cfg = hd.config_from_hf(hf, router_bias_rate=1e-3)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 1)
    sd = hd.state_dict_from_params(params, cfg)
    want_loss, want = reference.loss_terms(sd, tokens, targets, hf)
    # `which` in the key: the rope-kernel case traces under its patch
    loss = jitted(tfm.loss_fn, cfg, which)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    hidden, _ = jitted(tfm.forward_hidden, cfg, which)(params, tokens)
    assert rel(hidden, want["hidden"][-1]) < 2e-6
    stats = jitted(tfm.moe_routing_stats, cfg, which)(params, tokens)
    np.testing.assert_array_equal(
        np.sort(np.asarray(stats["experts"]), -1),
        np.sort(np.asarray(want["experts"]), -1))
    np.testing.assert_array_equal(np.asarray(stats["picks"]),
                                  np.asarray(want["counts"]))
    assert int(stats["dropped"].sum()) == 0
    grads = hd.state_dict_from_params(
        jitted(grads_of_loss, cfg, which)(params, tokens, targets), cfg)
    names = [n for n in sd if "e_score" not in n]
    _, want_grads = reference.grads_of(names)(sd, tokens, targets, hf)
    for n in names:
        assert rel(grads[n], want_grads[n]) < 2e-5, n
    # the lean gradient is jax.grad of the plain forward
    few = ["model.layers.1.self_attn.kv_a_layernorm.weight",
           "model.layers.1.mlp.gate.weight",
           "model.layers.2.mlp.shared_experts.up_proj.weight"]
    plain = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, tokens, targets, hf)[0])({n: sd[n] for n in few})
    for n in few:
        assert rel(want_grads[n], plain[n]) < 1e-5, n
    if taken is not None:
        # forward, `jax.grad`'s forward and its transpose, a layer each
        assert taken and set(taken) == {(2, 32, 2 * 192)}


@pytest.mark.parametrize("which", ["share", "rope-kernel"])
def test_flash_path_is_the_dot_path(which, request):
    """The trunk with the kernels forced on (interpreted here): the same
    loss and gradients, at 48 / 24 columns a head; and at 192 / 24 with q's
    rotary columns turned by the kernel too, against `_rope_interleaved` on
    the dot path."""
    cfg = hd.config_from_hf(CONFIGS[which])
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 2)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    a, ga = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets, cfg)
    taken = (request.getfixturevalue("rope_kernel_taken")
             if which == "rope-kernel" else None)
    b, gb = jax.value_and_grad(tfm.loss_fn)(params, tokens, targets, flash)
    if taken is not None:
        assert len(taken) == 2 * cfg.n_layers   # forward and transposed
    assert abs(float(a) - float(b)) < 1e-6
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)


def test_bias_moves_by_the_sign_rule_and_adamw_leaves_it():
    cfg = hd.config_from_hf(HF, router_bias_rate=1e-2)
    params = _params(cfg, bias=0.0)
    tokens, targets = seeded_tokens(HF, 3)
    opt = tfm.init_opt_state(params)
    want = reference.loss_terms(hd.state_dict_from_params(params, cfg),
                                tokens, targets, HF)[1]["counts"]
    _, new, opt = tfm.make_train_step(cfg, lr=1e-3)(
        jax.tree.map(jnp.copy, params), opt, tokens, targets)
    np.testing.assert_allclose(
        np.asarray(new["blocks"][1][tfm.ROUTER_BIAS]),
        reference.bias_after_step(np.zeros((2, 8)), want, 1e-2), atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(opt["m"]["blocks"][1][tfm.ROUTER_BIAS]), np.asarray(want))


# -- the shares add up -------------------------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer of 16 experts cut in EIGHT shares of 2: the routed
    parts of the eight (the system's `_moe_mlp` told its share, less the
    shared expert every member computes alike) and the shared expert ONCE
    sum to the UNCUT reference's layer; each share's own output is its
    routed part plus the shared expert, and the reference given the same
    share says the same."""
    hf = {**HF, "n_routed_experts": 16, "num_hidden_layers": 2}
    whole_cfg = hd.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[0], params["blocks"][1])
    sd = hd.state_dict_from_params(params, whole_cfg)
    w = {n[len("model.layers.1."):]: v for n, v in sd.items()
         if n.startswith("model.layers.1.")}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    rows = m.reshape(-1, 64)
    want, _ = reference._experts_math(rows, w, hf, 0)
    whole, _ = tfm._moe_mlp(m, p, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, 64)),
                               np.asarray(want), atol=2e-6)
    shared = reference._swiglu(rows, w, "mlp.shared_experts.")
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = []
    for first in range(0, 16, 2):
        share = {**hf, "n_routed_experts": 2, "num_routed_experts": 16,
                 "first_expert_held": first}
        cfg = hd.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 2] for k in
                        ("w1", "w3", "w2", "b1", "b2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _ = reference._experts_math(rows, w, share, first)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                                   np.asarray(same), atol=2e-6)
        # what this member adds that no other does
        part, _ = tfm._moe_mlp(m, held, dataclasses.replace(
            cfg, d_ff_shared=0), None)
        np.testing.assert_allclose(
            np.asarray(out - part).reshape(-1, 64), np.asarray(shared),
            atol=2e-6)
        routed.append(part.reshape(-1, 64))
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(want), atol=4e-6)
    # counted eight times it would be off by seven shared experts
    assert float(jnp.max(jnp.abs(7 * shared))) > 1e-2


def test_default_config_has_no_shared_expert_and_the_old_epsilon():
    olmoe = tfm.TransformerConfig(n_experts=4, n_experts_per_tok=2,
                                  n_layers=2, mlp="swiglu")
    blocks = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), olmoe))["blocks"]
    assert not {"ws1", "ws2", "ws3", "wq", "wkv_a"} & set(blocks)
    assert tfm.Router().normalize_eps == 1e-6 and olmoe.mla is None
    with pytest.raises(tfm.MoEConfigError, match="shared expert"):
        tfm.TransformerConfig(d_ff_shared=64)
    with pytest.raises(ValueError, match="mla layer"):
        tfm.TransformerConfig(n_layers=1, layer_types=("mla",))


# -- scopes ----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["share", "rope-kernel"])
def test_scopes_of_latent_attention_and_the_shared_expert_in_the_step(
        which, request):
    cfg = hd.config_from_hf(CONFIGS[which], router_bias_rate=1e-3)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    if which == "rope-kernel":
        request.getfixturevalue("rope_kernel_taken")
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    # the rotation's kernel where it is taken: forward, run again under
    # `remat` and transposed, each call under q's scope, by its name
    # (`reduce/mla.py` and `reduce/block.py` find it there, by phase)
    kernel = [n for n in names if f"/{rope_kernel.ROPE_PAIRS}/" in n]
    assert all(f"/{tracing.SCOPE_MLA_Q}/{rope_kernel.ROPE_PAIRS}/" in n
               for n in kernel)
    phases = {("recompute" if "/rematted_computation/" in n else "bwd")
              if "transpose(" in n else "fwd" for n in kernel}
    assert phases == ({"fwd", "recompute", "bwd"} if which == "rope-kernel"
                      else set())
    assert "flash_" not in rope_kernel.ROPE_PAIRS
    for scope in tracing.MLA_SCOPES:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
        # always INSIDE the block's projection scope
        assert all(f"{tracing.SCOPE_BLK_QKV}/{scope}/" in n
                   for n in under), scope
    shared = [n for n in names if f"/{tracing.SCOPE_MOE_SHARED}/" in n]
    assert any(f"/{tracing.SCOPE_MOE_SHARED}/{tracing.SCOPE_BLK_MLP_UP}/"
               in n for n in shared)
    assert any("transpose(" in n for n in shared)
    # a fifth part: inside none of the four
    assert not [n for n in shared
                if any(f"/{s}/" in n for s in tracing.MOE_SCOPES)]
    assert tracing.MLA_SCOPES == ("hetu_mla_q", "hetu_mla_kv_down",
                                  "hetu_mla_kv_up")
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    old = ((tracing.STEP, tracing.SCOPE_FWD, tracing.SCOPE_OPT,
            tracing.SCOPE_EXIT) + tracing.MOE_SCOPES + tracing.SSM_SCOPES
           + tracing.SCONV_SCOPES + tracing.SSD_SCOPES + tracing.BLOCK_SCOPES
           + (tracing.SCOPE_EMBED, tracing.SCOPE_HEAD)
           + sum(tracing.REMAT_CANDIDATES, ()))
    new = tracing.MLA_SCOPES + (tracing.SCOPE_MOE_SHARED,
                                tracing.REMAT_MLA_LATENT)
    for name in new:
        assert f"`{name}`" in doc, name
        # readers match by substring: no new name in an old one or the
        # other way round, nor in another new one
        for other in old + new:
            assert other == name or (name not in other
                                     and other not in name), (name, other)


# -- refusals by name -------------------------------------------------------------

def test_decode_and_pipeline_refuse_by_name():
    cfg = hd.config_from_hf(HF)
    decode = lambda c: lambda: generate._check_decode_args(c, 16, 0)
    refuses(decode(cfg), "mla=MLAConfig(")
    no_mla = dataclasses.replace(cfg, layer_types=(), mla=None)
    refuses(decode(no_mla), "d_ff_shared=96")
    share = dataclasses.replace(
        hd.config_from_hf(SHARE), layer_types=(), mla=None, d_ff_shared=0,
        n_experts=0, n_dense_layers=0,
        router=tfm.Router(width=8, first_held=2))
    refuses(decode(share), "width=8, first_held=2")
    with pytest.raises(NotImplementedError, match="unequal kinds"):
        pipeline._make_stage_fn(cfg, 1)
    one_kind = dataclasses.replace(cfg, n_dense_layers=0)
    assert tfm.layer_runs(one_kind) == (("mla", 3),)
    with pytest.raises(NotImplementedError, match=r"latent attention \(mla\)"):
        pipeline._make_stage_fn(one_kind, 1)


def test_latent_attention_refuses_a_sequence_sharded_mesh():
    cfg = dataclasses.replace(hd.config_from_hf(HF), attn_impl="ring")
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"][0])
    with pytest.raises(NotImplementedError, match="one head width"):
        tfm._mla(jnp.zeros((1, 8, 64)), p, cfg, None)
