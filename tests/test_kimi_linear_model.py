"""Kimi-Linear on the flagship trunk (models/hf_kimi_linear.py): the loader,
the chunked gated delta rule (models/kda.py) against the recurrence over
POSITIONS of the float32 reference
(benchmark/configs/kimi-linear-48b-a3b/reference.py), forward and gradients,
also where 1 / exp(G) overflows float32; the system against the reference at a
toy size; latent attention without rotation; the 32 shares of an expert layer
adding up to the whole with the shared expert counted once; the scopes; the
refusals by name. The other cells' lowered steps are in test_cell_digests.py."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import (generate, hf_deepseek_v3 as hd,
                             hf_kimi_linear as hk, kda, transformer as tfm)
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, grads_of_loss, jitted, load_reference,
                           loss_and_grads, refuses, rel, round_trip,
                           seeded_params, seeded_tokens)

reference = load_reference("kimi-linear-48b-a3b")

# the published keys at a small size, every expert held: layers 1-5 are KDA +
# dense MLP, KDA, KDA, latent, KDA
HF = dict(
    first_k_dense_replace=1, head_dim=16, hidden_act="silu", hidden_size=64,
    intermediate_size=128, kv_lora_rank=32,
    linear_attn_config=dict(full_attn_layers=[4, 8], head_dim=16,
                            kda_layers=[1, 2, 3, 5, 6, 7], num_heads=4,
                            short_conv_kernel_size=4),
    mla_use_nope=True, model_max_length=64, model_type="kimi_linear",
    moe_intermediate_size=48, moe_layer_freq=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_attention_heads=4,
    num_expert_group=1, num_experts=8, num_experts_per_token=2,
    num_hidden_layers=5, num_key_value_heads=4, num_nextn_predict_layers=0,
    num_shared_experts=1, q_lora_rank=None, qk_nope_head_dim=32,
    qk_rope_head_dim=16, rms_norm_eps=1e-5, rope_scaling=None,
    rope_theta=10000, routed_scaling_factor=2.446, tie_word_embeddings=False,
    topk_group=1, use_grouped_topk=True, v_head_dim=24, vocab_size=256)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "num_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}
_params = lambda cfg, seed=0: seeded_params(
    cfg, seed, noisy=("kv_norm", "ln1_scale", "ln2_scale", "kda_norm"))


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hk.config_from_hf(SHARE, router_bias_rate=1e-3, kda_chunk=16)
    assert tfm.layer_runs(cfg) == (("kda" + tfm.DENSE, 1), ("kda", 2),
                                   ("mla", 1), ("kda", 1))
    assert cfg.kda == tfm.KDAConfig(n_heads=4, head_dim=16, d_conv=4,
                                    chunk=16)
    assert cfg.mla == tfm.MLAConfig(kv_rank=32, nope_dim=32, rope_dim=16,
                                    v_dim=24, rotate=False)
    assert not cfg.rope and not cfg.use_pos_emb and not cfg.tied_head
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.d_ff_shared) == (128, 48, 48)
    assert cfg.router == tfm.Router(
        score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
        scale=2.446, aux_losses=False, bias_rate=1e-3, width=8, first_held=2)
    # the published file itself: layers 1-5, 8 of 256 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/kimi-linear-48b-a3b/config.json")) as f:
        cell = hk.config_from_hf(json.load(f))
    assert cell.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert (cell.n_experts, cell.router.width, cell.n_experts_per_tok,
            cell.d_ff_shared, cell.n_dense_layers) == (8, 256, 8, 1024, 1)
    assert cell.kda == tfm.KDAConfig() and cell.ln_eps == 1e-5
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    moe = shapes["blocks"][1]
    assert moe["kda_wqkv"].shape == (2, 2304, 3 * 4096)
    assert moe["kda_conv"].shape == (2, 4, 3 * 4096)
    assert moe["kda_fb"].shape == (2, 128, 4096)
    assert moe["w1"].shape == (2, 8, 2304, 1024)
    assert shapes["blocks"][2]["wkv_b"].shape == (1, 512, 32 * 256)
    # the ISSUE's count: 602.4M parameters beside 1,024 selection-bias entries
    assert round(tfm.count_params(shapes) / 1e6, 1) == 602.6


@pytest.mark.parametrize("key,value,named", [
    ("q_lora_rank", 1536, "low-rank q"),
    ("num_expert_group", 2, "num_expert_group=2"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers=1"),
    ("moe_router_activation_func", "softmax", "sigmoid"),
    ("num_key_value_heads", 2, "every head's own")])
def test_loader_refuses_by_name(key, value, named):
    refuses(lambda: hk.config_from_hf({**HF, key: value}), named,
            NotImplementedError)


def test_loader_refuses_scaled_rotation_and_unnamed_layers():
    refuses(lambda: hk.config_from_hf(
        {**HF, "mla_use_nope": False, "rope_scaling": {"factor": 4}}),
        "mla_use_nope false with rope_scaling", NotImplementedError)
    # rotated latent attention itself is the trunk's (kanana's)
    assert hk.config_from_hf({**HF, "mla_use_nope": False}).mla.rotate
    la = {**HF["linear_attn_config"], "full_attn_layers": [8]}
    refuses(lambda: hk.config_from_hf({**HF, "linear_attn_config": la}),
            "layers [4]", NotImplementedError)


def test_state_dict_round_trip_and_names():
    cfg = hk.config_from_hf(SHARE)
    sd = round_trip(hk, _params(cfg), cfg)
    at = "model.layers.1.self_attn."
    assert sd[at + "q_conv1d.weight"].shape == (64, 1, 4)
    assert sd[at + "A_log"].shape == (1, 1, 4, 1)
    assert sd[at + "f_b_proj.weight"].shape == (64, 16)
    assert sd[at + "o_norm.weight"].shape == (16,)
    assert sd["model.layers.3.self_attn.kv_b_proj.weight"].shape == (
        4 * (32 + 24), 32)
    assert "model.layers.1.block_sparse_moe.experts.2.w1.weight" in sd
    assert "model.layers.1.block_sparse_moe.experts.0.w1.weight" not in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd


# -- the chunked rule against the recurrence over positions ------------------------

def _scan_inputs(T, seed=0, hard=False, H=2, K=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (2, T, H, K)))
    v = jax.random.normal(ks[2], (2, T, H, K))
    g = -jnp.exp(jax.random.normal(ks[3], (2, T, H, K)) - 2)
    if hard:    # -20 a position on a third of the channels: -1,280 a chunk
        g = g.at[..., ::3].set(-20.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T,chunk,hard", [
    (64, 64, False), (40, 16, False), (100, 32, False), (64, 8, False),
    (130, 64, True), (96, 64, True)],
    ids=["one-chunk", "16-not-dividing-40", "32-not-dividing-100", "chunk-8",
         "decay-20-a-position-130", "decay-20-a-position-96"])
def test_chunked_form_is_the_recurrence_over_positions(T, chunk, hard):
    """Forward and every gradient, at chunks that do and do not divide T, and
    where the log-decay reaches -1,280 inside a chunk (1 / exp(G) is inf in
    float32 from -88 on): every value finite."""
    x = _scan_inputs(T, hard=hard)
    w = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    chunked = lambda *a: kda.scan(*a, chunk)
    with jax.default_matmul_precision("highest"):
        want = reference._recurrence(*x)
        want_g = jax.grad(lambda *a: jnp.sum(reference._recurrence(*a) * w),
                          argnums=range(5))(*x)
    got = jax.jit(chunked)(*x)
    got_g = jax.jit(jax.grad(lambda *a: jnp.sum(chunked(*a) * w),
                             argnums=range(5)))(*x)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in (got,) + got_g)
    assert rel(got, want) < 2e-6
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert rel(a, b) < 5e-6, name
    if hard:
        assert float(kda.chunk_log_decay_min(x[3], chunk)) < -1000


def test_naive_factoring_overflows_where_the_chunked_form_does_not():
    """exp(G_r) / exp(G_i) written as two factors is inf * 0 at the decays
    of the case above: the trap the pairwise differences avoid."""
    g = _scan_inputs(64, hard=True)[3][0, :, 0]
    G = jnp.cumsum(g, 0)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(G)[:, None]
                                         * (1.0 / jnp.exp(G))[None])))


def test_inverse_of_the_unit_lower_system_is_exact():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1)
    X = kda.unit_lower_inverse(A)
    want = np.linalg.inv(np.eye(64) + np.asarray(A, np.float64))
    assert rel(X, want) < 1e-5
    # its rule: d X = -X dA X
    dA = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), A.shape), -1)
    _, dX = jax.jvp(kda.unit_lower_inverse, (A,), (dA,))
    assert rel(dX, -want @ np.asarray(dA, np.float64) @ want) < 1e-5


def test_scan_terms_are_the_recurrences_own():
    x = _scan_inputs(100)
    o, terms = kda.scan(*x, 32, terms=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(
        kda.scan(*x, 32)))
    assert terms["entering"].shape == (2, 4, 2, 16, 16)
    assert float(jnp.max(jnp.abs(terms["entering"][:, 0]))) == 0.0
    g = np.asarray(x[3], np.float64)
    np.testing.assert_allclose(np.asarray(terms["G"])[:, 32:64],
                               np.cumsum(g[:, 32:64], 1), rtol=1e-5)


# -- the system against the reference ---------------------------------------------

@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_matches_reference_loss_hidden_gradients_and_adamw(which):
    hf = CONFIGS[which]
    cfg = hk.config_from_hf(hf, kda_chunk=16)
    params = _params(cfg, 1)
    sd = hk.state_dict_from_params(params, cfg)
    tokens, targets = seeded_tokens(hf, 3, B=2, T=40)
    want_loss, terms = reference.loss_terms(sd, tokens, targets, hf)
    loss, grads = jitted(loss_and_grads, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    from model_harness import hidden_after_runs
    hidden = jitted(hidden_after_runs, cfg)(params, tokens)
    for (kind, layers), got in zip(tfm.run_layers(cfg), hidden):
        assert rel(got, terms["hidden"][layers[-1]]) < 2e-5, kind
    names = sorted(n for n in sd if "e_score" not in n)
    _, _, want = reference.grads_of(names)(sd, tokens, targets, hf)
    got = hk.state_dict_from_params(grads, cfg)
    for n in names:
        assert rel(got[n], want[n]) < 5e-5, n
    # the picks the step counted are the reference's
    counts = np.concatenate([np.asarray(b[tfm.ROUTER_BIAS]) for b in
                             tfm.run_blocks(cfg, grads["blocks"])
                             if tfm.ROUTER_BIAS in b])
    np.testing.assert_array_equal(counts, np.asarray(terms["counts"]))
    # AdamW's step, the first: the reference's float64 rule
    new, _ = tfm.adamw_update(params, grads, tfm.init_opt_state(params),
                              lr=1e-3)
    new_sd = hk.state_dict_from_params(new, cfg)
    adamw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    for n in ("model.layers.1.self_attn.A_log",
              "model.layers.0.self_attn.dt_bias",
              "model.layers.3.self_attn.kv_b_proj.weight"):
        p = np.asarray(sd[n], np.float64)
        want_p = reference.adamw_after_step(p, 0 * p, 0 * p, want[n], 1.0,
                                            1e-3, adamw)
        assert rel(np.asarray(new_sd[n]) - p, want_p - p) < 1e-3, n


WRONG = {
    "no-delta-term": ("v_t - jnp.einsum(\"bhkv,bhk->bhv\", S, k_t)", "v_t"),
    "decay-a-head": ("S = jnp.exp(g_t)[..., None] * S", "S = jnp.exp("
                     "jnp.mean(g_t, -1, keepdims=True))[..., None] * S"),
    "output-before-update": (
        "return S, jnp.einsum(\"bhkv,bhk->bhv\", S, q_t)",
        "return S, jnp.einsum(\"bhkv,bhk->bhv\", S0, q_t)"),
    "no-l2-norm": ("q, k = _l2(q) * K ** -0.5, _l2(k)",
                   "q, k = q * K ** -0.5, k"),
    "beta-one": ("beta = jax.nn.sigmoid(u @ w[\"b_proj.weight\"].T)",
                 "beta = jnp.ones(u.shape[:2] + (H,))"),
    "gate-before-norm": (
        "o = _rms(o, w[\"o_norm.weight\"], c[\"rms_norm_eps\"]) * "
        "heads(gate)",
        "o = _rms(o * heads(gate), w[\"o_norm.weight\"], "
        "c[\"rms_norm_eps\"])"),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_reference_wrong_on_purpose_is_told_from_the_system(name):
    """The check's table at a toy size: the reference patched in ONE place
    reads away from the system in the residual stream."""
    old, new = WRONG[name]
    path = os.path.join(ROOT, "benchmark/configs/kimi-linear-48b-a3b",
                        "reference.py")
    text = open(path).read()
    assert text.count(old) == 1, name
    text = text.replace(old, new)
    if name == "output-before-update":
        text = text.replace("        S = jnp.exp(g_t)[..., None] * S\n",
                            "        S0 = S\n"
                            "        S = jnp.exp(g_t)[..., None] * S\n")
    wrong = {}
    exec(compile(text, path, "exec"), wrong)
    cfg = hk.config_from_hf(HF, kda_chunk=16)
    params = _params(cfg, 1)
    sd = hk.state_dict_from_params(params, cfg)
    tokens, targets = seeded_tokens(HF, 3, B=2, T=40)
    from model_harness import hidden_after_runs
    hidden = jitted(hidden_after_runs, cfg)(params, tokens)
    _, terms = wrong["loss_terms"](sd, tokens, targets, HF)
    assert rel(hidden[0], terms["hidden"][0]) > 1e-3, name


# -- latent attention without rotation --------------------------------------------

def test_rotate_false_is_the_projections_with_the_rotations_taken_out():
    cfg = hk.config_from_hf(HF)
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"][2])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    q, k, v, _ = tfm._mla_qkv(h, p, cfg)
    m = cfg.mla
    raw, k_shared = jnp.split(h @ p["wkv_a"], [m.kv_rank], -1)
    k_nope, want_v = jnp.split(
        tfm._rms_norm32(raw, p["kv_norm"], cfg.ln_eps) @ p["wkv_b"],
        [cfg.n_heads * m.nope_dim], -1)
    np.testing.assert_allclose(np.asarray(q), np.asarray(h @ p["wq"]),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(k), np.asarray(tfm._mla_keys(k_nope, k_shared,
                                                cfg.n_heads)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v), np.asarray(want_v), atol=1e-6)
    # and with rotation it is another q and another shared key
    turned = dataclasses.replace(cfg, mla=dataclasses.replace(
        m, rotate=True))
    q2, k2, v2, _ = tfm._mla_qkv(h, p, turned)
    assert rel(q2, q) > 0.1 and rel(k2, k) > 0.1
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


def test_rotate_true_is_the_default_and_kananas_config_says_nothing_else():
    assert tfm.MLAConfig().rotate
    with open(os.path.join(
            ROOT, "benchmark/configs/kanana-2-30b-a3b/config.json")) as f:
        assert hd.config_from_hf(json.load(f)).mla == tfm.MLAConfig(
            kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, rotate=True)


# -- the shares add up -------------------------------------------------------------

def test_the_32_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer of 64 experts cut in 32 shares of 2: the routed
    parts of the 32 (the system's `_moe_mlp` told its share, less the shared
    expert every member computes alike) and the shared expert ONCE sum to
    the UNCUT reference's layer."""
    hf = {**HF, "num_experts": 64, "num_hidden_layers": 2}
    whole_cfg = hk.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[0], params["blocks"][1])
    sd = hk.state_dict_from_params(params, whole_cfg)
    at = "model.layers.1.block_sparse_moe."
    w = {n[len(at):]: v for n, v in sd.items() if n.startswith(at)}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    rows = m.reshape(-1, 64)
    want, _ = reference._experts_math(rows, w, hf, 0)
    shared = reference._shared_math(rows, w)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = []
    for first in range(0, 64, 2):
        share = {**hf, "num_experts": 2, "num_routed_experts": 64,
                 "first_expert_held": first}
        cfg = hk.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 2] for k in
                        ("w1", "w3", "w2", "b1", "b2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _ = reference._experts_math(rows, w, share, first)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                                   np.asarray(same), atol=2e-6)
        routed.append(out.reshape(-1, 64) - shared)
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(want), atol=5e-6)


# -- scopes ------------------------------------------------------------------------

def test_scopes_of_the_kda_mixer_in_the_step():
    cfg = hk.config_from_hf(SHARE, router_bias_rate=1e-3, kda_chunk=16)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in tracing.KDA_SCOPES + (tracing.SCOPE_KDA_SOLVE,):
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
    # the solve INSIDE the scan; no part inside the attention block's scopes
    assert all(f"{tracing.SCOPE_KDA_SCAN}/{tracing.SCOPE_KDA_SOLVE}/" in n
               for n in names if f"/{tracing.SCOPE_KDA_SOLVE}/" in n)
    assert not [n for n in names if "hetu_kda_" in n and any(
        f"/{s}/" in n for s in tracing.BLOCK_SCOPES[:3])]
    assert tracing.KDA_SCOPES == ("hetu_kda_proj", "hetu_kda_conv",
                                  "hetu_kda_gate", "hetu_kda_scan")
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    old = ((tracing.STEP, tracing.SCOPE_FWD, tracing.SCOPE_OPT,
            tracing.SCOPE_EXIT) + tracing.MOE_SCOPES + tracing.SSM_SCOPES
           + tracing.SCONV_SCOPES + tracing.SSD_SCOPES + tracing.BLOCK_SCOPES
           + tracing.MLA_SCOPES + (tracing.SCOPE_EMBED, tracing.SCOPE_HEAD)
           + sum(tracing.REMAT_CANDIDATES, ()))
    new = tracing.KDA_SCOPES + (tracing.SCOPE_KDA_SOLVE,
                                tracing.REMAT_KDA_INV)
    for name in new:
        assert f"`{name}`" in doc, name
        for other in old + new:
            assert other == name or (name not in other
                                     and other not in name), (name, other)


def test_no_kda_layer_imports_no_kda_module():
    """A stack without the kind traces nothing of it: `models/kda.py` is
    imported by `_kda` alone (import cost stays off the other cells)."""
    import subprocess
    import sys
    code = ("import sys, jax, jax.numpy as jnp\n"
            "from hetu_tpu.models import transformer as tfm\n"
            "cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, "
            "n_heads=2, n_layers=1, d_ff=64, max_seq_len=16)\n"
            "p = tfm.init_params(jax.random.PRNGKey(0), cfg)\n"
            "t = jnp.zeros((1, 8), jnp.int32)\n"
            "jax.jit(lambda p: tfm.loss_fn(p, t, t, cfg)).lower(p)\n"
            "assert 'hetu_tpu.models.kda' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


# -- refusals by name --------------------------------------------------------------

def test_decode_pipeline_and_meshes_refuse_by_name():
    cfg = hk.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(cfg, 16, 0),
            "kda=KDAConfig(")
    with pytest.raises(NotImplementedError, match="unequal kinds"):
        pipeline._make_stage_fn(cfg, 1)
    one_kind = dataclasses.replace(cfg, n_dense_layers=0,
                                   layer_types=("kda",) * 5)
    with pytest.raises(NotImplementedError, match="kda mixers"):
        pipeline._make_stage_fn(one_kind, 1)
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"][0])
    h = jnp.zeros((1, 16, 64))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    with pytest.raises(NotImplementedError, match="sp or ep > 1"):
        tfm._kda(h, p, cfg, mesh)
    with pytest.raises(NotImplementedError, match="no attention bias"):
        tfm._kda(h, p, cfg, None, attn_bias=jnp.zeros((1, 1, 1, 16)))


@pytest.mark.parametrize("change,named", [
    (dict(kda=None), "a kda layer takes `kda` sizes"),
    (dict(post_ln=True), "a kda layer takes `kda` sizes"),
    (dict(kda=tfm.KDAConfig(n_heads=4, head_dim=16, chunk=48)),
     "a chunk that is a power of two"),
    (dict(attn_gate=True), "mla, dsa and kda layers have neither")])
def test_config_refuses_by_name(change, named):
    cfg = hk.config_from_hf(HF)
    refuses(lambda: dataclasses.replace(cfg, **change), named, ValueError)
