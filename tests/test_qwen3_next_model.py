"""Qwen3-Next on the flagship trunk (models/hf_qwen3_next.py): the loader with
the key-head grouping and the [q | gate] interleave undone; the chunked gated
delta rule with a decay a HEAD (models/kda.py) against the recurrence over
POSITIONS of the float32 reference
(benchmark/configs/qwen3-next-80b-a3b/reference.py) and against the channel
form with g broadcast, forward and gradients, also where 1 / exp(G) overflows
float32; the system against the reference at a toy size, with a non-zero w in
every zero-centred norm; the new fields at their defaults; the 16 shares of
an expert layer adding up to the whole with the gated shared expert counted
once; the scopes; the refusals by name. The other cells' lowered steps are in
test_cell_digests.py."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import (generate, hf_kimi_linear as hk, hf_laguna,
                             hf_olmoe, hf_qwen3_next as hq, kda,
                             transformer as tfm)
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, hidden_after_runs, jitted, load_reference,
                           loss_and_grads, refuses, rel, round_trip,
                           seeded_params, seeded_tokens)

reference = load_reference("qwen3-next-80b-a3b")

# the published keys at a small size, every expert held: layers 0-3 are GDN,
# GDN, GDN, gated attention
HF = dict(
    decoder_sparse_step=1, full_attention_interval=4, head_dim=32,
    hidden_act="silu", hidden_size=64, intermediate_size=128,
    linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_value_head_dim=16,
    max_position_embeddings=64, mlp_only_layers=[], model_type="qwen3_next",
    moe_intermediate_size=48, norm_topk_prob=True, num_attention_heads=4,
    num_experts=8, num_experts_per_tok=3, num_hidden_layers=4,
    num_key_value_heads=2, partial_rotary_factor=0.25, rms_norm_eps=1e-6,
    rope_scaling=None, rope_theta=10000000,
    shared_expert_intermediate_size=40, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=256,
    assumed={"router_aux_loss_coef": 0.001})
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "num_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
# a non-zero w in EVERY zero-centred norm (the stream's, the final one, the
# q/k norms), and the head norm's scale and dt_bias off their constants
_noisy = lambda name: (name.endswith("_scale") or name in (
    "q_norm", "k_norm", "gdn_norm", "gdn_dt_bias"))


def _params(cfg, seed=0):
    """Seeded weights with the decays made to MATTER: at A = U(0, 16) a head
    forgets everything a position and the decay's gradients are e^-16 of the
    others' (rounding); A_log - 3 is A in (0, 0.8]."""
    params = seeded_params(cfg, seed, bias=None, noisy=_noisy)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x - 3.0 if path[-1].key == "gdn_A_log" else x, params)



# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_row():
    cfg = hq.config_from_hf(SHARE, gdn_chunk=16)
    assert tfm.layer_runs(cfg) == (("gdn", 3), ("attention", 1))
    assert cfg.gdn == tfm.GDNConfig(n_k_heads=2, n_v_heads=4, k_dim=16,
                                    v_dim=16, d_conv=4, chunk=16)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.rope_dim) == (
        4, 2, 32, 8)
    assert cfg.rope and cfg.rope_theta == 1e7 and not cfg.use_pos_emb
    assert cfg.norm_offset and cfg.shared_gate and cfg.qk_norm == "head"
    assert cfg.attn_gate == "column" and not cfg.tied_head
    assert (cfg.d_ff_expert, cfg.d_ff_shared, cfg.ln_eps) == (48, 40, 1e-6)
    assert cfg.router == tfm.Router(
        score="softmax", normalize=True, normalize_eps=0.0,
        loss_weights=(0.001, 0.0), width=8, first_held=2)
    # the published file itself: layers 0-3, 32 of 512 experts from expert 0
    with open(os.path.join(
            ROOT, "benchmark/configs/qwen3-next-80b-a3b/config.json")) as f:
        cell = hq.config_from_hf(json.load(f))
    assert cell.layer_types == ("gdn", "gdn", "gdn", "attention")
    assert (cell.n_experts, cell.router.width, cell.n_experts_per_tok,
            cell.d_ff_shared, cell.rope_dim) == (32, 512, 10, 512, 64)
    assert cell.gdn == tfm.GDNConfig()
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    gdn, attn = shapes["blocks"]
    assert gdn["gdn_wqkvz"].shape == (3, 2048, 12288)
    assert gdn["gdn_wba"].shape == (3, 2048, 64)
    assert gdn["gdn_conv"].shape == (3, 4, 8192)
    assert gdn["w1"].shape == (3, 32, 2048, 512)
    assert gdn["wsg"].shape == (3, 2048, 1)
    assert attn["wqkv"].shape == (1, 2048, (16 + 2 * 2) * 256)
    assert attn["wg"].shape == (1, 2048, 4096)
    assert attn["q_norm"].shape == (1, 256)
    # the ISSUE's count: 626.0M parameters beside the unused bias leaves
    assert round(tfm.count_params(shapes) / 1e6, 1) == 626.3


@pytest.mark.parametrize("key,value,named", [
    ("mlp_only_layers", [0], "mlp_only_layers=[0]"),
    ("decoder_sparse_step", 2, "decoder_sparse_step=2"),
    ("rope_scaling", {"factor": 4}, "rope_scaling="),
    ("use_sliding_window", True, "use_sliding_window=True"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers=1"),
    ("hidden_act", "gelu", "hidden_act='gelu'")])
def test_loader_refuses_by_name(key, value, named):
    refuses(lambda: hq.config_from_hf({**HF, key: value}), named,
            NotImplementedError)


def test_state_dict_round_trip_grouping_and_interleave():
    cfg = hq.config_from_hf(SHARE)
    params = _params(cfg)
    sd = round_trip(hq, params, cfg)
    at = "model.layers.1.linear_attn."
    assert sd[at + "in_proj_qkvz.weight"].shape == (2 * 16 * 2 + 2 * 64, 64)
    assert sd[at + "in_proj_ba.weight"].shape == (8, 64)
    assert sd[at + "conv1d.weight"].shape == (2 * 32 + 64, 1, 4)
    assert sd[at + "A_log"].shape == sd[at + "dt_bias"].shape == (4,)
    assert sd[at + "norm.weight"].shape == (16,)
    assert sd["model.layers.3.self_attn.q_proj.weight"].shape == (
        4 * 32 * 2, 64)
    assert sd["model.layers.3.self_attn.k_proj.weight"].shape == (64, 64)
    assert sd["model.layers.0.mlp.shared_expert_gate.weight"].shape == (1, 64)
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in sd
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in sd
    # the checkpoint groups W_qkvz's rows by KEY head: [q 16 | k 16 | v 2 x
    # 16 | z 2 x 16] twice; the trunk's columns are [q | k | v | z]
    b = jax.tree.map(lambda x: x[1], params["blocks"][0])
    rows = np.asarray(sd[at + "in_proj_qkvz.weight"]).reshape(2, 96, 64)
    w = np.asarray(b["gdn_wqkvz"]).T
    for j in range(2):
        np.testing.assert_array_equal(rows[j, :16], w[16 * j:16 * (j + 1)])
        np.testing.assert_array_equal(rows[j, 16:32],
                                      w[32 + 16 * j:32 + 16 * (j + 1)])
        np.testing.assert_array_equal(rows[j, 32:64],
                                      w[64 + 32 * j:64 + 32 * (j + 1)])
        np.testing.assert_array_equal(rows[j, 64:],
                                      w[128 + 32 * j:128 + 32 * (j + 1)])
    ba = np.asarray(sd[at + "in_proj_ba.weight"]).reshape(2, 4, 64)
    wba = np.asarray(b["gdn_wba"]).T
    np.testing.assert_array_equal(ba[1, :2], wba[2:4])       # b of heads 2, 3
    np.testing.assert_array_equal(ba[1, 2:], wba[4 + 2:4 + 4])   # their a
    # a head's q rows beside its gate's in q_proj
    a = jax.tree.map(lambda x: x[0], params["blocks"][1])
    qg = np.asarray(sd["model.layers.3.self_attn.q_proj.weight"]).reshape(
        4, 2, 32, 64)
    np.testing.assert_array_equal(
        qg[:, 0].reshape(128, 64), np.asarray(a["wqkv"])[:, :128].T)
    np.testing.assert_array_equal(qg[:, 1].reshape(128, 64),
                                  np.asarray(a["wg"]).T)


# -- the chunked rule with a decay a head ------------------------------------------

def _scan_inputs(T, seed=0, hard=False, H=2, K=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (2, T, H, K)))
    v = jax.random.normal(ks[2], (2, T, H, K))
    g = -jnp.exp(jax.random.normal(ks[3], (2, T, H)) - 2)
    if hard:    # -20 a position on head 0: -1,280 a chunk of 64
        g = g.at[..., 0].set(-20.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T,chunk,hard", [
    (40, 16, False), (130, 64, True)],
    ids=["16-not-dividing-40", "decay-20-a-position-130"])
def test_a_heads_decay_is_the_recurrence_and_the_channel_form(T, chunk, hard):
    """Forward and every gradient of `kda.scan` with a decay a HEAD, g (B, T,
    H), against the recurrence over positions, at chunks that do and do not
    divide T, and where the log-decay reaches -1,280 inside a chunk (1 /
    exp(G) is inf in float32 from -88 on): every value finite; and it IS
    `kda.scan` with g broadcast over a head's columns (the channel form,
    which serves it: the XLA head form PR 68 wrote lost on the chip and
    went, docs/KERNELS.md)."""
    x = _scan_inputs(T, hard=hard)
    w = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    form = lambda *a: kda.scan(*a, chunk)
    with jax.default_matmul_precision("highest"):
        want = reference._recurrence(*x)
        want_g = jax.grad(lambda *a: jnp.sum(reference._recurrence(*a) * w),
                          argnums=range(5))(*x)
    got = jax.jit(form)(*x)
    got_g = jax.jit(jax.grad(lambda *a: jnp.sum(form(*a) * w),
                             argnums=range(5)))(*x)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in (got,) + got_g)
    assert rel(got, want) < 2e-6
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        # where a head forgets everything a position, its decay's
        # gradient is e^-20 of the others': absolute, of the largest
        if name == "g" and hard:
            assert float(jnp.max(jnp.abs(a - b))) < 5e-6 * float(
                jnp.max(jnp.abs(b)))
        else:
            assert rel(a, b) < 5e-6, name
    q, k, v, g, beta = x
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jax.jit(form)(
        q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta)))
    if hard:
        assert float(kda.chunk_log_decay_min(x[3], chunk)) < -1000


def test_a_heads_terms_come_back_a_heads():
    x = _scan_inputs(100)
    o, terms = kda.scan(*x, 32, terms=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(
        kda.scan(*x, 32)))
    assert terms["entering"].shape == (2, 4, 2, 16, 16)
    assert terms["G"].shape == (2, 100, 2)
    g = np.asarray(x[3], np.float64)
    np.testing.assert_allclose(np.asarray(terms["G"])[:, 32:64],
                               np.cumsum(g[:, 32:64], 1), rtol=1e-5)


# -- the system against the reference ---------------------------------------------

def test_system_matches_reference_loss_hidden_gradients_and_adamw():
    # the SHARE (experts 2 and 3 of 8): the whole layer is held to the
    # reference by the shares' test below and by transformers' own module
    hf = SHARE
    cfg = hq.config_from_hf(hf, gdn_chunk=16)
    params = _params(cfg, 1)
    sd = hq.state_dict_from_params(params, cfg)
    for n, w in sd.items():     # every zero-centred w is off zero
        if n.endswith(("layernorm.weight", "_norm.weight",
                       "model.norm.weight")):
            assert float(jnp.min(jnp.abs(w))) > 0, n
    tokens, targets = seeded_tokens(hf, 3, B=2, T=40)
    want_loss, terms = reference.loss_terms(sd, tokens, targets, hf)
    loss, grads = jitted(loss_and_grads, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    # the balance loss is in it, at the published weight
    assert float(terms["balance"]) > 4 * 0.9
    assert abs(float(want_loss) - float(jnp.mean(terms["nll"]))
               - 0.001 * float(terms["balance"])) < 1e-6
    hidden = jitted(hidden_after_runs, cfg)(params, tokens)
    for (kind, layers), got in zip(tfm.run_layers(cfg), hidden):
        assert rel(got, terms["hidden"][layers[-1]]) < 2e-5, kind
    names = sorted(sd)
    _, _, want = reference.grads_of(names)(sd, tokens, targets, hf)
    got = hq.state_dict_from_params(grads, cfg)
    for n in names:
        assert rel(got[n], want[n]) < 1e-4, n
    # AdamW's step, the first: the reference's float64 rule; a zero-centred
    # w decays as every leaf does
    new, _ = tfm.adamw_update(params, grads, tfm.init_opt_state(params),
                              lr=1e-3)
    new_sd = hq.state_dict_from_params(new, cfg)
    adamw = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    for n in ("model.layers.1.linear_attn.A_log",
              "model.layers.0.linear_attn.dt_bias",
              "model.layers.2.input_layernorm.weight",
              "model.layers.3.self_attn.q_norm.weight",
              "model.layers.3.self_attn.q_proj.weight",
              "model.layers.0.mlp.shared_expert_gate.weight"):
        p = np.asarray(sd[n], np.float64)
        want_p = reference.adamw_after_step(p, 0 * p, 0 * p, want[n], 1.0,
                                            1e-3, adamw)
        assert rel(np.asarray(new_sd[n]) - p, want_p - p) < 1e-3, n


# reference.py patched in ONE place -> (what to replace, by what)
WRONG = {
    "no-delta-term": ("v_t - jnp.einsum(\"bhkv,bhk->bhv\", S, k_t)", "v_t"),
    "decay-after-update": None,     # two lines swapped: below
    "output-before-update": (
        "return S, jnp.einsum(\"bhkv,bhk->bhv\", S, q_t)",
        "return S, jnp.einsum(\"bhkv,bhk->bhv\", S0, q_t)"),
    "state-dropped-at-segment-start": (
        "return jax.lax.scan(step, S, at)",
        "return jax.lax.scan(step, S * 0.0, at)"),
    "no-l2-norm": ("q, k = _l2(q) * K ** -0.5, _l2(k)",
                   "q, k = q * K ** -0.5, k"),
    "beta-one": ("beta = jax.nn.sigmoid(b)", "beta = jnp.ones_like(b)"),
    "key-heads-tiled": (
        "q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)",
        "q, k = jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1))"),
    "gate-before-norm": (
        "o = _rms(o, w[\"norm.weight\"], c[\"rms_norm_eps\"]) * "
        "jax.nn.silu(z)",
        "o = _rms(o * jax.nn.silu(z), w[\"norm.weight\"], "
        "c[\"rms_norm_eps\"])"),
    "head-norm-zero-centred": (
        "o = _rms(o, w[\"norm.weight\"], c[\"rms_norm_eps\"]) * ",
        "o = _norm(o, w[\"norm.weight\"], c[\"rms_norm_eps\"]) * "),
    "stream-norm-scale-w": (
        "u = _norm(h, w[\"input_layernorm.weight\"], c[\"rms_norm_eps\"])",
        "u = _rms(h, w[\"input_layernorm.weight\"], c[\"rms_norm_eps\"])"),
    "gate-a-head": (
        "(ctx * jax.nn.sigmoid(gate))",
        "(ctx * jax.nn.sigmoid(jnp.mean(gate, -1, keepdims=True)))"),
    "all-columns-rotated": (
        "rot = int(hd * c[\"partial_rotary_factor\"])", "rot = hd"),
    "no-qk-norm": (
        "    q = _rope(_norm(q, w[\"q_norm.weight\"], c[\"rms_norm_eps\"]),",
        "    q = _rope(q + 0 * _norm(q, w[\"q_norm.weight\"], 1.0),"),
    "shared-expert-ungated": (
        "return jax.nn.sigmoid(m @ w[\"shared_expert_gate.weight\"].T) * "
        "_swiglu(", "return _swiglu("),
    "picks-not-normalised": ("if c[\"norm_topk_prob\"]:", "if False:"),
}


def wrong_reference(name):
    """reference.py's namespace with ONE thing patched by name."""
    path = os.path.join(ROOT, "benchmark/configs/qwen3-next-80b-a3b",
                        "reference.py")
    text = open(path).read()
    decay = "        S = jnp.exp(g_t)[..., None, None] * S\n"
    if name == "decay-after-update":
        update_end = "[:, :, None, :]\n"
        assert text.count(decay) == 1
        text = text.replace(decay, "").replace(
            update_end + "        return S, jnp.einsum",
            update_end + decay + "        return S, jnp.einsum")
        assert decay in text
    else:
        old, new = WRONG[name]
        assert text.count(old) == 1, name
        text = text.replace(old, new)
        if name == "output-before-update":
            text = text.replace(decay, "        S0 = S\n" + decay)
        if name == "no-qk-norm":
            old_k = ("    k = _rope(_norm(k, w[\"k_norm.weight\"], "
                     "c[\"rms_norm_eps\"]),")
            assert text.count(old_k) == 1
            text = text.replace(
                old_k, "    k = _rope(k + 0 * _norm(k, w[\"k_norm.weight\"], "
                "1.0),")
    wrong = {}
    exec(compile(text, path, "exec"), wrong)
    return wrong


# tier-1 runs the eight the chip read faintest or by the fewest parts (a
# patched reference compiles anew, ~2.5 s); the whole table runs under
# `-m slow` and, at the cell's size, on the chip (PERF.md section 6)
IN_TIER_1 = {"no-delta-term", "state-dropped-at-segment-start",
             "decay-after-update", "key-heads-tiled", "gate-before-norm",
             "head-norm-zero-centred", "gate-a-head", "all-columns-rotated"}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=() if n in IN_TIER_1 else pytest.mark.slow)
    for n in sorted(WRONG)])
def test_a_reference_wrong_on_purpose_is_told_from_the_system(name):
    """The check's table at a toy size: the reference patched in ONE place
    reads away from the system in the residual stream (after the run of the
    kind the patch is in)."""
    wrong = wrong_reference(name)
    cfg = hq.config_from_hf(HF, gdn_chunk=16)
    params = _params(cfg, 1)
    sd = hq.state_dict_from_params(params, cfg)
    tokens, targets = seeded_tokens(HF, 3, B=2, T=40)
    hidden = jitted(hidden_after_runs, cfg)(params, tokens)
    _, terms = wrong["loss_terms"](sd, tokens, targets, HF)
    assert rel(hidden[1], terms["hidden"][3]) > 2e-4, name
    # and the sound reference reads the system's
    _, terms = reference.loss_terms(sd, tokens, targets, HF)
    assert rel(hidden[1], terms["hidden"][3]) < 2e-5


# -- the new fields at their defaults ----------------------------------------------

def test_new_fields_at_their_defaults_leave_the_older_programs():
    """kimi's, laguna's and olmoe's configurations say nothing of the new
    fields, and the functions the fields are read by take their old path:
    the norms' stored weight IS the scale, laguna's gate is a head's and
    repeated, the shared expert has no gate leaf, the aux weights are the
    caller's. (The lowered steps themselves: test_cell_digests.py.)"""
    for loader, name in ((hk, "kimi-linear-48b-a3b"),
                         (hf_laguna, "laguna-xs.2"),
                         (hf_olmoe, "olmoe-1b-7b")):
        with open(os.path.join(ROOT, "benchmark/configs", name,
                               "config.json")) as f:
            cfg = loader.config_from_hf(json.load(f))
        assert cfg.gdn is None and not cfg.norm_offset and not cfg.shared_gate
        assert cfg.router.loss_weights is None and cfg.attn_gate in (False,
                                                                     True)
        shapes = jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
        leaves = {k for b in tfm.run_blocks(cfg, shapes["blocks"]) for k in b}
        assert "wsg" not in leaves and not any(
            k.startswith("gdn_") for k in leaves)
        if cfg.attn_gate:
            assert tfm.run_blocks(cfg, shapes["blocks"])[0]["wg"].shape[
                -1] in (cfg.n_heads, cfg.window.n_heads)
    plain = tfm.TransformerConfig(norm="rmsnorm")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 512))
    scale = jnp.full((512,), 0.5)
    np.testing.assert_array_equal(
        np.asarray(tfm._norm(x, scale, None, plain)),
        np.asarray(tfm._rms_norm(x, scale, plain.ln_eps)))
    offset = dataclasses.replace(plain, norm_offset=True)
    np.testing.assert_allclose(
        np.asarray(tfm._norm(x, scale, None, offset)),
        np.asarray(tfm._rms_norm(x, 1.5 * jnp.ones(512), plain.ln_eps)))
    np.testing.assert_array_equal(np.asarray(tfm.aux_weights(0.01, 2)),
                                  np.asarray(tfm.aux_weights(
                                      0.01, 2, tfm.Router())))
    np.testing.assert_allclose(
        np.asarray(tfm.aux_weights(0.01, 2, tfm.Router(
            loss_weights=(0.001, 0.0)))), [0.001, 0.0])
    # an initial zero-centred norm IS the plain norm at its initial scale
    assert float(jnp.max(jnp.abs(tfm._init_norm_scale(offset, (4,))))) == 0
    assert float(jnp.min(tfm._init_norm_scale(plain, (4,)))) == 1


def test_the_gate_a_column_and_the_gate_a_head():
    o = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4 * 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    wg = jax.random.normal(jax.random.PRNGKey(2), (32, 4 * 16))
    column = tfm._gate_heads(o, x, wg, 16)
    np.testing.assert_allclose(np.asarray(column), np.asarray(
        o * jax.nn.sigmoid(x @ wg)), atol=1e-6)
    head = tfm._gate_heads(o, x, wg[:, :4], 16)
    np.testing.assert_allclose(np.asarray(head), np.asarray(
        o * jnp.repeat(jax.nn.sigmoid(x @ wg[:, :4]), 16, -1)), atol=1e-6)


# -- the shares add up -------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer of 32 experts cut in 4 shares of 8 (the cell's is
    one of 16; a share is a compile here): the routed parts of the 4 (the
    system's `_moe_mlp` told its share, less the gated shared expert every
    member computes alike) and the gated shared expert ONCE sum to the UNCUT
    reference's layer."""
    hf = {**HF, "num_experts": 32, "num_hidden_layers": 1,
          "full_attention_interval": 4}
    whole_cfg = hq.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[0], params["blocks"])
    sd = hq.state_dict_from_params(params, whole_cfg)
    at = "model.layers.0.mlp."
    w = {n[len(at):]: v for n, v in sd.items() if n.startswith(at)}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    rows = m.reshape(-1, 64)
    want, _, _ = reference._experts_math(rows, w, hf, 0)
    shared = reference._shared_math(rows, w)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    ungated = reference._swiglu(rows, *(w[f"shared_expert.{x}_proj.weight"]
                                        for x in ("gate", "up", "down")))
    assert rel(shared, ungated) > 0.1           # the gate IS there
    routed = []
    for first in range(0, 32, 8):
        share = {**hf, "num_experts": 8, "num_routed_experts": 32,
                 "first_expert_held": first}
        cfg = hq.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 8] for k in
                        ("w1", "w3", "w2", "b1", "b2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _, _ = reference._experts_math(rows, w, share, first)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                                   np.asarray(same), atol=2e-6)
        routed.append(out.reshape(-1, 64) - shared)
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(want), atol=5e-6)


# -- scopes ------------------------------------------------------------------------

def test_scopes_of_the_gdn_mixer_in_the_step():
    cfg = hq.config_from_hf(SHARE, gdn_chunk=16)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    scopes = tracing.GDN_SCOPES
    for scope in scopes + (tracing.SCOPE_KDA_SOLVE,):
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
    # the XLA form's solve INSIDE the scan, the one name of the kda mixer's
    # here: the scan's scope is the calling mixer's; no part inside the
    # attention block's scopes
    assert all(f"{tracing.SCOPE_GDN_SCAN}/{tracing.SCOPE_KDA_SOLVE}/" in n
               for n in names if "hetu_kda_" in n)
    assert not [n for n in names if "hetu_gdn_" in n and any(
        f"/{s}/" in n for s in tracing.BLOCK_SCOPES[:3])]
    # the attention layer's gate under the gate's scope, as laguna's
    assert any(f"/{tracing.SCOPE_ATTN_GATE}/" in n for n in names)
    assert any(f"/{tracing.SCOPE_MOE_SHARED}/" in n for n in names)
    assert tracing.GDN_SCOPES == ("hetu_gdn_proj", "hetu_gdn_conv",
                                  "hetu_gdn_gate", "hetu_gdn_scan")
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    old = ((tracing.STEP, tracing.SCOPE_FWD, tracing.SCOPE_OPT,
            tracing.SCOPE_EXIT) + tracing.MOE_SCOPES + tracing.SSM_SCOPES
           + tracing.SCONV_SCOPES + tracing.SSD_SCOPES + tracing.BLOCK_SCOPES
           + tracing.MLA_SCOPES + tracing.KDA_SCOPES
           + (tracing.SCOPE_KDA_SOLVE, tracing.SCOPE_EMBED,
              tracing.SCOPE_HEAD) + sum(tracing.REMAT_CANDIDATES, ()))
    for name in scopes:
        assert f"`{name}`" in doc, name
        for other in old + scopes:
            assert other == name or (name not in other
                                     and other not in name), (name, other)


# -- refusals by name --------------------------------------------------------------

def test_decode_pipeline_and_meshes_refuse_by_name():
    cfg = hq.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(cfg, 16, 0),
            "gdn=GDNConfig(")
    with pytest.raises(NotImplementedError, match="unequal kinds"):
        pipeline._make_stage_fn(cfg, 1)
    one_kind = dataclasses.replace(cfg, layer_types=("gdn",) * 4)
    with pytest.raises(NotImplementedError, match="gdn and kda mixers"):
        pipeline._make_stage_fn(one_kind, 1)
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"][0])
    h = jnp.zeros((1, 16, 64))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    with pytest.raises(NotImplementedError, match="sp or ep > 1"):
        tfm._gdn(h, p, cfg, mesh)
    with pytest.raises(NotImplementedError, match="no attention bias"):
        tfm._gdn(h, p, cfg, None, attn_bias=jnp.zeros((1, 1, 1, 16)))


@pytest.mark.parametrize("change,named", [
    (dict(gdn=None), "a gdn layer takes `gdn` sizes"),
    (dict(post_ln=True), "a gdn layer takes `gdn` sizes"),
    (dict(gdn=tfm.GDNConfig(n_k_heads=2, n_v_heads=4, k_dim=16, v_dim=16,
                            chunk=48)), "a chunk that is a power of two"),
    (dict(gdn=tfm.GDNConfig(n_k_heads=3, n_v_heads=4, k_dim=16, v_dim=16)),
     "whole groups a key head"),
    (dict(attn_gate="row"), "attn_gate True (a head) or 'column'"),
    (dict(rope_dim=7), "the \"attention\" and \"window\" layers' own"),
    (dict(norm="layernorm"), "the zero-centred form is RMSNorm's"),
    (dict(d_ff_shared=0), "the gate of a shared expert")])
def test_config_refuses_by_name(change, named):
    cfg = hq.config_from_hf(HF)
    refuses(lambda: dataclasses.replace(cfg, **change), named, ValueError)
