"""OLMoE through the flagship trunk (ISSUE 25): dropless top-k routing over
SwiGLU experts and QK-norm, against the plain float32 reference the
benchmark ships (benchmark/configs/olmoe-1b-7b/reference.py, imported by
path: one copy), on seeded weights that enter through
``hf_olmoe.params_from_state_dict`` under their HuggingFace names.

Tolerance 1e-4 (relative to the largest entry of what is compared) in
float32 on the CPU: system and reference do the same arithmetic in another
order: a grouped matmul over sorted picks against 8 dense masked experts, a
fused q|k|v projection against three, a sum over k picks against a sum over
E experts. Nothing else differs; float32 rounding of sums of ~64 terms is
~1e-6, so 1e-4 leaves room for the softmax and the norms and none for a
dropped pick, normalised top-k weights or a missing loss term (each moves
the result by more than 1e-2).
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import hf_olmoe, transformer as tfm
from hetu_tpu.parallel import mesh as meshlib
from hetu_tpu.telemetry import tracing
from model_harness import load_reference, refuses

TOL = 1e-4
HF = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
      "hidden_size": 64, "intermediate_size": 32,
      "max_position_embeddings": 32, "model_type": "olmoe",
      "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
      "num_experts_per_tok": 2, "num_hidden_layers": 2,
      "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "rope_scaling": None,
      "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 96,
      "assumed": {"router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}}


@pytest.fixture(scope="module")
def reference():
    return load_reference("olmoe-1b-7b")


def _state_dict(hf, seed, skew=False):
    """Seeded HF-named weights; norm scales away from 1 so that a scale
    applied in the wrong place shows. ``skew``: every embedding shares a
    constant component, which survives the norms, and the routers push
    expert 0 down and expert 1 up along it: nobody picks expert 0, nearly
    everybody expert 1."""
    rng = np.random.RandomState(seed)
    D, F, E = hf["hidden_size"], hf["intermediate_size"], hf["num_experts"]
    V = hf["vocab_size"]
    normal = lambda *shape: (rng.randn(*shape) * 0.3 / np.sqrt(
        shape[-1])).astype(np.float32)
    scale = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)
    sd = {"model.embed_tokens.weight": normal(V, D) * 4 + (0.3 if skew else 0),
          "model.norm.weight": scale(D), "lm_head.weight": normal(V, D)}
    for i in range(hf["num_hidden_layers"]):
        p = f"model.layers.{i}."
        for proj in "qkvo":
            sd[p + f"self_attn.{proj}_proj.weight"] = normal(D, D)
        sd[p + "self_attn.q_norm.weight"] = scale(D)
        sd[p + "self_attn.k_norm.weight"] = scale(D)
        sd[p + "input_layernorm.weight"] = scale(D)
        sd[p + "post_attention_layernorm.weight"] = scale(D)
        router = normal(E, D) * 4
        if skew:
            router[0] -= 20.0 / D
            router[1] += 20.0 / D
        sd[p + "mlp.gate.weight"] = router
        for e in range(E):
            q = p + f"mlp.experts.{e}."
            sd[q + "gate_proj.weight"] = normal(F, D) * 2
            sd[q + "up_proj.weight"] = normal(F, D) * 2
            sd[q + "down_proj.weight"] = normal(D, F) * 2
    return sd


def _data(hf, seed, B=2):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, hf["vocab_size"],
                         (B, hf["max_position_embeddings"])).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(np.roll(tokens, -1, 1))


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert err <= TOL, f"{what}: relative error {err:.2e}"


def _system_terms(params, tokens, targets, cfg):
    logits, aux = tfm.forward(params, tokens, cfg)
    return {"ce": tfm.nll_loss(logits, targets), "balance": aux[0],
            "z": aux[1], "logits": logits,
            "loss": tfm.loss_fn(params, tokens, targets, cfg)}


def _flat(tree, prefix=""):
    for k, v in sorted(tree.items()):
        if isinstance(v, dict):
            yield from _flat(v, prefix + k + ".")
        else:
            yield prefix + k, v


CASES = {"seeded": (HF, False),
         # 16 experts: with k = 2 of 8 no expert can pass 4x the mean load
         "skewed": ({**HF, "num_experts": 16}, True),
         "top1": ({**HF, "num_experts_per_tok": 1}, False)}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request, reference):
    """System and reference on one case: terms, gradients in the system's
    layout, routing statistics."""
    hf, skew = CASES[request.param]
    cfg = hf_olmoe.config_from_hf(hf, remat=False)
    sd = _state_dict(hf, seed=3, skew=skew)
    params = jax.tree.map(jnp.asarray,
                          hf_olmoe.params_from_state_dict(sd, cfg))
    tokens, targets = _data(hf, seed=4)
    got = jax.jit(lambda p: _system_terms(p, tokens, targets, cfg))(params)
    grads = jax.jit(jax.grad(
        lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    sd = {n: jnp.asarray(v) for n, v in sd.items()}
    want_loss, want = jax.jit(
        lambda sd: reference.loss_and_hidden(sd, tokens, targets, hf))(sd)
    want_grads = hf_olmoe.params_from_state_dict(jax.jit(
        lambda sd: reference.grads_of(sorted(sd))(sd, tokens, targets, hf))(
            sd), cfg, xp=jnp)
    stats = jax.jit(lambda p: tfm.moe_routing_stats(p, tokens, cfg))(params)
    return {"name": request.param, "hf": hf, "cfg": cfg, "got": got,
            "want": {**want, "loss": want_loss}, "grads": grads,
            "want_grads": want_grads, "stats": stats, "tokens": tokens}


@pytest.mark.parametrize("term", ["logits", "ce", "balance", "z", "loss"])
def test_forward_and_loss_terms_match_reference(case, term):
    _close(case["got"][term], case["want"][term], f"{case['name']}: {term}")


def test_every_gradient_matches_reference(case):
    got, want = dict(_flat(case["grads"])), dict(_flat(case["want_grads"]))
    # the dialect's dead parameters (rmsnorm biases, swiglu b1/b2) have no
    # HF name: the system's gradient of them is exactly zero
    dead = {"blocks.ln1_bias", "blocks.ln2_bias", "blocks.b1", "blocks.b2",
            "lnf_bias"}
    assert set(got) == set(want)
    for name in sorted(got):
        if name in dead:
            assert not np.any(np.asarray(got[name])), name
        else:
            assert np.any(np.asarray(want[name])), name
            _close(got[name], want[name], f"{case['name']}: d {name}")


def test_routing_is_dropless_and_counted(case):
    st, hf = case["stats"], case["hf"]
    S = int(np.prod(case["tokens"].shape))
    k, E = hf["num_experts_per_tok"], hf["num_experts"]
    picks = np.asarray(st["picks"])
    assert picks.shape == (hf["num_hidden_layers"], E)
    assert (picks.sum(1) == S * k).all()
    assert not np.asarray(st["dropped"]).any()
    np.testing.assert_allclose(np.asarray(st["max_over_mean"]),
                               picks.max(1) * E / (S * k), rtol=1e-6)
    assert (np.asarray(st["entropy"]) > 0).all()
    assert (np.asarray(st["entropy"]) <= np.log(E) + 1e-6).all()
    # the system and the reference send every token to the same experts
    assert np.array_equal(np.sort(np.asarray(st["experts"]), -1),
                          np.sort(np.asarray(case["want"]["experts"]), -1))
    if case["name"] == "skewed":
        # one expert gets no token and one more than 4x the mean: a
        # capacity of the mean load would drop three quarters of its picks
        assert (picks[:, 0] == 0).all(), picks
        assert (picks[:, 1] > 4 * S * k / E).all(), picks


def test_top1_matches_the_switch_path_at_a_capacity_that_drops_nothing():
    """k = 1 GELU experts with biases: the dropless path against the older
    capacity path (kept for ep > 1 meshes), run here on a one-device mesh
    shape, at a capacity factor of E: no expert can overflow."""
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=48,
        max_seq_len=16, n_experts=4, capacity_factor=4.0, dtype=jnp.float32,
        remat=False)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    blocks = dict(params["blocks"])
    blocks["b1"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                           blocks["b1"].shape)
    blocks["b2"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                           blocks["b2"].shape)
    layer = {n: v[0] for n, v in blocks.items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32))

    def both(layer, h):
        return (tfm._moe_mlp(h, layer, cfg, None),
                tfm._moe_mlp_capacity(h, layer, cfg, None))

    (out, aux), (want, want_aux) = jax.jit(both)(layer, h)
    _close(out, want, "k=1 output")
    _close(aux, want_aux, "k=1 aux")
    g = jax.jit(jax.grad(lambda l: jnp.sum(tfm._moe_mlp(h, l, cfg, None)[0]
                                           ** 2)))(layer)
    gw = jax.jit(jax.grad(lambda l: jnp.sum(
        tfm._moe_mlp_capacity(h, l, cfg, None)[0] ** 2)))(layer)
    for name in ("router", "w1", "b1", "w2", "b2"):
        _close(g[name], gw[name], f"k=1 d {name}")


def test_topk_on_an_expert_parallel_mesh_is_refused_by_name():
    cfg = hf_olmoe.config_from_hf(HF)
    mesh = meshlib.make_mesh(dp=2, pp=1, tp=1, sp=1, ep=4)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(HF, 0, B=4)
    with pytest.raises(tfm.MoEConfigError, match="n_experts_per_tok=2"):
        tfm.loss_fn(params, tokens, targets, cfg, mesh)
    with pytest.raises(tfm.MoEConfigError):
        tfm.TransformerConfig(n_experts=4, n_experts_per_tok=5)


def test_moe_scopes_are_in_the_compiled_program():
    """The four names reduce/moe.py reads, in the `op_name` paths of the
    compiled HLO (what a device trace carries): forward, recomputed and
    backward ops of every part of the block."""
    cfg = hf_olmoe.config_from_hf(HF)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(HF, 0)
    step = tfm.make_train_step(cfg)
    text = step.lower(params, tfm.init_opt_state(params), tokens,
                      targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in tracing.MOE_SCOPES:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   and "rematted_computation" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   and "rematted_computation" not in n
                   for n in under), scope
    assert not [n for n in names if tracing.SCOPE_OPT in n
                and "hetu_moe" in n]


def test_olmoe_trains_and_decode_refuses_it():
    cfg = hf_olmoe.config_from_hf(HF)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = tfm.init_opt_state(params)
    tokens, targets = _data(HF, 0)
    step = tfm.make_train_step(cfg, lr=1e-2)
    losses = []
    for _ in range(6):
        loss, params, opt = step(params, opt, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    from hetu_tpu.models import generate
    refuses(lambda: generate._check_decode_args(cfg, 8, 0), "n_experts=8")
    import dataclasses
    dense = dataclasses.replace(cfg, n_experts=0, n_experts_per_tok=1)
    refuses(lambda: generate._check_decode_args(dense, 8, 0), "qk_norm=True")


def test_state_dict_round_trip_and_refusals():
    cfg = hf_olmoe.config_from_hf(HF)
    sd = _state_dict(HF, seed=5)
    params = hf_olmoe.params_from_state_dict(sd, cfg)
    back = hf_olmoe.state_dict_from_params(params, cfg)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)
    unscoped = {n[len("model."):] if n.startswith("model.") else n: v
                for n, v in sd.items()}
    again = hf_olmoe.params_from_state_dict(unscoped, cfg)
    np.testing.assert_array_equal(again["blocks"]["w3"],
                                  params["blocks"]["w3"])
    for key, value in (("norm_topk_prob", True), ("clip_qkv", 8.0),
                       ("attention_bias", True), ("hidden_act", "gelu")):
        with pytest.raises(NotImplementedError, match=key):
            hf_olmoe.config_from_hf({**HF, key: value})
