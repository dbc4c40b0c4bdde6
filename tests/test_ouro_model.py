"""Ouro through the flagship trunk (ISSUE 29): a looped decoder (one set of
block weights applied ``total_ut_steps`` times), sandwich norms, the exit
gate and the expected-exit loss, against the plain float32 reference the
benchmark ships (benchmark/configs/ouro-2.6b/reference.py, imported by path:
one copy), on seeded weights that enter through
``hf_ouro.params_from_state_dict`` under their HuggingFace names.

Tolerance 1e-4 (relative to the largest entry of what is compared) in
float32 on the CPU: system and reference do the same arithmetic in another
order: a fused q|k|v projection against three, q in log space against
products, a scan over passes and layers against Python loops. float32
rounding of sums of ~64 terms is ~1e-6, so 1e-4 leaves room for the softmax
and the norms and none for a norm in the wrong place, a gate read at the
last exit, a missing entropy term or a weight copy that loses a pass's
gradient (each moves the result by more than 1e-2).
"""
import dataclasses
import hashlib
import json
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import bert, generate, hf_olmoe, hf_ouro
from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib, pipeline
from hetu_tpu.telemetry import tracing
from model_harness import ROOT, load_reference, refuses

from test_olmoe_model import HF as OLMOE_HF

TOL = 1e-4
CONFIG_JSON = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b",
                           "config.json")
# 2 layers x 3 loops
HF = {"head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
      "intermediate_size": 96, "layer_types": ["full_attention"] * 2,
      "max_position_embeddings": 32, "max_window_layers": 2,
      "model_type": "ouro", "num_attention_heads": 4,
      "num_hidden_layers": 2, "num_key_value_heads": 4,
      "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
      "sliding_window": None, "tie_word_embeddings": False,
      "total_ut_steps": 3, "early_exit_threshold": 1,
      "use_sliding_window": False, "vocab_size": 96,
      "assumed": {"exit_entropy_weight": 0.05}}


@pytest.fixture(scope="module")
def reference():
    return load_reference("ouro-2.6b")


def _state_dict(hf, seed):
    """Seeded HF-named weights; norm scales away from 1 so that a scale
    applied in the wrong place shows, a gate strong enough that q is far
    from both uniform and one-hot."""
    rng = np.random.RandomState(seed)
    D, F, V = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    normal = lambda *shape: (rng.randn(*shape) * 0.3 / np.sqrt(
        shape[-1])).astype(np.float32)
    scale = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)
    sd = {"model.embed_tokens.weight": normal(V, D) * 4,
          "model.norm.weight": scale(D), "lm_head.weight": normal(V, D),
          hf_ouro.GATE_W: normal(1, D) * 3,
          hf_ouro.GATE_B: np.array([-0.4], np.float32)}
    for i in range(hf["num_hidden_layers"]):
        for proj in "qkvo":
            sd[hf_ouro.hf_name(i, f"self_attn.{proj}_proj")] = normal(D, D)
        for norm in hf_ouro.NORMS.values():
            sd[hf_ouro.hf_name(i, norm)] = scale(D)
        sd[hf_ouro.hf_name(i, "mlp.gate_proj")] = normal(F, D) * 2
        sd[hf_ouro.hf_name(i, "mlp.up_proj")] = normal(F, D) * 2
        sd[hf_ouro.hf_name(i, "mlp.down_proj")] = normal(D, F) * 2
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


def _flat(tree, prefix=""):
    for k, v in sorted(tree.items()):
        if isinstance(v, dict):
            yield from _flat(v, prefix + k + ".")
        else:
            yield prefix + k, v


@pytest.fixture(scope="module")
def case(reference):
    """System and reference at 2 layers x 3 loops: loss terms, gradients in
    the system's layout."""
    cfg = hf_ouro.config_from_hf(HF, remat=False)
    sd = _state_dict(HF, seed=3)
    params = jax.tree.map(jnp.asarray,
                          hf_ouro.params_from_state_dict(sd, cfg))
    tokens, targets = _data(HF, seed=4)
    loss, got = jax.jit(
        lambda p: tfm.exit_loss_terms(p, tokens, targets, cfg))(params)
    grads = jax.jit(jax.grad(
        lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    sd = {n: jnp.asarray(v) for n, v in sd.items()}
    want_loss, want = reference.loss_terms(sd, tokens, targets, HF)
    want_sd_grads = reference.grads_of(sorted(sd))(sd, tokens, targets, HF)
    return {"cfg": cfg, "params": params, "sd": sd, "tokens": tokens,
            "targets": targets, "got": {**got, "loss": loss},
            "want": {**want, "loss": want_loss}, "grads": grads,
            "want_sd_grads": want_sd_grads,
            "want_grads": hf_ouro.params_from_state_dict(want_sd_grads, cfg,
                                                         xp=jnp)}


@pytest.mark.parametrize("term", ["loss", "nll", "q", "exits"])
def test_loss_terms_match_reference(case, term):
    assert term == "loss" or case["got"][term].shape[0] == 3
    _close(case["got"][term], case["want"][term], term)


def test_exit_distribution_sums_to_one_and_the_last_exit_takes_the_rest(case):
    q = np.asarray(case["got"]["q"])
    np.testing.assert_allclose(q.sum(0), 1.0, atol=1e-6)
    # what the gate's stop probabilities leave after the first two exits
    exits = case["got"]["exits"].astype(jnp.float32)
    stop = np.asarray(jax.nn.sigmoid(
        exits @ case["params"]["exit_gate_w"] + case["params"]["exit_gate_b"]))
    np.testing.assert_allclose(q[0], stop[0], rtol=1e-5)
    np.testing.assert_allclose(q[1], stop[1] * (1 - stop[0]), rtol=1e-5)
    np.testing.assert_allclose(q[2], (1 - stop[0]) * (1 - stop[1]), rtol=1e-5)
    # neither uniform nor one-hot: the case exercises the weighting
    assert 0.05 < q.mean((1, 2)).min() and q.mean((1, 2)).max() < 0.8
    # the last exit's own gate output is not read: no gradient through it
    stats = jax.jit(lambda p: tfm.exit_stats(p, case["tokens"],
                                             case["cfg"]))(case["params"])
    np.testing.assert_allclose(stats["q_mean"], q.mean((1, 2)), rtol=1e-5)
    np.testing.assert_allclose(
        stats["expected_exit_step"],
        (q.mean((1, 2)) * np.arange(1, 4)).sum(), rtol=1e-5)


def test_every_gradient_matches_reference(case):
    got, want = dict(_flat(case["grads"])), dict(_flat(case["want_grads"]))
    # the dialect's dead parameters (rmsnorm biases, swiglu b1/b2) have no
    # HF name: the system's gradient of them is exactly zero
    dead = {"lnf_bias", "blocks.b1", "blocks.b2"} | {
        f"blocks.{n}_bias" for n in ("ln1", "ln1_post", "ln2", "ln2_post")}
    assert set(got) == set(want)
    for name in sorted(got):
        if name in dead:
            assert not np.any(np.asarray(got[name])), name
        else:
            assert np.any(np.asarray(want[name])), name
            _close(got[name], want[name], f"d {name}")


def test_reference_gradients_are_jax_grad_of_its_plain_forward(case,
                                                               reference):
    """`grads_of` runs each block again in the backward pass; the plain
    forward keeps everything: the same gradients."""
    sd, names = case["sd"], sorted(case["sd"])
    plain = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, case["tokens"], case["targets"], HF)[0])(
            {n: sd[n] for n in names})
    for n in names:
        _close(case["want_sd_grads"][n], plain[n], n)


def test_a_block_weight_gradient_is_the_sum_over_passes_of_the_untied_model(
        case):
    """The untied twin: three copies of the two layers, one a pass, through
    the same block, norm and exit loss. Tied, the gradient of a weight is
    the sum of its copies' gradients, pass by pass: no pass is lost, none
    counted twice."""
    cfg, params = case["cfg"], case["params"]
    once = dataclasses.replace(cfg, n_loops=1)
    tokens, targets = case["tokens"], case["targets"]

    def untied_loss(copies):
        h = tfm.embed_tokens(params, tokens, cfg)
        exits = []
        for blocks in copies:
            h, _ = tfm.encode({**params, "blocks": blocks}, h, once)
            h = tfm._norm(h, params["lnf_scale"], params["lnf_bias"], cfg)
            exits.append(h)
        return tfm._exit_loss(params, jnp.stack(exits),
                              jnp.zeros((2,), jnp.float32), targets, cfg,
                              None, 0.01)[0]

    loss, by_pass = jax.jit(jax.value_and_grad(untied_loss))(
        [params["blocks"]] * 3)
    _close(loss, case["got"]["loss"], "untied loss")
    for name in ("wqkv", "wo", "w1", "w2", "w3", "ln1_post_scale",
                 "ln2_scale"):
        passes = [np.asarray(g[name]) for g in by_pass]
        # every pass contributes, and differently
        assert all(np.abs(p).max() > 0 for p in passes), name
        assert np.abs(passes[0] - passes[2]).max() > 1e-3 * np.abs(
            passes[0]).max(), name
        _close(case["grads"]["blocks"][name], sum(passes), f"sum d {name}")


# the lowered text of value_and_grad(loss) at the parent commit (251f49b,
# jax 0.9.0), made by this very function there: a model with one exit and no
# sandwich norm runs the program it ran before the loop existed.
# "fused-small" was made again in PR 30, which changed the fused CE's
# kernels on purpose (before: a5899be7c4b2d5d0, 2883 lines). PR 36 made all
# three again AT ITS PARENT (5438e58) with the symbols' counters cut off:
# its three names in `_split_heads` renumber the private functions and move
# nothing else (with the counters: 8a382cdc3978a109, a29bf60f74c5cd33,
# d9ea68b963874d0f, the same lines). "bert-small" was made again in PR 62,
# which changed `_gelu`'s exact form on purpose (before: 617e8252fb383233,
# 1237 lines), and once more in PR 65, which gave that form its own
# derivative rule ending in a barrier (before: 0cf4bea2345f0a83, 1241 lines).
GOLDEN = {"bert-small": ("5836e6632ef34fb3", 1253),
          "olmoe-small": ("ce3bd8fa8df83045", 1733),
          "fused-small": ("b3d89febeac34c1c", 2902)}


def _lowered_digest(which):
    if which == "bert-small":
        # the HF dialect the benchmark's adapter builds: post-LN, biases
        cfg = bert.BertConfig.hf(vocab_size=512, d_model=64, n_layers=2,
                                 n_heads=4, d_ff=128, max_seq_len=64,
                                 dtype=jnp.float32)
        params = jax.eval_shape(
            lambda: bert.init_params(jax.random.PRNGKey(0), cfg))
        B, T, P = 4, 32, 5
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        batch = {"input_ids": i32(B, T), "segment_ids": i32(B, T),
                 "input_mask": f32(B, T), "mlm_positions": i32(B, P),
                 "mlm_ids": i32(B, P), "mlm_weights": f32(B, P),
                 "nsp_label": i32(B)}
        fn = jax.value_and_grad(
            lambda p, b: bert.pretrain_loss(p, b, cfg)[0])
        text = jax.jit(fn).lower(params, batch).as_text()
    else:
        # the fused cross-entropy path of `loss_fn`, as the TPU takes it
        cfg = (hf_olmoe.config_from_hf(OLMOE_HF) if which == "olmoe-small"
               else tfm.TransformerConfig(
                   vocab_size=512, d_model=128, n_heads=2, n_layers=2,
                   d_ff=256, max_seq_len=32, fused_lm_ce=True))
        params = jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
        tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        fn = jax.value_and_grad(
            lambda p, x, y: tfm.loss_fn(p, x, y, cfg))
        text = jax.jit(fn).lower(params, tok, tok).as_text()
    # a private function's symbol ends in a counter that every traced
    # equation moves, a `checkpoint_name` too, which lowers to nothing
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], text.count("\n")


@pytest.mark.parametrize("which", sorted(GOLDEN))
def test_one_loop_lowers_to_the_program_it_was(which):
    assert _lowered_digest(which) == GOLDEN[which]


def test_ouro_scope_and_one_weight_copy_in_the_compiled_program():
    """`hetu_exit` in the `op_name` paths of the compiled HLO, forward and
    backward (what reduce/loop.py reads); the passes are a loop in the
    program, not four copies of the stack."""
    cfg = hf_ouro.config_from_hf(HF)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(HF, 0)
    step = tfm.make_train_step(cfg)
    text = step.lower(params, tfm.init_opt_state(params), tokens,
                      targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    under = [n for n in names if f"/{tracing.SCOPE_EXIT}/" in n]
    assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under)
    assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n for n in under)
    assert not [n for n in names if tracing.SCOPE_OPT in n
                and tracing.SCOPE_EXIT in n]
    # the trunk is outside the exit scope
    assert not [n for n in under if "while" in n]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    # the 3 passes are one scan forward and one reversed, each around ONE
    # scan over the 2 layers: the block is traced once a direction, and the
    # reversed pass scan carries the running sum of the weights' gradients
    found = []
    _scans(jaxpr.jaxpr, 0, found)
    passes = [f for f in found if f[1] == 3]
    assert [(depth, reverse) for depth, _n, reverse, _c in passes] == [
        (0, False), (0, True)], found
    assert sorted((depth, reverse) for depth, n, reverse, _c in found
                  if n == 2 and depth == 1) == [(1, False), (1, True)], found
    wqkv = params["blocks"]["wqkv"].shape
    assert passes[1][3].count(wqkv) == 1, passes[1][3]


def _scans(jaxpr, depth, found):
    """(depth, length, reverse, carry shapes) of every scan, nested ones
    after their parent."""
    for e in jaxpr.eqns:
        inner = depth
        if e.primitive.name == "scan":
            nc, nk = e.params["num_consts"], e.params["num_carry"]
            found.append((depth, e.params["length"], e.params["reverse"],
                          [v.aval.shape for v in e.invars[nc:nc + nk]]))
            inner = depth + 1
        for v in e.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    _scans(sub, inner, found)


def test_ouro_trains_and_decode_and_pipeline_refuse_it():
    cfg = hf_ouro.config_from_hf(HF)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert params["exit_gate_w"].shape == (64,)
    assert "exit_gate_w" not in tfm.init_params(
        jax.random.PRNGKey(0), dataclasses.replace(cfg, n_loops=1))
    opt = tfm.init_opt_state(params)
    tokens, targets = _data(HF, 0)
    step = tfm.make_train_step(cfg, lr=1e-2)
    losses = []
    for _ in range(6):
        loss, params, opt = step(params, opt, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    logits, _ = jax.jit(lambda p: tfm.forward(p, tokens, cfg))(params)
    assert logits.shape == (3, 2, 32, 96)


def test_decode_refuses_a_looped_model_by_name():
    cfg = hf_ouro.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(cfg, 8, 0), "n_loops=3")
    refuses(lambda: generate._check_decode_args(
        dataclasses.replace(cfg, n_loops=1), 8, 0), "sandwich_norm=True")


def test_pipeline_refuses_a_looped_model_by_name():
    cfg = hf_ouro.config_from_hf(HF)
    mesh = meshlib.make_mesh(dp=1, pp=2, tp=1, sp=1, ep=1,
                             devices=jax.devices()[:2])
    for build in (pipeline.make_pipeline_train_step,
                  pipeline.make_pipeline_train_step_1f1b):
        with pytest.raises(NotImplementedError, match="n_loops=3"):
            build(cfg, mesh, 2)
    with pytest.raises(ValueError, match="pre-LN"):
        tfm.TransformerConfig(post_ln=True, n_loops=2)
    with pytest.raises(ValueError, match="n_loops=0"):
        tfm.TransformerConfig(n_loops=0)


def test_published_config_and_state_dict_round_trip():
    with open(CONFIG_JSON) as f:
        published = json.load(f)
    cfg = hf_ouro.config_from_hf(published)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size,
            cfg.n_loops, cfg.n_layers) == (2048, 16, 128, 5632, 49152, 4, 6)
    assert cfg.sandwich_norm and cfg.rope and cfg.norm == "rmsnorm"
    assert (cfg.rope_theta, cfg.ln_eps, cfg.tied_head) == (1e6, 1e-6, False)
    assert not cfg.n_kv_heads and cfg.mlp == "swiglu" and cfg.causal
    assert list(published["reduced"]) == ["num_hidden_layers"]
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert 509.6e6 < tfm.count_params(shapes) < 509.9e6

    small = hf_ouro.config_from_hf(HF)
    sd = _state_dict(HF, seed=5)
    params = hf_ouro.params_from_state_dict(sd, small)
    back = hf_ouro.state_dict_from_params(params, small)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)
    unscoped = {n[len("model."):] if n.startswith("model.") else n: v
                for n, v in sd.items()}
    again = hf_ouro.params_from_hf(unscoped, small)
    np.testing.assert_array_equal(again["blocks"]["w3"],
                                  params["blocks"]["w3"])
    assert jax.tree.structure(again) == jax.tree.structure(
        tfm.init_params(jax.random.PRNGKey(0), small))
    for key, value in (("use_sliding_window", True), ("hidden_act", "gelu"),
                       ("rope_scaling", {"type": "linear"}),
                       ("head_dim", 32),
                       ("layer_types", ["sliding_attention"] * 2)):
        with pytest.raises(NotImplementedError, match=key):
            hf_ouro.config_from_hf({**HF, key: value})
