"""HuggingFace Llama numerical parity (models/hf_llama.py): RoPE, RMSNorm,
SwiGLU, GQA — random-weight transformers Llama (no network), import,
compare logits / KV-cache decode / whole-loop generation, round-trip
export, refusals. Same pinning pattern as the BERT/GPT-2/ViT suites."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from hetu_tpu.models import generate as gen
from hetu_tpu.models import transformer as tfm
from hetu_tpu.models.hf_llama import (config_from_hf, export_to_hf,
                                      params_from_hf)


def small_hf_config(**over):
    kw = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=2,  # GQA
              intermediate_size=112, max_position_embeddings=64,
              rms_norm_eps=1e-6, rope_theta=10000.0,
              tie_word_embeddings=False)
    kw.update(over)
    return transformers.LlamaConfig(**kw)


@pytest.fixture(scope="module")
def llama_pair():
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(small_hf_config()).eval()
    params, cfg = params_from_hf(model)
    import dataclasses
    cfg = dataclasses.replace(cfg, remat=False, attn_impl="dot",
                              fused_lm_ce=False)
    return model, params, cfg


def hf_logits(model, ids):
    with torch.no_grad():
        return model(input_ids=torch.tensor(ids)).logits.numpy()


def test_logits_match_hf(llama_pair):
    model, params, cfg = llama_pair
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (3, 20))
    ours, _ = tfm.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(ours), hf_logits(model, ids),
                               atol=3e-4, rtol=3e-4)


def test_kv_cache_decode_matches_hf(llama_pair):
    """RoPE through the cache: teacher-forced incremental logits equal the
    torch full forward (rotated keys cached at absolute positions)."""
    model, params, cfg = llama_pair
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (2, 14))
    fn = gen.make_generate_fn(cfg, max_len=14)
    toks, inc_logits = fn(params, jnp.asarray(ids, jnp.int32),
                          jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(toks), ids)
    np.testing.assert_allclose(np.asarray(inc_logits),
                               hf_logits(model, ids), atol=3e-4, rtol=3e-4)


def test_greedy_generation_matches_hf_generate(llama_pair):
    model, params, cfg = llama_pair
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    ours = gen.generate(params, cfg, prompt, max_len=16)
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(prompt, dtype=torch.long),
            attention_mask=torch.ones((2, 6), dtype=torch.long),
            max_new_tokens=10, do_sample=False, pad_token_id=0)
    np.testing.assert_array_equal(np.asarray(ours), ref.numpy())


def test_speculative_decode_runs_on_llama(llama_pair):
    """The imported Llama rides speculative decoding unchanged (self-draft
    -> full acceptance -> exact greedy)."""
    model, params, cfg = llama_pair
    prompt = np.random.default_rng(4).integers(
        0, cfg.vocab_size, (1, 5)).astype(np.int32)
    plain = gen.generate(params, cfg, prompt, max_len=20)
    fn = gen.make_speculative_generate_fn(cfg, cfg, 20, k=3)
    spec, rounds = fn(params, params, jnp.asarray(prompt))
    np.testing.assert_array_equal(np.asarray(spec), plain)
    assert int(rounds) == -(-(20 - 5 - 1) // 4)


def test_imported_llama_trains_a_step(llama_pair):
    model, params, cfg = llama_pair
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 17)), jnp.int32)
    step = tfm.make_train_step(cfg, lr=1e-3)
    p2 = jax.tree.map(jnp.array, params)
    opt = tfm.init_opt_state(p2)
    l1, p2, opt = step(p2, opt, toks[:, :-1], toks[:, 1:])
    l2, p2, opt = step(p2, opt, toks[:, :-1], toks[:, 1:])
    assert float(l2) < float(l1)


def test_train_then_export_roundtrip(llama_pair):
    model, params, cfg = llama_pair
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 17)), jnp.int32)
    step = tfm.make_train_step(cfg, lr=1e-3)
    trained = jax.tree.map(jnp.array, params)
    _, trained, _ = step(trained, tfm.init_opt_state(trained),
                         toks[:, :-1], toks[:, 1:])
    fresh = transformers.LlamaForCausalLM(model.config).eval()
    export_to_hf(trained, cfg, fresh)
    ids = rng.integers(0, cfg.vocab_size, (3, 12))
    ours, _ = tfm.forward(trained, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(ours), hf_logits(fresh, ids),
                               atol=3e-4, rtol=3e-4)


def test_mha_variant_and_tied_head():
    """num_key_value_heads == num_attention_heads (plain MHA) and
    tie_word_embeddings=True both import and match."""
    torch.manual_seed(7)
    model = transformers.LlamaForCausalLM(small_hf_config(
        num_key_value_heads=4, tie_word_embeddings=True)).eval()
    params, cfg = params_from_hf(model)
    assert cfg.tied_head and cfg.n_kv_heads == 0 and "head" not in params
    import dataclasses
    cfg = dataclasses.replace(cfg, remat=False, attn_impl="dot",
                              fused_lm_ce=False)
    ids = np.random.default_rng(8).integers(0, cfg.vocab_size, (2, 10))
    ours, _ = tfm.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(ours), hf_logits(model, ids),
                               atol=3e-4, rtol=3e-4)


def test_mesh_forward_matches_hf(llama_pair):
    """The imported GQA Llama sharded dp2/tp2 on the virtual mesh equals
    the torch forward (kv heads split 2-over-tp2, rope under GSPMD)."""
    model, params, cfg = llama_pair
    from hetu_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    sharded = tfm.shard_params(params, cfg, mesh)
    rng = np.random.default_rng(9)
    ids = rng.integers(0, cfg.vocab_size, (4, 12))
    ours, _ = jax.jit(lambda p, t: tfm.forward(p, t, cfg, mesh))(
        sharded, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits(model, ids),
                               atol=3e-4, rtol=3e-4)


def test_windowless_mistral_imports(llama_pair):
    """Mistral shares the Llama layout; a windowless config imports and
    matches the torch forward (the windowed default refuses instead)."""
    import dataclasses
    torch.manual_seed(10)
    model = transformers.MistralForCausalLM(transformers.MistralConfig(
        vocab_size=96, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=112, max_position_embeddings=64,
        rms_norm_eps=1e-6, sliding_window=None)).eval()
    params, cfg = params_from_hf(model)
    cfg = dataclasses.replace(cfg, remat=False, attn_impl="dot",
                              fused_lm_ce=False)
    ids = np.random.default_rng(11).integers(0, 96, (2, 12))
    ours, _ = tfm.forward(params, jnp.asarray(ids, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(ours), hf_logits(model, ids),
                               atol=3e-4, rtol=3e-4)


def test_import_refuses_mismatched_config(llama_pair):
    model, _, _ = llama_pair
    truncated = config_from_hf(model.config, n_layers=2)
    with pytest.raises(ValueError, match="n_layers"):
        params_from_hf(model, truncated)


def test_import_refuses_attention_bias():
    cfg = small_hf_config(attention_bias=True)
    model = transformers.LlamaForCausalLM(cfg).eval()
    with pytest.raises(NotImplementedError, match="attention_bias"):
        params_from_hf(model)


def test_import_refuses_sliding_window_and_odd_head_dim():
    class FakeCfg:
        # minimal duck-typed config: a Mistral-style windowed variant
        vocab_size = 96; hidden_size = 64; num_attention_heads = 4
        num_key_value_heads = 2; num_hidden_layers = 2
        intermediate_size = 112; max_position_embeddings = 64
        rms_norm_eps = 1e-6; rope_theta = 10000.0
        tie_word_embeddings = False; hidden_act = "silu"
        attention_bias = False; rope_scaling = None
        sliding_window = 4096; head_dim = None
    with pytest.raises(NotImplementedError, match="sliding_window"):
        config_from_hf(FakeCfg())
    FakeCfg.sliding_window = None
    FakeCfg.head_dim = 32     # != hidden_size / num_heads
    with pytest.raises(NotImplementedError, match="head_dim"):
        config_from_hf(FakeCfg())


def test_swiglu_experts_compute_swiglu():
    """mlp='swiglu' with experts (refused until ISSUE 25): every expert is
    the dialect's down(silu(gate x) * up x). With E copies of one dense
    SwiGLU MLP as the experts, a token's output is that MLP's output times
    the router probabilities of its picks."""
    import jax
    import jax.numpy as jnp
    cfg = tfm.TransformerConfig(d_model=32, n_heads=4, d_ff=48, mlp="swiglu",
                                n_experts=4, n_experts_per_tok=2,
                                dtype=jnp.float32)
    dense_cfg = tfm.TransformerConfig(d_model=32, n_heads=4, d_ff=48,
                                      mlp="swiglu", dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    dense = {"w1": jax.random.normal(ks[0], (32, 48)) * 0.2,
             "w3": jax.random.normal(ks[1], (32, 48)) * 0.2,
             "w2": jax.random.normal(ks[2], (48, 32)) * 0.2}
    layer = {k: jnp.broadcast_to(v, (4,) + v.shape) for k, v in dense.items()}
    layer["router"] = jax.random.normal(ks[3], (32, 4))
    h = jax.random.normal(ks[4], (2, 8, 32))
    out, _aux = tfm._moe_mlp(h, layer, cfg, None)
    probs = jax.nn.softmax(h.reshape(16, 32) @ layer["router"], -1)
    weight = jnp.sum(jax.lax.top_k(probs, 2)[0], -1).reshape(2, 8, 1)
    want = tfm._dense_mlp(h, dense, dense_cfg, None) * weight
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
