"""The `reference.py` files of five cells held to the PUBLISHED modelling code
(`transformers`' granitemoehybrid, deepseek_v3, lfm2, mamba2 and qwen3_next
modules on copied weights), in ONE file: importing `torch` and `transformers` costs a
worker tens of seconds, and a file is xdist's unit, so one worker pays it and
not four. Each test was its model file's (test_granite_model.py,
test_kanana_model.py, test_lfm2_model.py, test_nemotron_h_model.py; the
qwen3_next one was written here, PR 68), whose toy configuration it still
runs at. `test_torch_twin.py` and the
`test_hf_*.py` files need torch throughout and stay."""
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import (hf_deepseek_v3 as hd, hf_granite, hf_lfm2,
                             hf_qwen3_next, transformer as tfm)
from model_harness import load_reference, rel, seeded_params, seeded_tokens
from test_granite_model import HF as GRANITE
from test_kanana_model import HF as KANANA
from test_lfm2_model import HF as LFM2
from test_nemotron_h_model import HF as NEMOTRON
from test_qwen3_next_model import HF as QWEN3_NEXT, _params as qwen3_params

granite_reference = load_reference("granite-4.0-h-micro")
kanana_reference = load_reference("kanana-2-30b-a3b")
lfm2_reference = load_reference("lfm2-8b-a1b")
nemotron_reference = load_reference("nemotron-twotower-30b-a3b")
qwen3_next_reference = load_reference("qwen3-next-80b-a3b")


# -- granite-4.0-h-micro ------------------------------------------------------

def test_reference_matches_transformers_torch_forward():
    """The reference's recurrence over time against GRANITE's chunked
    ``torch_forward`` (and its eager attention, gated norm, multipliers) on
    copied seeded weights: logits within 1e-4, and within 1e-4 of their RMS
    (the logits' spread is 0.02 at these weights). Both are float32 on the
    CPU: GRANITE's chunked sums and the time scan differ by summation order,
    measured 6e-8 and 1.7e-7."""
    torch = pytest.importorskip("torch", reason="torch is not installed")
    try:
        from transformers import (GraniteMoeHybridConfig,
                                  GraniteMoeHybridForCausalLM)
    except ImportError as e:
        pytest.skip(f"transformers has no GraniteMoeHybridForCausalLM: {e}")
    cfg = hf_granite.config_from_hf(GRANITE)
    # the leaves the initialiser makes constant, moved so that a wrong use
    # of any of them shows
    params = seeded_params(cfg, bias=None, noisy=(
        "A_log", "dt_bias", "D", "conv_b", "ssm_norm", "ln1_scale",
        "ln2_scale", "lnf_scale"))
    sd = hf_granite.state_dict_from_params(params, cfg)
    hf_cfg = GraniteMoeHybridConfig(
        **{**GRANITE, "intermediate_size": 128, "num_experts_per_tok": 0,
           "attention_dropout": 0.0, "attn_implementation": "eager"})
    model = GraniteMoeHybridForCausalLM(hf_cfg).float().eval()
    missing = model.load_state_dict(
        {k: torch.tensor(np.asarray(v)) for k, v in sd.items()}, strict=True)
    assert not missing.missing_keys and not missing.unexpected_keys
    tokens, _ = seeded_tokens(GRANITE, 1)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(tokens), dtype=torch.long),
                     use_cache=False).logits.numpy()
    got = np.asarray(granite_reference.logits(sd, tokens, GRANITE))
    assert np.max(np.abs(got - want)) < 1e-4
    assert rel(got, want) < 1e-4 and np.std(want) > 0.01


# -- kanana-2-30b-a3b ---------------------------------------------------------

def test_reference_matches_transformers_deepseek_v3():
    """`DeepseekV3ForCausalLM` (eager attention, float32) on copied weights,
    every expert held, the selection bias off zero: the reference's logits
    are KANANA's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    keys = {k: v for k, v in KANANA.items() if k not in ("qk_head_dim",)}
    config = transformers.DeepseekV3Config(**keys,
                                           attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.DeepseekV3ForCausalLM(config).eval().float()
    with torch.no_grad():
        for name, buf in model.named_buffers():
            if name.endswith("e_score_correction_bias"):
                buf.copy_(0.05 * torch.randn_like(buf))
        for name, p in model.named_parameters():
            if name.endswith("layernorm.weight") or (
                    name == "model.norm.weight"):
                p.add_(0.1 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()
          if "rotary_emb" not in k}
    tokens, _ = seeded_tokens(KANANA, 4)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(tokens))).logits.numpy()
    got = kanana_reference.logits(sd, tokens, KANANA)
    assert rel(got, want) < 2e-5
    # and the trunk loads the same checkpoint to the same logits
    cfg = hd.config_from_hf(config)
    assert cfg.mla.qk_dim == 48 and cfg.d_ff_shared == 96
    params = hd.params_from_hf(model.state_dict(), cfg)
    ours, _ = tfm.forward(params, tokens, cfg)
    assert rel(ours, want) < 2e-5


# -- lfm2-8b-a1b: its conv mixer, attention and dense layer -------------------

@pytest.fixture(scope="module")
def hf_modules():
    torch = pytest.importorskip("torch")
    lfm2 = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers import Lfm2Config
    config = Lfm2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, norm_eps=1e-5, rope_theta=1000000.0,
        conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
        layer_types=["conv", "full_attention"])
    config._attn_implementation = "eager"
    torch.manual_seed(0)
    return torch, lfm2, config


def _np_state(module):
    return {k: v.detach().numpy() for k, v in module.state_dict().items()}


def test_conv_mixer_is_transformers_slow_forward(hf_modules):
    torch, lfm2, config = hf_modules
    conv = lfm2.Lfm2ShortConv(config, 0).eval()
    u = torch.randn(2, 16, 64)
    with torch.no_grad():
        want = conv.slow_forward(u).numpy()
    w = {"conv." + k: jnp.asarray(v) for k, v in _np_state(conv).items()}
    got = lfm2_reference._conv_math(jnp.asarray(u.numpy()), w, LFM2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-6)
    # the system's mixer on the same weights
    cfg = hf_lfm2.config_from_hf(LFM2)
    p = {"w_in": w["conv.in_proj.weight"].T,
         "conv_w": w["conv.conv.weight"][:, 0, :].T,
         "w_out": w["conv.out_proj.weight"].T}
    mine = tfm._short_conv(jnp.asarray(u.numpy()), p, cfg, None)
    np.testing.assert_allclose(np.asarray(mine), want, rtol=2e-4, atol=2e-6)


def test_attention_layer_is_transformers_lfm2_attention(hf_modules):
    torch, lfm2, config = hf_modules
    attn = lfm2.Lfm2Attention(config, 1).eval()
    with torch.no_grad():      # norm scales off 1, so that they matter
        attn.q_layernorm.weight.uniform_(0.5, 1.5)
        attn.k_layernorm.weight.uniform_(0.5, 1.5)
    T = 16
    u = torch.randn(2, T, 64)
    rope = lfm2.Lfm2RotaryEmbedding(config)
    cos_sin = rope(u, torch.arange(T)[None])
    mask = torch.full((T, T), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = attn(u, cos_sin, mask)[0].numpy()
    w = {"self_attn." + k: jnp.asarray(v) for k, v in _np_state(attn).items()}
    got = lfm2_reference._attention_math(jnp.asarray(u.numpy()), w, LFM2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-6)
    # the system's attention (per-head QK-norm, RoPE, GQA) on the same
    cfg = hf_lfm2.config_from_hf(LFM2)
    p = {"wqkv": jnp.concatenate(
        [w[f"self_attn.{n}_proj.weight"].T for n in "qkv"], 1),
        "wo": w["self_attn.out_proj.weight"].T,
        "q_norm": w["self_attn.q_layernorm.weight"],
        "k_norm": w["self_attn.k_layernorm.weight"]}
    mine = tfm._attention(jnp.asarray(u.numpy()), p, cfg, None)
    np.testing.assert_allclose(np.asarray(mine), want, rtol=2e-4, atol=2e-6)


def test_dense_mlp_and_layer_are_transformers(hf_modules):
    torch, lfm2, config = hf_modules
    mlp = lfm2.Lfm2MLP(config).eval()
    assert mlp.w1.weight.shape == (128, 64)     # the width taken as it is
    layer = lfm2.Lfm2DecoderLayer(config, 0).eval()     # conv + dense
    T = 16
    u = torch.randn(2, T, 64)
    rope = lfm2.Lfm2RotaryEmbedding(config)
    with torch.no_grad():
        want = layer(u, rope(u, torch.arange(T)[None])).numpy()
    w = {k: jnp.asarray(v) for k, v in _np_state(layer).items()}
    got = lfm2_reference._layer_math(jnp.asarray(u.numpy()), w, LFM2,
                                     ("conv", None))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-6)


# -- nemotron-twotower-30b-a3b: its Mamba-2 mixer -----------------------------

def test_reference_mixer_is_transformers_mamba2_at_one_group():
    """The reference's Mamba-2 mixer (a recurrence over time) against
    `transformers`' `Mamba2Mixer.torch_forward` (the chunked form) on copied
    weights at ONE group, where the norm by group IS the norm over all
    channels (HF's gated norm knows no groups)."""
    torch = pytest.importorskip("torch", reason="torch is not installed")
    try:
        from transformers import Mamba2Config
        from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer
    except ImportError as e:
        pytest.skip(f"transformers has no Mamba2Mixer: {e}")
    hf = {**NEMOTRON, "n_groups": 1}
    mixer = Mamba2Mixer(Mamba2Config(
        num_heads=8, head_dim=16, hidden_size=64, state_size=16, n_groups=1,
        conv_kernel=4, expand=2, chunk_size=8, use_bias=False,
        use_conv_bias=True, hidden_act="silu", layer_norm_epsilon=1e-5,
        time_step_limit=(0.0, float("inf")), num_hidden_layers=1,
        vocab_size=256), layer_idx=0).float().eval()
    rng = np.random.default_rng(0)
    w = {n: (0.3 * rng.standard_normal(tuple(p.shape))).astype(np.float32)
         for n, p in mixer.named_parameters()}
    w["A_log"] = np.log(np.arange(1, 9, dtype=np.float32))
    w["dt_bias"] = rng.uniform(-5, -2, 8).astype(np.float32)
    w["norm.weight"] = w["norm.weight"] + 1.0
    mixer.load_state_dict({n: torch.tensor(v) for n, v in w.items()},
                          strict=True)
    u = rng.standard_normal((2, 32, 64)).astype(np.float32)
    with torch.no_grad():
        want = mixer.torch_forward(torch.tensor(u)).numpy()
    got = np.asarray(nemotron_reference._mamba_math(
        jnp.asarray(u), {n: jnp.asarray(v) for n, v in w.items()}, hf))
    assert np.std(want) > 0.05 and rel(got, want) < 2e-5


# -- bert-base ----------------------------------------------------------------

@pytest.mark.parametrize("name,exact", [("gelu", True), ("gelu_new", False)])
def test_gelu_is_transformers_activation(name, exact):
    """`transformer._gelu` is the activation `gelu_exact` stands for:
    `ACT2FN["gelu"]` (torch's erf form; BERT, ViT) under it, `gelu_new` (the
    tanh form; GPT-2) without. In float32 on the CPU to 2e-6 (torch's `erf`
    and XLA's differ by 2.4e-7), and the erf form in bfloat16 to one place of
    the result: torch too computes it in float32 and rounds once (its tanh
    form rounds every step to bfloat16 and is no oracle there)."""
    torch = pytest.importorskip("torch", reason="torch is not installed")
    activations = pytest.importorskip("transformers.activations")
    cfg = tfm.TransformerConfig(gelu_exact=exact)
    x = np.linspace(-10.0, 10.0, 40001).astype(np.float32)
    with torch.no_grad():
        want = activations.ACT2FN[name](torch.tensor(x)).numpy()
    got = np.asarray(tfm._gelu(jnp.asarray(x), cfg))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-7)
    if not exact:
        return
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    with torch.no_grad():
        want = activations.ACT2FN[name](
            torch.tensor(np.asarray(xb.astype(jnp.float32))).bfloat16()
        ).float().numpy()
    got = np.asarray(tfm._gelu(xb, cfg).astype(jnp.float32))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2.0 ** -7)


# -- qwen3-next-80b-a3b -------------------------------------------------------

def test_qwen3_next_reference_matches_transformers():
    """The reference (the recurrence over POSITIONS, the zero-centred norms,
    the gate a column, the gated shared expert) against `transformers`'
    `Qwen3NextForCausalLM` (its torch CHUNKED gated delta rule, eager
    attention) on copied seeded weights, every expert held: every key of the
    loader's state dict lands (`strict=True`: the HF names, the key-head
    grouping of `in_proj_qkvz` / `in_proj_ba` and the [q | gate] rows of
    `q_proj` are the published ones), the residual stream after each layer
    and every token's NLL within 1e-5 of their RMS (measured 1e-7, float32
    on the CPU on both sides)."""
    torch = pytest.importorskip("torch", reason="torch is not installed")
    try:
        from transformers import Qwen3NextConfig, Qwen3NextForCausalLM
    except ImportError as e:
        pytest.skip(f"transformers has no Qwen3NextForCausalLM: {e}")
    cfg = hf_qwen3_next.config_from_hf(QWEN3_NEXT)
    sd = hf_qwen3_next.state_dict_from_params(qwen3_params(cfg, 1), cfg)
    hf_cfg = Qwen3NextConfig(
        **{k: v for k, v in QWEN3_NEXT.items() if k != "assumed"},
        attention_dropout=0.0, attn_implementation="eager")
    assert hf_cfg.layer_types == ["linear_attention"] * 3 + [
        "full_attention"]
    model = Qwen3NextForCausalLM(hf_cfg).float().eval()
    missing = model.load_state_dict(
        {k: torch.tensor(np.asarray(v)) for k, v in sd.items()}, strict=True)
    assert not missing.missing_keys and not missing.unexpected_keys
    tokens, targets = seeded_tokens(QWEN3_NEXT, 3, B=2, T=40)
    with torch.no_grad():
        out = model(torch.tensor(np.asarray(tokens), dtype=torch.long),
                    use_cache=False, output_hidden_states=True)
    logp = torch.log_softmax(out.logits.double(), -1).numpy()
    want_nll = -np.take_along_axis(logp, np.asarray(targets)[..., None],
                                   -1)[..., 0]
    _, terms = qwen3_next_reference.loss_terms(sd, tokens, targets,
                                               QWEN3_NEXT)
    assert rel(terms["nll"], want_nll) < 1e-5
    # hidden_states[0] is the embedding; the last is after the final norm
    for i in range(3):
        assert rel(terms["hidden"][i],
                   out.hidden_states[i + 1].numpy()) < 1e-5, i
