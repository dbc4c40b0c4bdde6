"""HuggingFace Ouro import: the flagship trunk's looped dialect.

``OuroForCausalLM`` (ByteDance Ouro-1.4B/2.6B, ``model_type`` ``ouro``; Zhu
et al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) is the Llama dialect of ``hf_llama.py`` (RMSNorm, RoPE in
the HF rotate_half convention, SwiGLU, no biases, untied head, full MHA)
with three additions:

- the whole stack of ``num_hidden_layers`` layers is applied
  ``total_ut_steps`` times with the same weights, ``model.norm`` after each
  pass (``n_loops``);
- sandwich normalisation: ``input_layernorm`` before and
  ``input_layernorm_2`` after attention, ``post_attention_layernorm`` before
  and ``post_attention_layernorm_2`` after the MLP, each sublayer's normed
  output added to the residual (``sandwich_norm``: ``ln1``, ``ln1_post``,
  ``ln2``, ``ln2_post``);
- ``model.early_exit_gate``, a ``Linear(hidden, 1)`` with bias on every
  pass's normed state (``exit_gate_w``/``exit_gate_b``), from which the
  training loss builds the exit distribution (``transformer.exit_loss_terms``).

Import is a pure weight relayout and works on a plain mapping of names to
arrays: no ``transformers`` is needed (4.57 carries no ``modeling_ouro.py``;
the names above are the checkpoint's). ``benchmark/configs/ouro-2.6b/
reference.py`` is the float32 reference the tests and the benchmark compare
against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import TransformerConfig


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """An Ouro ``config.json`` (a mapping, or a ``transformers`` config) ->
    TransformerConfig; refuses variants the trunk does not implement
    (importing them would run and be numerically wrong)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    if c.get("hidden_act", "silu") not in ("silu", "swish"):
        raise NotImplementedError(f"hidden_act={c['hidden_act']!r}: only silu")
    for key in ("rope_scaling", "use_sliding_window", "attention_bias"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path")
    layers = c["num_hidden_layers"]
    kinds = set((c.get("layer_types") or [])[:layers])
    if kinds - {"full_attention"}:
        raise NotImplementedError(
            f"layer_types={sorted(kinds)}: only full_attention")
    heads = c["num_attention_heads"]
    head_dim = c.get("head_dim") or c["hidden_size"] // heads
    if head_dim * heads != c["hidden_size"]:
        raise NotImplementedError(
            f"head_dim={head_dim} x {heads} heads != hidden_size="
            f"{c['hidden_size']}: the trunk's heads tile the hidden size")
    kv_heads = c.get("num_key_value_heads") or heads
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads,
        n_layers=layers, d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        n_loops=c["total_ut_steps"], sandwich_norm=True,
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm", rope=True,
        rope_theta=float(c.get("rope_theta", 10000.0)), mlp="swiglu",
        use_pos_emb=False,
        tied_head=bool(c.get("tie_word_embeddings", False)), causal=True,
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.
NORMS = {"ln1_scale": "input_layernorm",
          "ln1_post_scale": "input_layernorm_2",
          "ln2_scale": "post_attention_layernorm",
          "ln2_post_scale": "post_attention_layernorm_2"}
LINEARS = {"wo": "self_attn.o_proj", "w1": "mlp.gate_proj",
           "w3": "mlp.up_proj", "w2": "mlp.down_proj"}
GATE_W, GATE_B = "model.early_exit_gate.weight", "model.early_exit_gate.bias"


def hf_name(i, part):
    """``model.layers.<i>.<part>.weight``."""
    return f"model.layers.{i}.{part}.weight"


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``OuroForCausalLM.state_dict()`` names, with or
    without the ``model.`` scope; numpy or jax arrays) -> the trunk's params:
    q|k|v fused into ``wqkv``, every Linear transposed to (in, out), layers
    stacked. ``xp=jnp`` keeps device arrays on the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items() if "rotary_emb" not in k}
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    blocks = {
        "wqkv": xp.stack([xp.concatenate(
            [sd[hf_name(i, f"self_attn.{p}_proj")].T for p in "qkv"], axis=1)
            for i in range(L)]),
        "b1": xp.zeros((L, F), xp.float32),          # unused (swiglu)
        "b2": xp.zeros((L, D), xp.float32)}
    for name, part in LINEARS.items():
        blocks[name] = xp.stack([sd[hf_name(i, part)].T for i in range(L)])
    for name, part in NORMS.items():
        blocks[name] = xp.stack([sd[hf_name(i, part)] for i in range(L)])
        blocks[name[:-len("scale")] + "bias"] = xp.zeros(
            (L, D), xp.float32)                      # unused (rmsnorm)
    params = {"embed": sd["model.embed_tokens.weight"], "blocks": blocks,
              "lnf_scale": sd["model.norm.weight"],
              "lnf_bias": xp.zeros((D,), xp.float32),
              "exit_gate_w": sd[GATE_W].reshape(D),
              "exit_gate_b": sd[GATE_B].reshape(())}
    if not cfg.tied_head:
        params["head"] = sd["lm_head.weight"].T
    return params


def params_from_hf(state_dict, cfg: TransformerConfig):
    """A checkpoint's ``state_dict()`` (torch tensors of any float dtype, or
    arrays) -> the trunk's params as float32 jax arrays."""
    return tree_to_jnp(params_from_state_dict(
        {k: np_f32(v) if hasattr(v, "detach") else np.asarray(v, np.float32)
         for k, v in state_dict.items()}, cfg))


def state_dict_from_params(params, cfg: TransformerConfig):
    """The inverse relayout: params -> HF-named arrays (views of whatever
    array type ``params`` holds)."""
    b = params["blocks"]
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          GATE_W: params["exit_gate_w"].reshape(1, cfg.d_model),
          GATE_B: params["exit_gate_b"].reshape(1),
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for i in range(cfg.n_layers):
        wqkv = b["wqkv"][i]
        sd[hf_name(i, "self_attn.q_proj")] = wqkv[:, :nq].T
        sd[hf_name(i, "self_attn.k_proj")] = wqkv[:, nq:nq + nkv].T
        sd[hf_name(i, "self_attn.v_proj")] = wqkv[:, nq + nkv:].T
        for name, part in LINEARS.items():
            sd[hf_name(i, part)] = b[name][i].T
        for name, part in NORMS.items():
            sd[hf_name(i, part)] = b[name][i]
    return sd
