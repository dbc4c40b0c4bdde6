"""HuggingFace OLMoE import: the flagship trunk's sparse dialect.

``transformers`` ``OlmoeForCausalLM`` (arXiv:2409.02060; ``model_type``
``olmoe``) is the Llama dialect of ``hf_llama.py`` (pre-norm RMSNorm, RoPE
in the HF rotate_half convention, no biases, untied head) with two
additions: RMSNorm on the whole projected q and k before the head split
(``self_attn.q_norm``/``k_norm`` -> ``q_norm``/``k_norm``, ``qk_norm=True``)
and, in place of the dense MLP, ``num_experts`` SwiGLU experts of which
every token takes the ``num_experts_per_tok`` with the largest router
probability, weighted by that probability as it stands
(``norm_topk_prob`` false): ``mlp.gate`` -> ``router``,
``mlp.experts.N.{gate,up,down}_proj`` stacked on an expert axis into
``w1``/``w3``/``w2``. Import is a pure weight relayout and works on a plain
mapping of names to arrays: no ``transformers`` is needed.
``benchmark/configs/olmoe-1b-7b/reference.py`` is the float32 reference
the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .transformer import TransformerConfig


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """An OLMoE ``config.json`` (a mapping, or a ``transformers`` config) ->
    TransformerConfig; refuses variants the trunk does not implement
    (importing them would run and be numerically wrong)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    if c.get("hidden_act", "silu") not in ("silu", "swish"):
        raise NotImplementedError(f"hidden_act={c['hidden_act']!r}: only silu")
    for key in ("attention_bias", "clip_qkv", "rope_scaling",
                "norm_topk_prob"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path")
    heads = c["num_attention_heads"]
    kv_heads = c.get("num_key_value_heads") or heads
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads,
        n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        n_experts=c["num_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        ln_eps=c.get("rms_norm_eps", 1e-5), norm="rmsnorm", rope=True,
        rope_theta=float(c.get("rope_theta", 10000.0)), mlp="swiglu",
        qk_norm=True, use_pos_emb=False,
        tied_head=bool(c.get("tie_word_embeddings", False)), causal=True,
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


def _layer_names(i):
    p = f"model.layers.{i}."
    return {"q": p + "self_attn.q_proj.weight",
            "k": p + "self_attn.k_proj.weight",
            "v": p + "self_attn.v_proj.weight",
            "o": p + "self_attn.o_proj.weight",
            "q_norm": p + "self_attn.q_norm.weight",
            "k_norm": p + "self_attn.k_norm.weight",
            "ln1": p + "input_layernorm.weight",
            "ln2": p + "post_attention_layernorm.weight",
            "router": p + "mlp.gate.weight",
            "expert": p + "mlp.experts.{e}.{proj}_proj.weight"}


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``OlmoeForCausalLM.state_dict()`` names, with or
    without the ``model.`` scope; numpy or jax arrays) -> the trunk's params:
    q|k|v fused into ``wqkv``, every Linear transposed to (in, out), experts
    stacked on an expert axis. ``xp=jnp`` keeps device arrays on the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items() if "rotary_emb" not in k}
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    names = [_layer_names(i) for i in range(L)]

    def stack(key, t=False):
        return xp.stack([sd[n[key]].T if t else sd[n[key]] for n in names])

    def experts(proj):
        return xp.stack([xp.stack([
            sd[n["expert"].format(e=e, proj=proj)].T for e in range(E)])
            for n in names])

    blocks = {
        "wqkv": xp.stack([xp.concatenate(
            [sd[n["q"]].T, sd[n["k"]].T, sd[n["v"]].T], axis=1)
            for n in names]),
        "wo": stack("o", t=True),
        "q_norm": stack("q_norm"), "k_norm": stack("k_norm"),
        "ln1_scale": stack("ln1"), "ln2_scale": stack("ln2"),
        "ln1_bias": xp.zeros((L, D), xp.float32),    # unused (rmsnorm)
        "ln2_bias": xp.zeros((L, D), xp.float32),
        "router": stack("router", t=True),
        "w1": experts("gate"), "w3": experts("up"), "w2": experts("down"),
        "b1": xp.zeros((L, E, F), xp.float32),       # unused (swiglu)
        "b2": xp.zeros((L, E, D), xp.float32),
    }
    params = {"embed": sd["model.embed_tokens.weight"], "blocks": blocks,
              "lnf_scale": sd["model.norm.weight"],
              "lnf_bias": xp.zeros((D,), xp.float32)}
    if not cfg.tied_head:
        params["head"] = sd["lm_head.weight"].T
    return params


def state_dict_from_params(params, cfg: TransformerConfig):
    """The inverse relayout: params -> HF-named arrays (views of whatever
    array type ``params`` holds)."""
    b = params["blocks"]
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for i in range(cfg.n_layers):
        n = _layer_names(i)
        wqkv = b["wqkv"][i]
        sd[n["q"]] = wqkv[:, :nq].T
        sd[n["k"]] = wqkv[:, nq:nq + nkv].T
        sd[n["v"]] = wqkv[:, nq + nkv:].T
        sd[n["o"]] = b["wo"][i].T
        sd[n["q_norm"]], sd[n["k_norm"]] = b["q_norm"][i], b["k_norm"][i]
        sd[n["ln1"]], sd[n["ln2"]] = b["ln1_scale"][i], b["ln2_scale"][i]
        sd[n["router"]] = b["router"][i].T
        for e in range(cfg.n_experts):
            for proj, key in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                sd[n["expert"].format(e=e, proj=proj)] = b[key][i, e].T
    return sd
