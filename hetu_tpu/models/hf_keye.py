"""HuggingFace Keye-VL-2.0 import (the language model): the flagship trunk's
learned-sparse-attention dialect.

``KeyeVL2`` (Kwai-Keye/Keye-VL-2.0-30B-A3B; its text stack follows the
Qwen3-MoE lineage its keys show: ``decoder_sparse_step``, ``mlp_only_layers``,
``norm_topk_prob``) is a pre-norm RMSNorm decoder with no bias and an untied
head. Every layer's mixer is grouped-query attention (``num_attention_heads``
query heads on ``num_key_value_heads`` k/v heads, ``head_dim`` columns a head
whatever ``hidden_size`` / heads is: ``TransformerConfig.d_head``; RMSNorm on
each head's q and k, ``qk_norm="head"``; RoPE in the rotate_half convention)
under a DeepSeek-V3.2-Exp lightning indexer (``sa_config``:
``indexer_num_heads`` index heads of ``indexer_head_dim`` columns against ONE
index key a token score every key, and a query attends to the ``topk`` best:
``transformer.DSAConfig``, the "dsa" mixer). Every layer ends in
``num_experts`` SwiGLU experts of width ``moe_intermediate_size`` of which a
token takes ``num_experts_per_tok`` (softmax over the router's logits, the
picks' probabilities over their sum where ``norm_topk_prob``).

M-RoPE (``rope_scaling.mrope_section``) gives the rotary pairs of a head to
three position streams (time, height, width). On text the three carry the
same position and M-RoPE is plain RoPE, which is what runs here: a batch of
token ids has no image. The vision tower is not imported.

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_lfm2`` has them: ``num_routed_experts`` (the router's width
where ``num_experts`` counts the experts HELD: the chip's share of an expert
layer) and ``first_expert_held``.

Import is a pure weight relayout on a mapping of names to arrays: q|k|v fused
into ``wqkv``, every Linear transposed to (in, out), the held experts stacked
on an expert axis, the indexer's three Linears and its key LayerNorm under
``self_attn.indexer.*``. ``benchmark/configs/keye-vl-2.0-30b-a3b/reference.py``
is the float32 reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import DSAConfig, Router, TransformerConfig


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Keye-VL-2.0 ``config.json`` (its language model's keys; a mapping,
    or a ``transformers`` config) -> TransformerConfig; refuses variants the
    trunk does not implement (importing them would run and be numerically
    wrong)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    for key in ("attention_bias", "use_sliding_window", "sliding_window",
                "mlp_only_layers"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path (projection "
                "biases, a sliding window, dense layers among the experts')")
    if c.get("decoder_sparse_step", 1) != 1 or c.get(
            "hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "decoder_sparse_step, hidden_act = "
            f"{c.get('decoder_sparse_step')}, {c.get('hidden_act')}: an "
            "expert block in every layer; SiLU")
    heads, hd = c["num_attention_heads"], c["head_dim"]
    scaling = c.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default" \
            or 2 * sum(scaling.get("mrope_section", [hd // 2])) != hd:
        raise NotImplementedError(
            f"rope_scaling={scaling!r}: default frequencies, and an "
            "mrope_section that covers a head's rotary pairs (on text M-RoPE "
            "is then plain RoPE)")
    sa = c["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError(
            f"sa_config.indexer_num_kv_heads={sa['indexer_num_kv_heads']}: "
            "the indexer scores against ONE index key a token")
    kv_heads = c.get("num_key_value_heads") or heads
    held = c["num_experts"]
    width = c.get("num_routed_experts", held)
    layers = c["num_hidden_layers"]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads,
        d_head=0 if heads * hd == c["hidden_size"] else hd,
        n_layers=layers, d_ff=c["intermediate_size"],
        d_ff_expert=c["moe_intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        n_experts=held, n_experts_per_tok=c["num_experts_per_tok"],
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm", rope=True,
        rope_theta=float(c.get("rope_theta", 1e4)), mlp="swiglu",
        qk_norm="head", use_pos_emb=False, causal=True,
        tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=("dsa",) * layers,
        dsa=DSAConfig(n_heads=sa["indexer_num_heads"],
                      head_dim=sa["indexer_head_dim"], top_k=sa["topk"]),
        router=Router(
            score="softmax", normalize=bool(c.get("norm_topk_prob", True)),
            normalize_eps=0.0,      # Qwen3-MoE: over the picks' sum as it is
            width=0 if width == held else width,
            first_held=c.get("first_expert_held", 0)),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.:
# a vector (as it is), a Linear (transposed to (in, out))
VECTORS = {"ln1_scale": "input_layernorm.weight",
           "ln2_scale": "post_attention_layernorm.weight",
           "q_norm": "self_attn.q_norm.weight",
           "k_norm": "self_attn.k_norm.weight",
           "k_idx_norm_scale": "self_attn.indexer.k_norm.weight",
           "k_idx_norm_bias": "self_attn.indexer.k_norm.bias"}
LINEARS = {"wo": "self_attn.o_proj.weight",
           "wq_idx": "self_attn.indexer.wq.weight",
           "wk_idx": "self_attn.indexer.wk.weight",
           "ww_idx": "self_attn.indexer.weights_proj.weight",
           "router": "mlp.gate.weight"}
QKV = tuple(f"self_attn.{x}_proj.weight" for x in "qkv")
MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
       "w2": "down_proj.weight"}


def hf_name(i, part):
    """``model.layers.<i>.<part>``."""
    return f"model.layers.{i}.{part}"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"mlp.experts.{e}.{MLP[w]}")


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (with or without the ``model.`` scope; numpy or jax
    arrays; an expert's index the model's) -> the trunk's params. ``xp=jnp``
    keeps device arrays on the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items()}
    L, E, D = cfg.n_layers, cfg.n_experts, cfg.d_model
    F, first = cfg.d_ff_expert or cfg.d_ff, cfg.router.first_held
    stack = lambda part, f=lambda w: w: xp.stack(
        [f(sd[hf_name(i, part)]) for i in range(L)])
    blocks = {name: stack(part) for name, part in VECTORS.items()}
    blocks.update({name: stack(part, lambda w: w.T)
                   for name, part in LINEARS.items()})
    blocks["wqkv"] = xp.stack([xp.concatenate(
        [sd[hf_name(i, part)].T for part in QKV], axis=1) for i in range(L)])
    for w in MLP:
        blocks[w] = xp.stack([xp.stack(
            [sd[expert_name(i, first + e, w)].T for e in range(E)])
            for i in range(L)])
    blocks.update(ln1_bias=xp.zeros((L, D), xp.float32),     # unused (rmsnorm)
                  ln2_bias=xp.zeros((L, D), xp.float32),
                  b1=xp.zeros((L, E, F), xp.float32),        # unused (swiglu)
                  b2=xp.zeros((L, E, D), xp.float32))
    params = {"embed": sd["model.embed_tokens.weight"], "blocks": blocks,
              "lnf_scale": sd["model.norm.weight"],
              "lnf_bias": xp.zeros((D,), xp.float32)}
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
    """The inverse relayout: params (or a tree shaped like them: gradients)
    -> HF-named arrays (of whatever array type ``params`` holds). Of a share
    only the experts held exist, under the model's indices."""
    b, first = params["blocks"], cfg.router.first_held
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for i in range(cfg.n_layers):
        for name, part in VECTORS.items():
            sd[hf_name(i, part)] = b[name][i]
        for name, part in LINEARS.items():
            sd[hf_name(i, part)] = b[name][i].T
        for part, cols in zip(QKV, (slice(0, nq), slice(nq, nq + nkv),
                                    slice(nq + nkv, None))):
            sd[hf_name(i, part)] = b["wqkv"][i][:, cols].T
        for e in range(cfg.n_experts):
            for w in MLP:
                sd[expert_name(i, first + e, w)] = b[w][i, e].T
    return sd
