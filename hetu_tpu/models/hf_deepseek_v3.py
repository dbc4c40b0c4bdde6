"""HuggingFace DeepSeek-V3 import: the flagship trunk's latent-attention
dialect with a shared expert.

``DeepseekV3ForCausalLM`` (``model_type`` ``deepseek_v3``; Kakao's
kanana-2-30b-a3b is one at 2,048 wide) is a pre-norm RMSNorm decoder with no
bias anywhere and an untied head. Every layer's mixer is multi-head latent
attention (``transformers`` ``models/deepseek_v3/modeling_deepseek_v3.py``
``DeepseekV3Attention``; ``transformer._mla``): q = h Wq (``q_lora_rank``
null), a head [q_nope ``qk_nope_head_dim`` | q_rope ``qk_rope_head_dim``];
[c | k_rope] = h Wkv_a, c of ``kv_lora_rank`` columns and ONE rotary key a
token; [k_nope | v] a head = RMSNorm(c) Wkv_b; the rotary columns in the
interleaved convention (``rope_interleave``); softmax(q k^T /
sqrt(``qk_head_dim``)) v with v ``v_head_dim`` wide; ``o_proj`` from heads x
``v_head_dim``.

The first ``first_k_dense_replace`` layers end in a SwiGLU MLP of width
``intermediate_size``, the others (``moe_layer_freq`` 1) in
``n_routed_experts`` SwiGLU experts of width ``moe_intermediate_size`` of
which a token takes ``num_experts_per_tok`` (``DeepseekV3TopkRouter``:
sigmoid scores, the picks the largest of score + ``e_score_correction_bias``,
which enters the selection only; their scores over their sum + 1e-20,
``norm_topk_prob``, times ``routed_scaling_factor``) beside the shared
experts, ONE SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size`` on
every token (``transformer.Router``, ``TransformerConfig.d_ff_shared``).

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_lfm2`` has them: ``num_routed_experts`` (the router's width
where ``n_routed_experts`` counts the experts HELD: the chip's share of an
expert layer) and ``first_expert_held``.

Import is a pure weight relayout on a mapping of names to arrays: every
Linear transposed to (in, out), ``kv_b_proj``'s rows (a head [k_nope | v])
regrouped to [every head's k_nope | every head's v], the held experts
stacked on an expert axis. ``benchmark/configs/kanana-2-30b-a3b/reference.py``
is the float32 reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import (MLA_KV_B as KV_B, MLA_KV_NORM as KV_NORM,
                        MLA_LINEARS as ATTN_LINEARS, mla_from_hf,
                        mla_leaves_from_hf, mla_leaves_to_hf, np_f32,
                        sigmoid_router_from_hf, tree_to_jnp)
from .transformer import (ROUTER_BIAS, TransformerConfig, blocks_of_runs,
                          experts_of, run_blocks, run_layers)


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A DeepSeek-V3 ``config.json`` (a mapping, or a ``transformers``
    config) -> TransformerConfig; refuses variants the trunk does not
    implement (importing them would run and be numerically wrong).
    ``router_bias_rate`` among the overrides sets ``Router.bias_rate`` (the
    rule that moves ``e_score_correction_bias`` is not a key of
    config.json)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    for key in ("rope_scaling", "attention_bias", "attention_dropout"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path (scaled "
                "rotary frequencies, projection biases)")
    if c.get("moe_layer_freq", 1) != 1 or c.get(
            "scoring_func", "sigmoid") != "sigmoid" or not c.get(
            "rope_interleave", True) or c.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "moe_layer_freq, scoring_func, rope_interleave, hidden_act = "
            f"{c.get('moe_layer_freq')}, {c.get('scoring_func')}, "
            f"{c.get('rope_interleave')}, {c.get('hidden_act')}: expert "
            "layers after the leading dense ones, every one; sigmoid "
            "scores; interleaved rotary columns; SiLU")
    heads = c["num_attention_heads"]
    mla = mla_from_hf(c)
    held = c["n_routed_experts"]
    layers = c["num_hidden_layers"]
    router = sigmoid_router_from_hf(
        c, groups=("n_group", "topk_group"), held=held,
        normalize=c.get("norm_topk_prob", True),
        bias_rate=overrides.pop("router_bias_rate", 0.0))
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_layers=layers, d_ff=c["intermediate_size"],
        d_ff_expert=c["moe_intermediate_size"],
        d_ff_shared=c.get("n_shared_experts", 0) * c["moe_intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        n_experts=held, n_experts_per_tok=c["num_experts_per_tok"],
        n_dense_layers=min(c.get("first_k_dense_replace", 0), layers),
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm", rope=True,
        rope_theta=float(c.get("rope_theta", 1e4)), mlp="swiglu",
        use_pos_emb=False, causal=True,
        tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=("mla",) * layers, mla=mla, router=router,
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.:
# a norm's scale (1-D, as it is), a Linear (transposed to (in, out))
NORMS = {"ln1_scale": "input_layernorm.weight",
         "ln2_scale": "post_attention_layernorm.weight"}
MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
       "w2": "down_proj.weight"}
SHARED = {"ws1": "w1", "ws3": "w3", "ws2": "w2"}
ROUTER, EXPERT_BIAS = "mlp.gate.weight", "mlp.gate.e_score_correction_bias"


def hf_name(i, part):
    """``model.layers.<i>.<part>``."""
    return f"model.layers.{i}.{part}"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"mlp.experts.{e}.{MLP[w]}")


def shared_name(i, w):
    """Layer ``i``'s shared expert, ``w`` a key of MLP."""
    return hf_name(i, f"mlp.shared_experts.{MLP[w]}")


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``DeepseekV3ForCausalLM.state_dict()`` names, with or
    without the ``model.`` scope; numpy or jax arrays; an expert's index the
    model's) -> the trunk's params: one stacked dict a run of ``layer_runs``.
    ``xp=jnp`` keeps device arrays on the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items()}
    D, first = cfg.d_model, cfg.router.first_held
    runs = []
    for kind, layers in run_layers(cfg):
        stack = lambda part, f=lambda w: w: xp.stack(
            [f(sd[hf_name(i, part)]) for i in layers])
        n, E = len(layers), experts_of(cfg, kind)
        blocks = {}
        for name, part in NORMS.items():
            blocks[name] = stack(part)
            blocks[name[:-len("scale")] + "bias"] = xp.zeros(
                (n, D), xp.float32)                  # unused (rmsnorm)
        blocks.update(mla_leaves_from_hf(stack, cfg))
        if E:
            F = cfg.d_ff_expert or cfg.d_ff
            for w in MLP:
                blocks[w] = xp.stack([xp.stack(
                    [sd[expert_name(i, first + e, w)].T for e in range(E)])
                    for i in layers])
            blocks["router"] = stack(ROUTER, lambda w: w.T)
            blocks[ROUTER_BIAS] = stack(EXPERT_BIAS)
            blocks["b1"] = xp.zeros((n, E, F), xp.float32)   # unused (swiglu)
            blocks["b2"] = xp.zeros((n, E, D), xp.float32)
            for name, w in (SHARED.items() if cfg.d_ff_shared else ()):
                blocks[name] = xp.stack(
                    [sd[shared_name(i, w)].T for i in layers])
        else:
            for w, part in MLP.items():
                blocks[w] = stack("mlp." + part, lambda m: m.T)
            blocks["b1"] = xp.zeros((n, cfg.d_ff), xp.float32)
            blocks["b2"] = xp.zeros((n, D), xp.float32)
        runs.append(blocks)
    params = {"embed": sd["model.embed_tokens.weight"],
              "blocks": blocks_of_runs(runs),
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
    first = cfg.router.first_held
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for (kind, layers), b in zip(run_layers(cfg),
                                 run_blocks(cfg, params["blocks"])):
        for j, i in enumerate(layers):
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            for part, w in mla_leaves_to_hf(b, j, cfg).items():
                sd[hf_name(i, part)] = w
            if experts_of(cfg, kind):
                for e in range(cfg.n_experts):
                    for w in MLP:
                        sd[expert_name(i, first + e, w)] = b[w][j, e].T
                sd[hf_name(i, ROUTER)] = b["router"][j].T
                sd[hf_name(i, EXPERT_BIAS)] = b[ROUTER_BIAS][j]
                for name, w in (SHARED.items() if cfg.d_ff_shared else ()):
                    sd[shared_name(i, w)] = b[name][j].T
            else:
                for w, part in MLP.items():
                    sd[hf_name(i, "mlp." + part)] = b[w][j].T
    return sd
