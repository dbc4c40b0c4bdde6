"""HuggingFace Kimi-Linear import: the flagship trunk's hybrid of Kimi Delta
Attention and latent attention over sparse experts.

``KimiLinearForCausalLM`` (``model_type`` ``kimi_linear``; Moonshot AI's
Kimi-Linear-48B-A3B, arXiv:2510.26692; the model's public
``modeling_kimi.py``) is a pre-norm RMSNorm decoder with no bias anywhere and
an untied head. ``linear_attn_config`` names each layer's mixer, counting
layers from ONE: ``kda_layers`` are Kimi Delta Attention
(``KimiDeltaAttention``; ``transformer._kda``: q, k, v each through its own
4-tap causal convolution and SiLU, q and k L2-normalised a head, a log-decay
a CHANNEL from a low-rank gate, a step a head, the gated delta rule, RMSNorm a
head then a sigmoid gate from a second low-rank pair), ``full_attn_layers``
latent attention (``KimiMLAAttention``: DeepSeek-V3's with ``q_lora_rank``
null; ``mla_use_nope`` true: NOTHING is rotated, ``MLAConfig.rotate`` false).
The first ``first_k_dense_replace`` layers end in a SwiGLU MLP of width
``intermediate_size``, the others (``moe_layer_freq`` 1) in ``num_experts``
SwiGLU experts of width ``moe_intermediate_size`` of which a token takes
``num_experts_per_token`` (``KimiMoEGate``: sigmoid scores, the picks the
largest of score + ``e_score_correction_bias``, their scores over their sum +
1e-20 (``moe_renormalize``), times ``routed_scaling_factor``) beside ONE
shared SwiGLU of ``num_shared_experts`` x ``moe_intermediate_size``.

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_deepseek_v3`` has them: ``num_routed_experts`` (the router's
width where ``num_experts`` counts the experts HELD) and
``first_expert_held``; ``num_hidden_layers`` fewer than the lists name reads
their first layers.

Import is a pure weight relayout on a mapping of names to arrays: every
Linear transposed to (in, out), q/k/v's three projections side by side in
``kda_wqkv`` and their three convolutions in ``kda_conv`` (taps, channels),
``A_log`` (1, 1, H, 1) as (H,), latent attention as ``hf_deepseek_v3`` lays
it out (``hf_common``), the held experts stacked on an expert axis. The
parameter names are ``modeling_kimi.py``'s as remembered (no network here):
``benchmark/configs/kimi-linear-48b-a3b/config.json`` ``assumed``.
``benchmark/configs/kimi-linear-48b-a3b/reference.py`` is the float32
reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import (mla_from_hf, mla_leaves_from_hf, mla_leaves_to_hf,
                        np_f32, sigmoid_router_from_hf, tree_to_jnp)
from .transformer import (ROUTER_BIAS, KDAConfig, TransformerConfig,
                          blocks_of_runs, experts_of, mixer_of, run_blocks,
                          run_layers)


def mixers_of(c):
    """A mixer a layer of the stack, "kda" or "mla": the model's layers 1 ..
    ``num_hidden_layers`` by ``linear_attn_config``'s two lists."""
    la = c["linear_attn_config"]
    layers = range(1, c["num_hidden_layers"] + 1)
    unnamed = [i for i in layers
               if (i in la["kda_layers"]) == (i in la["full_attn_layers"])]
    if unnamed:
        raise NotImplementedError(
            f"linear_attn_config: layers {unnamed} (from one) are in both of "
            "kda_layers and full_attn_layers, or in neither")
    return tuple("kda" if i in la["kda_layers"] else "mla" for i in layers)


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Kimi-Linear ``config.json`` (a mapping, or a ``transformers``
    config) -> TransformerConfig; refuses by name what the trunk does not
    take. ``router_bias_rate`` among the overrides sets ``Router.bias_rate``;
    ``kda_chunk`` the chunk of the chunked rule."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    rotate = not c.get("mla_use_nope", False)
    if rotate and c.get("rope_scaling"):
        raise NotImplementedError(
            f"mla_use_nope false with rope_scaling={c['rope_scaling']!r}: "
            "latent attention's rotary columns turn at unscaled frequencies "
            "only")
    if c.get("num_nextn_predict_layers", 0):
        raise NotImplementedError(
            f"num_nextn_predict_layers={c['num_nextn_predict_layers']}: "
            "multi-token prediction layers are not written")
    if (c.get("moe_layer_freq", 1) != 1 or c.get(
            "moe_router_activation_func", "sigmoid") != "sigmoid"
            or c.get("hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            "moe_layer_freq, moe_router_activation_func, hidden_act = "
            f"{c.get('moe_layer_freq')}, "
            f"{c.get('moe_router_activation_func')}, {c.get('hidden_act')}: "
            "expert layers after the leading dense ones, every one; sigmoid "
            "scores; SiLU")
    la = c["linear_attn_config"]
    held, layers = c["num_experts"], c["num_hidden_layers"]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=layers,
        d_ff=c["intermediate_size"], d_ff_expert=c["moe_intermediate_size"],
        d_ff_shared=(c.get("num_shared_experts", 0)
                     * c["moe_intermediate_size"]),
        max_seq_len=c.get("max_position_embeddings",
                          c.get("model_max_length", 1 << 20)),
        n_experts=held, n_experts_per_tok=c["num_experts_per_token"],
        n_dense_layers=min(c.get("first_k_dense_replace", 0), layers),
        ln_eps=c.get("rms_norm_eps", 1e-5), norm="rmsnorm", rope=rotate,
        rope_theta=float(c.get("rope_theta", 1e4)), mlp="swiglu",
        use_pos_emb=False, causal=True,
        tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=mixers_of(c), mla=mla_from_hf(c, rotate=rotate),
        kda=KDAConfig(n_heads=la["num_heads"], head_dim=la["head_dim"],
                      d_conv=la["short_conv_kernel_size"],
                      chunk=overrides.pop("kda_chunk", KDAConfig.chunk)),
        router=sigmoid_router_from_hf(
            c, groups=("num_expert_group", "topk_group"), held=held,
            normalize=c.get("moe_renormalize", True),
            bias_rate=overrides.pop("router_bias_rate", 0.0)),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.
NORMS = {"ln1_scale": "input_layernorm.weight",
         "ln2_scale": "post_attention_layernorm.weight"}
# a KDA mixer: a Linear (transposed to (in, out)) ...
KDA_LINEARS = {"kda_fa": "self_attn.f_a_proj.weight",
               "kda_fb": "self_attn.f_b_proj.weight",
               "kda_wb": "self_attn.b_proj.weight",
               "kda_ga": "self_attn.g_a_proj.weight",
               "kda_gb": "self_attn.g_b_proj.weight",
               "kda_wo": "self_attn.o_proj.weight"}
# ... a vector (flattened: ``A_log`` is (1, 1, H, 1) in the checkpoint) ...
KDA_VECTORS = {"kda_dt_bias": "self_attn.dt_bias",
               "kda_A_log": "self_attn.A_log",
               "kda_norm": "self_attn.o_norm.weight"}
# ... and q, k, v's projections and convolutions, side by side in one leaf
KDA_QKV = tuple(f"self_attn.{n}_proj.weight" for n in "qkv")
KDA_CONVS = tuple(f"self_attn.{n}_conv1d.weight" for n in "qkv")
MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
       "w2": "down_proj.weight"}
EXPERT = {"w1": "w1.weight", "w3": "w3.weight", "w2": "w2.weight"}
SHARED = {"ws1": "w1", "ws3": "w3", "ws2": "w2"}
ROUTER = "block_sparse_moe.gate.weight"
EXPERT_BIAS = "block_sparse_moe.gate.e_score_correction_bias"
EMBED, FINAL_NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                           "lm_head.weight")


def hf_name(i, part):
    """``model.layers.<i>.<part>``, ``i`` from zero."""
    return f"model.layers.{i}.{part}"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"block_sparse_moe.experts.{e}.{EXPERT[w]}")


def shared_name(i, w):
    """Layer ``i``'s shared expert, ``w`` a key of MLP."""
    return hf_name(i, f"block_sparse_moe.shared_experts.{MLP[w]}")


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``KimiLinearForCausalLM.state_dict()`` names, with or
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
        side_by_side = lambda parts, f: xp.stack([xp.concatenate(
            [f(sd[hf_name(i, part)]) for part in parts], -1)
            for i in layers])
        n, E = len(layers), experts_of(cfg, kind)
        blocks = {}
        for name, part in NORMS.items():
            blocks[name] = stack(part)
            blocks[name[:-len("scale")] + "bias"] = xp.zeros(
                (n, D), xp.float32)                  # unused (rmsnorm)
        if mixer_of(kind) == "kda":
            blocks["kda_wqkv"] = side_by_side(KDA_QKV, lambda w: w.T)
            blocks["kda_conv"] = side_by_side(
                KDA_CONVS, lambda w: w.reshape(w.shape[0], -1).T)
            for name, part in KDA_LINEARS.items():
                blocks[name] = stack(part, lambda w: w.T)
            for name, part in KDA_VECTORS.items():
                blocks[name] = stack(part, lambda w: w.reshape(-1))
        else:
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
    params = {"embed": sd[EMBED], "blocks": blocks_of_runs(runs),
              "lnf_scale": sd[FINAL_NORM],
              "lnf_bias": xp.zeros((D,), xp.float32)}
    if not cfg.tied_head:
        params["head"] = sd[HEAD].T
    return params


def params_from_hf(state_dict, cfg: TransformerConfig):
    """A checkpoint's ``state_dict()`` (torch tensors of any float dtype, or
    arrays) -> the trunk's params as float32 jax arrays."""
    return tree_to_jnp(params_from_state_dict(
        {k: np_f32(v) if hasattr(v, "detach") else np.asarray(v, np.float32)
         for k, v in state_dict.items()}, cfg))


def state_dict_from_params(params, cfg: TransformerConfig):
    """The inverse relayout: params (or a tree shaped like them: gradients)
    -> HF-named arrays (of whatever array type ``params`` holds), in the
    checkpoint's shapes. Of a share only the experts held exist, under the
    model's indices."""
    first, m = cfg.router.first_held, cfg.kda
    sd = {EMBED: params["embed"], FINAL_NORM: params["lnf_scale"],
          HEAD: params["embed"] if cfg.tied_head else params["head"].T}
    for (kind, layers), b in zip(run_layers(cfg),
                                 run_blocks(cfg, params["blocks"])):
        for j, i in enumerate(layers):
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            if mixer_of(kind) == "kda":
                HK = m.d_inner
                for x, (proj, conv) in enumerate(zip(KDA_QKV, KDA_CONVS)):
                    cols = slice(x * HK, (x + 1) * HK)
                    sd[hf_name(i, proj)] = b["kda_wqkv"][j][:, cols].T
                    sd[hf_name(i, conv)] = b["kda_conv"][j][:, cols].T[
                        :, None, :]
                for name, part in KDA_LINEARS.items():
                    sd[hf_name(i, part)] = b[name][j].T
                sd[hf_name(i, KDA_VECTORS["kda_dt_bias"])] = b[
                    "kda_dt_bias"][j]
                sd[hf_name(i, KDA_VECTORS["kda_A_log"])] = b["kda_A_log"][
                    j].reshape(1, 1, -1, 1)
                sd[hf_name(i, KDA_VECTORS["kda_norm"])] = b["kda_norm"][j]
            else:
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
