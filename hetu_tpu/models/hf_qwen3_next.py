"""HuggingFace Qwen3-Next import: the flagship trunk's hybrid of Gated
DeltaNet and gated attention over sparse experts.

``Qwen3NextForCausalLM`` (``model_type`` ``qwen3_next``; Qwen's
Qwen3-Next-80B-A3B, 2025-09; ``transformers``' ``modeling_qwen3_next.py``) is
a pre-norm decoder with no bias anywhere, an untied head and ZERO-CENTRED
RMSNorms (``Qwen3NextRMSNorm``: the stored weight is w of a scale 1 + w;
``TransformerConfig.norm_offset``). Layer i (from 0) is gated attention where
(i + 1) % ``full_attention_interval`` == 0 (``Qwen3NextAttention``: grouped
queries at ``head_dim``, a per-head zero-centred q/k norm, rotate-half RoPE on
a head's first ``partial_rotary_factor`` x ``head_dim`` columns, a sigmoid
gate a COLUMN from the second half of each head's ``q_proj`` rows:
``attn_gate`` "column") and a Gated DeltaNet elsewhere
(``Qwen3NextGatedDeltaNet``; ``transformer._gdn``: ``linear_num_key_heads``
key heads under ``linear_num_value_heads`` value heads, ONE 4-tap causal
convolution over [q | k | v], one log-decay a value head, RMSNorm a head then
SiLU(z)). Every layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` [])
ends in ``num_experts`` SwiGLU experts of ``moe_intermediate_size`` of which
a token takes ``num_experts_per_tok`` (softmax over the experts, the picks'
probabilities over their sum: ``norm_topk_prob``) beside ONE shared SwiGLU
of ``shared_expert_intermediate_size`` times sigmoid of one gate logit a
token (``shared_gate``).

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_kimi_linear`` has them: ``num_routed_experts`` (the router's
width where ``num_experts`` counts the experts HELD) and
``first_expert_held``; ``num_hidden_layers`` fewer than published reads the
first layers.

Import is a pure weight relayout on a mapping of names to arrays: every
Linear transposed to (in, out); ``in_proj_qkvz``'s columns, which the
checkpoint groups by KEY head ([q | k | v of its value heads | z of them] a
key head), regrouped as [q | k | v | z] with every head of a part side by
side (``qkvz_columns``), ``in_proj_ba``'s likewise (``ba_columns``);
``q_proj``'s rows, a head's query beside its gate, split into ``wqkv``'s q
columns and ``wg``; the held experts stacked on an expert axis.
``benchmark/configs/qwen3-next-80b-a3b/reference.py`` is the float32
reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .hf_kimi_linear import (EMBED, FINAL_NORM, HEAD, MLP, NORMS, SHARED,
                             hf_name)
from .transformer import (GDNConfig, Router, TransformerConfig,
                          blocks_of_runs, mixer_of, run_blocks, run_layers)


def mixers_of(c):
    """A mixer a layer of the stack, "gdn" or "attention", from 0."""
    every = c.get("full_attention_interval", 4)
    return tuple("attention" if (i + 1) % every == 0 else "gdn"
                 for i in range(c["num_hidden_layers"]))


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Qwen3-Next ``config.json`` (a mapping, or a ``transformers`` config)
    -> TransformerConfig; refuses by name what the trunk does not take.
    ``router_aux_loss_coef`` among the overrides (else the config's, else the
    published 0.001) weighs the routers' balance loss; ``gdn_chunk`` sets
    ``GDNConfig.chunk``."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    refused = {
        "mlp_only_layers": (c.get("mlp_only_layers") or [], [],
                            "every layer ends in the expert block"),
        "decoder_sparse_step": (c.get("decoder_sparse_step", 1), 1,
                                "every layer ends in the expert block"),
        "rope_scaling": (c.get("rope_scaling"), None,
                         "the rotary columns turn at unscaled frequencies"),
        "use_sliding_window": (bool(c.get("use_sliding_window", False)),
                               False, "the attention layers are full"),
        "num_nextn_predict_layers": (
            c.get("num_nextn_predict_layers", 0) or 0, 0,
            "multi-token prediction layers are not written"),
        "attention_bias": (bool(c.get("attention_bias", False)), False,
                           "no Linear has a bias"),
        "hidden_act": (c.get("hidden_act", "silu"), "silu", "SwiGLU")}
    for key, (got, taken, why) in refused.items():
        if got != taken:
            raise NotImplementedError(f"{key}={got!r}: {why} ({taken!r})")
    held, hd = c["num_experts"], c["head_dim"]
    width = c.get("num_routed_experts", held)
    rot = int(hd * c.get("partial_rotary_factor", 1.0))
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=hd,
        n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
        d_ff_expert=c["moe_intermediate_size"],
        d_ff_shared=c["shared_expert_intermediate_size"], shared_gate=True,
        max_seq_len=c.get("max_position_embeddings", 1 << 18),
        n_experts=held, n_experts_per_tok=c["num_experts_per_tok"],
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm", norm_offset=True,
        rope=True, rope_theta=float(c.get("rope_theta", 1e4)),
        rope_dim=0 if rot == hd else rot, mlp="swiglu", use_pos_emb=False,
        causal=True, tied_head=bool(c.get("tie_word_embeddings", False)),
        qk_norm="head", attn_gate="column", layer_types=mixers_of(c),
        gdn=GDNConfig(n_k_heads=c["linear_num_key_heads"],
                      n_v_heads=c["linear_num_value_heads"],
                      k_dim=c["linear_key_head_dim"],
                      v_dim=c["linear_value_head_dim"],
                      d_conv=c["linear_conv_kernel_dim"],
                      chunk=overrides.pop("gdn_chunk", GDNConfig.chunk)),
        router=Router(score="softmax",
                      normalize=bool(c.get("norm_topk_prob", True)),
                      normalize_eps=0.0,
                      loss_weights=(overrides.pop(
                          "router_aux_loss_coef",
                          c.get("router_aux_loss_coef", 0.001)), 0.0),
                      width=0 if width == held else width,
                      first_held=c.get("first_expert_held", 0)),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the per-layer tensors beside ``hf_kimi_linear``'s NORMS and MLP, under
# model.layers.N.: a Gated DeltaNet mixer ...
GDN_QKVZ = "linear_attn.in_proj_qkvz.weight"
GDN_BA = "linear_attn.in_proj_ba.weight"
GDN_CONV = "linear_attn.conv1d.weight"
GDN_OUT = "linear_attn.out_proj.weight"
GDN_VECTORS = {"gdn_dt_bias": "linear_attn.dt_bias",
               "gdn_A_log": "linear_attn.A_log",
               "gdn_norm": "linear_attn.norm.weight"}
# ... a gated-attention mixer ...
ATTN_Q, ATTN_K, ATTN_V, ATTN_O = (f"self_attn.{n}_proj.weight"
                                  for n in "qkvo")
ATTN_NORMS = {"q_norm": "self_attn.q_norm.weight",
              "k_norm": "self_attn.k_norm.weight"}
# ... and the expert block
ROUTER = "mlp.gate.weight"
SHARED_GATE = "mlp.shared_expert_gate.weight"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"mlp.experts.{e}.{MLP[w]}")


def shared_name(i, w):
    """Layer ``i``'s shared expert, ``w`` a key of MLP."""
    return hf_name(i, f"mlp.shared_expert.{MLP[w]}")


def qkvz_columns(m: GDNConfig):
    """The columns of HF's ``in_proj_qkvz`` output (a key head [q | k | v of
    its r value heads | z of them], the key heads side by side) in the
    trunk's order: [every head's q | every k | every v | every z]."""
    r = m.n_v_heads // m.n_k_heads
    K, rV = m.k_dim, r * m.v_dim
    cols = np.arange(m.conv_dim + m.v_inner).reshape(m.n_k_heads, -1)
    return np.concatenate([cols[:, a:b].reshape(-1) for a, b in (
        (0, K), (K, 2 * K), (2 * K, 2 * K + rV), (2 * K + rV, 2 * K + 2 * rV))])


def ba_columns(m: GDNConfig):
    """The columns of HF's ``in_proj_ba`` output (a key head [b of its r value
    heads | a of them]) in the trunk's order: [every head's b | every a]."""
    r = m.n_v_heads // m.n_k_heads
    cols = np.arange(2 * m.n_v_heads).reshape(m.n_k_heads, 2 * r)
    return np.concatenate([cols[:, :r].reshape(-1), cols[:, r:].reshape(-1)])


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``Qwen3NextForCausalLM.state_dict()`` names, with or
    without the ``model.`` scope; numpy or jax arrays; an expert's index the
    model's) -> the trunk's params: one stacked dict a run of ``layer_runs``.
    ``xp=jnp`` keeps device arrays on the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items()}
    D, first, E = cfg.d_model, cfg.router.first_held, cfg.n_experts
    nh, hd = cfg.n_heads, cfg.head_dim
    runs = []
    for kind, layers in run_layers(cfg):
        stack = lambda part, f=lambda w: w: xp.stack(
            [f(sd[hf_name(i, part)]) for i in layers])
        n = len(layers)
        blocks = {}
        for name, part in NORMS.items():
            blocks[name] = stack(part)
            blocks[name[:-len("scale")] + "bias"] = xp.zeros(
                (n, D), xp.float32)                  # unused (rmsnorm)
        if mixer_of(kind) == "gdn":
            blocks["gdn_wqkvz"] = stack(
                GDN_QKVZ, lambda w: w.T[:, qkvz_columns(cfg.gdn)])
            blocks["gdn_wba"] = stack(
                GDN_BA, lambda w: w.T[:, ba_columns(cfg.gdn)])
            blocks["gdn_conv"] = stack(
                GDN_CONV, lambda w: w.reshape(w.shape[0], -1).T)
            blocks["gdn_wo"] = stack(GDN_OUT, lambda w: w.T)
            for name, part in GDN_VECTORS.items():
                blocks[name] = stack(part)
        else:
            # a head's rows of q_proj: its hd query rows, then its hd gate's
            halves = lambda w: w.reshape(nh, 2, hd, D)
            blocks["wqkv"] = xp.stack([xp.concatenate(
                [halves(sd[hf_name(i, ATTN_Q)])[:, 0].reshape(nh * hd, D).T,
                 sd[hf_name(i, ATTN_K)].T, sd[hf_name(i, ATTN_V)].T], -1)
                for i in layers])
            blocks["wg"] = stack(
                ATTN_Q, lambda w: halves(w)[:, 1].reshape(nh * hd, D).T)
            blocks["wo"] = stack(ATTN_O, lambda w: w.T)
            for name, part in ATTN_NORMS.items():
                blocks[name] = stack(part)
        for w in MLP:
            blocks[w] = xp.stack([xp.stack(
                [sd[expert_name(i, first + e, w)].T for e in range(E)])
                for i in layers])
        blocks["router"] = stack(ROUTER, lambda w: w.T)
        blocks["b1"] = xp.zeros((n, E, cfg.d_ff_expert), xp.float32)
        blocks["b2"] = xp.zeros((n, E, D), xp.float32)   # unused (swiglu)
        for name, w in SHARED.items():
            blocks[name] = xp.stack([sd[shared_name(i, w)].T for i in layers])
        blocks["wsg"] = stack(SHARED_GATE, lambda w: w.T)
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
    first, m = cfg.router.first_held, cfg.gdn
    nh, hd, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    xp = np if isinstance(params["embed"], np.ndarray) else jnp
    sd = {EMBED: params["embed"], FINAL_NORM: params["lnf_scale"],
          HEAD: params["embed"] if cfg.tied_head else params["head"].T}
    for (kind, layers), b in zip(run_layers(cfg),
                                 run_blocks(cfg, params["blocks"])):
        for j, i in enumerate(layers):
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            if mixer_of(kind) == "gdn":
                sd[hf_name(i, GDN_QKVZ)] = b["gdn_wqkvz"][j][
                    :, np.argsort(qkvz_columns(m))].T
                sd[hf_name(i, GDN_BA)] = b["gdn_wba"][j][
                    :, np.argsort(ba_columns(m))].T
                sd[hf_name(i, GDN_CONV)] = b["gdn_conv"][j].T[:, None, :]
                sd[hf_name(i, GDN_OUT)] = b["gdn_wo"][j].T
                for name, part in GDN_VECTORS.items():
                    sd[hf_name(i, part)] = b[name][j]
            else:
                q, k, v = (b["wqkv"][j][:, a:z].T for a, z in (
                    (0, nh * hd), (nh * hd, (nh + cfg.kv_heads) * hd),
                    ((nh + cfg.kv_heads) * hd, None)))
                heads = lambda w: w.reshape(nh, 1, hd, D)
                # a head's query rows beside its gate's
                sd[hf_name(i, ATTN_Q)] = xp.concatenate(
                    [heads(q), heads(b["wg"][j].T)], 1).reshape(
                        2 * nh * hd, D)
                sd[hf_name(i, ATTN_K)], sd[hf_name(i, ATTN_V)] = k, v
                sd[hf_name(i, ATTN_O)] = b["wo"][j].T
                for name, part in ATTN_NORMS.items():
                    sd[hf_name(i, part)] = b[name][j]
            for e in range(cfg.n_experts):
                for w in MLP:
                    sd[expert_name(i, first + e, w)] = b[w][j, e].T
            sd[hf_name(i, ROUTER)] = b["router"][j].T
            for name, w in SHARED.items():
                sd[shared_name(i, w)] = b[name][j].T
            sd[hf_name(i, SHARED_GATE)] = b["wsg"][j].T
    return sd
