"""HuggingFace Laguna import: the flagship trunk's dialect with window and
full attention in one stack.

``laguna`` (poolside/Laguna-XS.2, 33B-A3B; Laguna-S-2.1) is a pre-norm
RMSNorm decoder with no bias anywhere and an untied head. ``layer_types``
names each layer's mixer, grouped-query attention on ``num_key_value_heads``
k/v heads of ``head_dim`` columns either way:

- ``full_attention`` (``transformer._attention``): causal softmax over every
  key; ``num_attention_heads_per_layer`` query heads (48); of a head's
  columns the first ``partial_rotary_factor`` x ``head_dim`` turn, rotate-half
  inside them, and the table's frequencies are YaRN's
  (``rope_parameters.full_attention``: ``transformer.YarnConfig``,
  ``rope_dim``), cos and sin times its ``attention_factor``.
- ``sliding_attention`` (``transformer._window``, the "window" kind): query t
  keeps the keys t - ``sliding_window`` < s <= t; its own head count (64);
  plain RoPE on all of a head's columns at its own theta
  (``transformer.WindowConfig``).

``gating`` puts a per-head sigmoid gate from the layer's normed input on
attention's output (``attn_gate``, leaf ``wg``; the reading of the key is the
config file's ``assumed``). ``mlp_layer_types`` names each layer's MLP half:
the leading ``dense`` layers a SwiGLU of ``intermediate_size``, the
``sparse`` ones ``num_experts`` SwiGLU experts of ``moe_intermediate_size``
of which a token takes ``num_experts_per_tok`` by DeepSeek-V3's router
(sigmoid scores, a selection bias, the picks' scores over their sum + 1e-20,
times ``moe_routed_scaling_factor``: ``transformer.Router``) beside ONE
shared SwiGLU of ``shared_expert_intermediate_size`` on every token.

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_lfm2`` has them: ``num_routed_experts`` (the router's width
where ``num_experts`` counts the experts HELD: the chip's share of an expert
layer) and ``first_expert_held``.

What the program cannot follow is refused BY NAME, here or where it would
run: decode (``generate._check_decode_args``: the plain attention block
only), the pipeline (``parallel/pipeline.py``: one kind of block a
stage), a window layer on a mesh that shards the sequence
(``transformer._attention_core``: the ring), a share on an ``ep`` mesh
(``transformer._moe_mlp``), a dense layer that is not leading and a head count
that differs inside a layer type (below).

Import is a pure weight relayout on a mapping of names to arrays (the names
are assumed: no ``transformers`` release carries the model): q|k|v fused into
``wqkv``, every Linear transposed to (in, out), the gate's ``g_proj`` to
``wg``, the held experts stacked on an expert axis; a checkpoint holds 48-
and 64-head layers side by side, each run of one kind its own stacked dict.
``benchmark/configs/laguna-xs.2/reference.py`` is the float32 reference the
tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import (ROUTER_BIAS, Router, TransformerConfig,
                          WindowConfig, YarnConfig, blocks_of_runs,
                          experts_of, mixer_of, run_blocks, run_layers)

KINDS = {"full_attention": "attention", "sliding_attention": "window"}


def _heads_of(c, layer_type, layers):
    """The ONE head count of the layers of ``layer_type``."""
    per_layer = c.get("num_attention_heads_per_layer") or [
        c["num_attention_heads"]] * layers
    counts = {h for h, t in zip(per_layer, c["layer_types"])
              if t == layer_type}
    if len(counts) > 1:
        raise NotImplementedError(
            f"num_attention_heads_per_layer: {sorted(counts)} among the "
            f"{layer_type} layers: a layer type has ONE head count (its "
            "run's stacked weights have one shape)")
    return counts.pop() if counts else c["num_attention_heads"]


def _rotary(c, layer_type):
    """-> (theta, rotary columns of a head, YarnConfig | None) of a layer
    type's ``rope_parameters``."""
    r = c["rope_parameters"][layer_type]
    hd = c["head_dim"]
    rot = int(round(r.get("partial_rotary_factor", 1.0) * hd))
    kind = r.get("rope_type", "default")
    if kind == "default":
        return float(r["rope_theta"]), rot, None
    if (kind != "yarn" or any(k in r for k in ("mscale", "mscale_all_dim"))
            or not r.get("truncate", True)):
        raise NotImplementedError(
            f"rope_parameters.{layer_type}={r!r}: default frequencies or "
            "YaRN's with the attention factor given (no mscale terms) and "
            "the ramp's ends whole frequencies (truncate true)")
    factor = float(r["factor"])
    return float(r["rope_theta"]), rot, YarnConfig(
        factor=factor,
        original_max_len=r["original_max_position_embeddings"],
        beta_fast=float(r.get("beta_fast", 32)),
        beta_slow=float(r.get("beta_slow", 1)),
        attention_factor=float(r.get("attention_factor")
                               or 0.1 * np.log(factor) + 1.0))


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Laguna ``config.json`` (a mapping, or a ``transformers`` config) ->
    TransformerConfig; refuses variants the trunk does not implement
    (importing them would run and be numerically wrong).
    ``router_bias_rate`` among the overrides sets ``Router.bias_rate`` (the
    rule that moves the selection bias is not a key of config.json)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    layers = c["num_hidden_layers"]
    types = list(c["layer_types"])
    mlps = list(c.get("mlp_layer_types") or ["sparse"] * layers)
    if len(types) != layers or len(mlps) != layers or set(types) - set(KINDS):
        raise NotImplementedError(
            f"layer_types={types}, mlp_layer_types={mlps}: one of "
            f"{sorted(KINDS)} and one of dense / sparse for each of the "
            f"{layers} layers")
    n_dense = mlps.index("sparse") if "sparse" in mlps else layers
    if "dense" in mlps[n_dense:]:
        raise NotImplementedError(
            f"mlp_layer_types={mlps}: a dense layer that is not leading (the "
            "trunk's dense MLP halves are the first `n_dense_layers`)")
    for key in ("attention_bias", "moe_apply_router_weight_on_input",
                "moe_router_logit_softcapping"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path (projection "
                "biases, the picks' weights on an expert's input, capped "
                "router logits)")
    if c.get("gating") not in (None, False, True, "per-head", "per_head"):
        raise NotImplementedError(
            f"gating={c['gating']!r}: a per-head gate on attention's "
            "output, or none")
    if c.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"hidden_act={c['hidden_act']!r}: SiLU")
    hd = c["head_dim"]
    heads = _heads_of(c, "full_attention", layers)
    theta, rot, yarn = _rotary(c, "full_attention")
    window = None
    if "sliding_attention" in types:
        w_theta, w_rot, w_yarn = _rotary(c, "sliding_attention")
        if w_rot != hd or w_yarn is not None:
            raise NotImplementedError(
                "rope_parameters.sliding_attention="
                f"{c['rope_parameters']['sliding_attention']!r}: a window "
                "layer turns all of a head's columns at default frequencies")
        window = WindowConfig(
            window=c["sliding_window"],
            n_heads=_heads_of(c, "sliding_attention", layers),
            rope_theta=w_theta)
    held = c["num_experts"]
    width = c.get("num_routed_experts", held)
    bias_rate = overrides.pop("router_bias_rate", 0.0)
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=c.get("num_key_value_heads") or 0, d_head=hd,
        n_layers=layers, d_ff=c["intermediate_size"],
        d_ff_expert=c["moe_intermediate_size"],
        d_ff_shared=c.get("shared_expert_intermediate_size", 0),
        max_seq_len=c["max_position_embeddings"],
        n_experts=held, n_experts_per_tok=c["num_experts_per_tok"],
        n_dense_layers=n_dense,
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm", rope=True,
        rope_theta=theta, rope_dim=0 if rot == hd else rot, rope_yarn=yarn,
        mlp="swiglu", use_pos_emb=False, causal=True,
        tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=tuple(KINDS[t] for t in types), window=window,
        attn_gate=bool(c.get("gating")),
        router=Router(
            score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
            scale=float(c.get("moe_routed_scaling_factor", 1.0)),
            aux_losses=False, bias_rate=bias_rate,
            width=0 if width == held else width,
            first_held=c.get("first_expert_held", 0)),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their (assumed) HF names under
# model.layers.N.: a norm's scale (1-D, as it is), a Linear (transposed to
# (in, out))
NORMS = {"ln1_scale": "input_layernorm.weight",
         "ln2_scale": "post_attention_layernorm.weight"}
QKV = tuple(f"self_attn.{x}_proj.weight" for x in "qkv")
ATTN_LINEARS = {"wo": "self_attn.o_proj.weight",
                "wg": "self_attn.g_proj.weight"}
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
    return hf_name(i, f"mlp.shared_expert.{MLP[w]}")


def _attn_linears(cfg: TransformerConfig):
    return {n: part for n, part in ATTN_LINEARS.items()
            if n != "wg" or cfg.attn_gate}


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (with or without the ``model.`` scope; numpy or jax
    arrays; an expert's index the model's) -> the trunk's params: one stacked
    dict a run of ``layer_runs``, a window run's at its own head count.
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
        blocks["wqkv"] = xp.stack([xp.concatenate(
            [sd[hf_name(i, part)].T for part in QKV], -1) for i in layers])
        for name, part in _attn_linears(cfg).items():
            blocks[name] = stack(part, lambda w: w.T)
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
    first, hd = cfg.router.first_held, cfg.head_dim
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for (kind, layers), b in zip(run_layers(cfg),
                                 run_blocks(cfg, params["blocks"])):
        heads = (cfg.window.n_heads if mixer_of(kind) == "window"
                 else cfg.n_heads)
        cuts = [heads * hd, (heads + cfg.kv_heads) * hd]
        for j, i in enumerate(layers):
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            for part, w in zip(QKV, (b["wqkv"][j][:, :cuts[0]],
                                     b["wqkv"][j][:, cuts[0]:cuts[1]],
                                     b["wqkv"][j][:, cuts[1]:])):
                sd[hf_name(i, part)] = w.T
            for name, part in _attn_linears(cfg).items():
                sd[hf_name(i, part)] = b[name][j].T
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
