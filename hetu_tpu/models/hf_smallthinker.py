"""HuggingFace SmallThinker import: the flagship trunk's dialect with a router
ahead of attention, ReGLU experts, and rotary window layers beside NoPE
global layers.

``smallthinker`` (PowerInfer/SmallThinker-21BA3B-Instruct, 21B-A3B;
SmallThinker-4BA0.6B; arXiv:2507.20984) is a pre-norm RMSNorm decoder with
no bias anywhere and an untied head. Every layer is grouped-query attention
(``num_attention_heads`` on ``num_key_value_heads`` heads of ``head_dim``
columns) and ``moe_num_primary_experts`` ReGLU experts of
``moe_ffn_hidden_size`` (``mlp="reglu"``: down(relu(gate u) * up u)), no
shared expert and no dense layer. Two lists name a layer's attention:

- ``sliding_window_layout[l]`` 1 (``transformer._window``, the "window"
  kind): query t keeps the keys t - ``sliding_window_size`` < s <= t; 0
  (``transformer._attention``): every causal key.
- ``rope_layout[l]`` 1: rotate-half RoPE at ``rope_theta`` on all of a head's
  columns; 0: NO position signal. The published models rotate exactly their
  window layers (``transformer.WindowConfig`` rotates by itself; ``rope``
  false leaves the "attention" layers NoPE).

The router reads the layer's INPUT, the residual stream before
``input_layernorm`` and before attention (``modeling_smallthinker.py``:
``router_input = hidden_states`` ahead of the norm; llama.cpp
``llm_build_smallthinker``: ``ffn_gate_inp`` on ``inpL``):
``Router.input`` "block". Its ``moe_num_active_primary_experts`` largest
logits are the picks and their weights softmax over THOSE logits
(``moe_primary_router_apply_softmax`` and ``norm_topk_prob`` true) = softmax
over all, then the picks' over their sum: ``Router(score="softmax",
normalize=True, normalize_eps=0.0)``. config.json names no auxiliary loss;
the trunk's balance and z losses run as OLMoE's do (``aux_losses``).

A CUT of the model is described by two keys of our own beside the published
ones, as ``hf_lfm2`` has them: ``num_routed_experts`` (the router's width
where ``moe_num_primary_experts`` counts the experts HELD: the chip's share
of an expert layer) and ``first_expert_held``. The two layouts stay WHOLE in
a cut file; the first ``num_hidden_layers`` entries are read, and they must be
whole periods of the layout.

What the program cannot follow is refused BY NAME, here or where it would
run: a config WITH secondary-expert keys (the report's hierarchical experts:
the published config.json has no key for them, and no equation of theirs is
written here), ``rope_scaling``, a router without the softmax over the
picks, a layout that is not whole periods, a window layer that does not
rotate or global layers that disagree on rotating; decode
(``generate._check_decode_args``), the pipeline (``parallel/pipeline.py``),
a window layer on a mesh that shards the sequence, a share or early routing
on an ``ep`` mesh (``transformer._routed_experts``).

Import is a pure weight relayout on a mapping of names to arrays (names as
the model's public code has them, remembered: no ``transformers`` release
here carries the model): q|k|v fused into ``wqkv``, every Linear transposed
to (in, out), ``block_sparse_moe.primary_router`` to ``router``, the held
experts' ``gate`` / ``up`` / ``down`` stacked on an expert axis into ``w1`` /
``w3`` / ``w2``. ``benchmark/configs/smallthinker-21b-a3b/reference.py`` is
the float32 reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import (Router, TransformerConfig, WindowConfig,
                          blocks_of_runs, run_blocks, run_layers)


def _period(layout):
    """The least p with layout[i] == layout[i % p] for every i."""
    n = len(layout)
    return next(p for p in range(1, n + 1)
                if all(layout[i] == layout[i % p] for i in range(n)))


def kinds_of(c):
    """The mixer kind ("window" | "attention") of each of the first
    ``num_hidden_layers`` layers, and whether the "attention" ones rotate."""
    layers = c["num_hidden_layers"]
    slide = list(c.get("sliding_window_layout") or [0] * layers)
    rope = list(c.get("rope_layout") or [1] * layers)
    if len(slide) < layers or len(rope) != len(slide):
        raise NotImplementedError(
            f"sliding_window_layout ({len(slide)} entries), rope_layout "
            f"({len(rope)}): one entry each for every one of the {layers} "
            "layers")
    period = _period(list(zip(slide, rope)))
    if layers % period:
        raise NotImplementedError(
            f"sliding_window_layout={slide[:period]}... cut to {layers} "
            f"layers: not whole periods of {period} layers (every kind of "
            "layer in its published ratio)")
    slide, rope = slide[:layers], rope[:layers]
    if any(s and not r for s, r in zip(slide, rope)):
        raise NotImplementedError(
            f"rope_layout={rope} beside sliding_window_layout={slide}: a "
            "window layer rotates (`transformer.WindowConfig`)")
    of_global = {bool(r) for s, r in zip(slide, rope) if not s}
    if len(of_global) > 1:
        raise NotImplementedError(
            f"rope_layout={rope}: the global layers rotate, or none does "
            "(one `rope` flag for the \"attention\" kind)")
    return (tuple("window" if s else "attention" for s in slide),
            bool(of_global and of_global.pop()))


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A SmallThinker ``config.json`` (a mapping, or a ``transformers``
    config) -> TransformerConfig; refuses variants the trunk does not
    implement (importing them would run and be numerically wrong)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    secondary = sorted(k for k in c if "secondary" in k)
    if secondary:
        raise NotImplementedError(
            f"{secondary}: secondary experts (the hierarchical MLP of the "
            "report) have no path in the trunk and no equation here; the "
            "published config.json has no such key")
    if c.get("rope_scaling"):
        raise NotImplementedError(
            f"rope_scaling={c['rope_scaling']!r}: a window layer's table has "
            "default frequencies")
    if not (c.get("moe_primary_router_apply_softmax", True)
            and c.get("norm_topk_prob", True)):
        raise NotImplementedError(
            "moe_primary_router_apply_softmax="
            f"{c.get('moe_primary_router_apply_softmax')!r}, norm_topk_prob="
            f"{c.get('norm_topk_prob')!r}: the picks' weights are softmax "
            "over the picks' logits (both true)")
    kinds, global_rope = kinds_of(c)
    heads, hd = c["num_attention_heads"], c["head_dim"]
    kv_heads = c.get("num_key_value_heads") or heads
    theta = float(c.get("rope_theta", 10000.0))
    held = c["moe_num_primary_experts"]
    width = c.get("num_routed_experts", held)
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads, d_head=hd,
        n_layers=c["num_hidden_layers"], d_ff=c["moe_ffn_hidden_size"],
        d_ff_expert=c["moe_ffn_hidden_size"],
        max_seq_len=c["max_position_embeddings"],
        n_experts=held,
        n_experts_per_tok=c["moe_num_active_primary_experts"],
        ln_eps=c.get("rms_norm_eps", 1e-6), norm="rmsnorm",
        rope=global_rope, rope_theta=theta, mlp="reglu", use_pos_emb=False,
        causal=True, tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=kinds,
        window=(WindowConfig(window=c["sliding_window_size"], n_heads=heads,
                             rope_theta=theta)
                if "window" in kinds else None),
        router=Router(score="softmax", normalize=True, normalize_eps=0.0,
                      aux_losses=True, input="block",
                      width=0 if width == held else width,
                      first_held=c.get("first_expert_held", 0)),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.: a
# norm's scale (1-D, as it is), a Linear (transposed to (in, out))
NORMS = {"ln1_scale": "input_layernorm.weight",
         "ln2_scale": "post_attention_layernorm.weight"}
QKV = tuple(f"self_attn.{x}_proj.weight" for x in "qkv")
WO = "self_attn.o_proj.weight"
MLP = {"w1": "gate.weight", "w3": "up.weight", "w2": "down.weight"}
ROUTER = "block_sparse_moe.primary_router.weight"
EMBED, FINAL_NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                           "lm_head.weight")


def hf_name(i, part):
    """``model.layers.<i>.<part>``."""
    return f"model.layers.{i}.{part}"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"block_sparse_moe.experts.{e}.{MLP[w]}")


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (with or without the ``model.`` scope; numpy or jax
    arrays; an expert's index the model's) -> the trunk's params: one stacked
    dict a run of ``layer_runs``. ``xp=jnp`` keeps device arrays on the
    device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items()}
    D, E, first = cfg.d_model, cfg.n_experts, cfg.router.first_held
    F = cfg.d_ff_expert or cfg.d_ff
    runs = []
    for _, layers in run_layers(cfg):
        stack = lambda part, f=lambda w: w: xp.stack(
            [f(sd[hf_name(i, part)]) for i in layers])
        n = len(layers)
        blocks = {}
        for name, part in NORMS.items():
            blocks[name] = stack(part)
            blocks[name[:-len("scale")] + "bias"] = xp.zeros(
                (n, D), xp.float32)                  # unused (rmsnorm)
        blocks["wqkv"] = xp.stack([xp.concatenate(
            [sd[hf_name(i, part)].T for part in QKV], -1) for i in layers])
        blocks["wo"] = stack(WO, lambda w: w.T)
        for w in MLP:
            blocks[w] = xp.stack([xp.stack(
                [sd[expert_name(i, first + e, w)].T for e in range(E)])
                for i in layers])
        blocks["router"] = stack(ROUTER, lambda w: w.T)
        blocks["b1"] = xp.zeros((n, E, F), xp.float32)   # unused (reglu)
        blocks["b2"] = xp.zeros((n, E, D), xp.float32)
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
    -> HF-named arrays (of whatever array type ``params`` holds). Of a share
    only the experts held exist, under the model's indices."""
    first, hd = cfg.router.first_held, cfg.head_dim
    cuts = [cfg.n_heads * hd, (cfg.n_heads + cfg.kv_heads) * hd]
    sd = {EMBED: params["embed"], FINAL_NORM: params["lnf_scale"],
          HEAD: params["embed"] if cfg.tied_head else params["head"].T}
    for (_, layers), b in zip(run_layers(cfg),
                              run_blocks(cfg, params["blocks"])):
        for j, i in enumerate(layers):
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            for part, w in zip(QKV, (b["wqkv"][j][:, :cuts[0]],
                                     b["wqkv"][j][:, cuts[0]:cuts[1]],
                                     b["wqkv"][j][:, cuts[1]:])):
                sd[hf_name(i, part)] = w.T
            sd[hf_name(i, WO)] = b["wo"][j].T
            for e in range(cfg.n_experts):
                for w in MLP:
                    sd[expert_name(i, first + e, w)] = b[w][j, e].T
            sd[hf_name(i, ROUTER)] = b["router"][j].T
    return sd
