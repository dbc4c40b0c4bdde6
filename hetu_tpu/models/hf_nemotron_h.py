"""HuggingFace Nemotron-H import: the flagship trunk's dialect whose every
layer is ONE sublayer.

``nemotron_h`` (nvidia Nemotron-H, arXiv:2504.03624; the causal tower of
Nemotron-Labs-TwoTower-30B-A3B) is a pre-norm RMSNorm decoder with no bias
but the convolution's, no position embedding of any kind and an untied head.
``hybrid_override_pattern`` spells the stack, a letter a layer, and a layer
is x + f(RMSNorm(x)) with ONE norm (``transformer.single_sublayer``):

- ``M`` (``transformer._mamba`` without an MLP half): a Mamba-2 mixer of
  ``mamba_num_heads`` heads of ``mamba_head_dim`` channels on a state of
  ``ssm_state_size``, B and C in ``n_groups`` groups, a ``conv_kernel``-tap
  causal convolution, chunks of ``chunk_size``; the gated norm runs over
  each GROUP's d_inner / n_groups channels (``mamba_ssm``'s ``RMSNormGated``
  at ``group_size``, gate first: ``SSMConfig.norm_groups``) where HF
  Granite's runs over all; dt_bias starts as ``mamba_ssm``'s ``Mamba2``
  draws it (``time_step_min`` / ``_max`` / ``_floor``:
  ``SSMConfig.dt_init``).
- ``*`` (``transformer._attention`` without an MLP half): grouped-query
  attention, ``num_attention_heads`` on ``num_key_value_heads`` of
  ``head_dim`` columns (``d_head``: not hidden_size / heads), causal, NO
  rotary and no other position (the family's paper; ``rope_theta`` and
  ``partial_rotary_factor`` are the config class's defaults and unused).
- ``E`` (kind "mlp": the MLP half without a mixer): ``n_routed_experts``
  UNGATED experts, down(relu(up(x))^2) at ``moe_intermediate_size``
  (``mlp`` "relu2": two matrices an expert), ``num_experts_per_tok`` a token
  by DeepSeek-V3's router (sigmoid scores, ``e_score_correction_bias`` for
  the selection only, the picks' scores over their sum + 1e-20 under
  ``norm_topk_prob``, times ``routed_scaling_factor``) beside ONE shared
  expert of the same form at ``moe_shared_expert_intermediate_size`` on
  every token.
- ``-`` (a dense MLP layer) is refused: no published model of the family
  with experts has one, and the trunk's dense halves are leading layers.

Of the pattern the first ``num_hidden_layers`` letters are read, as
Granite's ``layer_types`` is. A CUT of the model is described by two keys of
our own beside the published ones, as ``hf_lfm2`` and ``hf_laguna`` have
them: ``num_routed_experts`` (the router's width where ``n_routed_experts``
counts the experts HELD: the chip's share of an expert layer) and
``first_expert_held``.

NOT SUPPORTED, refused by name here: the TwoTower model's second, DENOISER
tower (adaLN from a noise level, bidirectional attention inside a block,
conditioning on this tower) and its block-diffusion objective and decode:
``config.json`` holds not one key of them, so there is nothing to write
down; a config that carries such keys is refused rather than read without
them. Also refused: biases (``use_bias``, ``mlp_bias``, ``attention_bias``,
``mamba_proj_bias``), a convolution without its bias, group-limited routing
(``n_group`` / ``topk_group`` > 1), a clamp on dt (``time_step_limit``),
another activation than relu2 / SiLU. Decode (``generate._check_decode_
args``) and the pipeline (``parallel/pipeline.py``) refuse the dialect where
they would run it.

Import is a pure weight relayout on a mapping of names to arrays
(``backbone.layers.N.norm.weight``, ``backbone.layers.N.mixer.*``; the
names are nvidia's ``modeling_nemotron_h.py``'s as remembered, no
``transformers`` release here carries the model): q|k|v fused into ``wqkv``,
every Linear transposed to (in, out), the convolution's (channels, 1, K)
weight to (K, channels), the held experts stacked on an expert axis; each run
of one kind of layer its own stacked dict.
``benchmark/configs/nemotron-twotower-30b-a3b/reference.py`` is the float32
reference the tests and the benchmark compare against.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import (ROUTER_BIAS, Router, SSMConfig, TransformerConfig,
                          blocks_of_runs, mixer_of, run_blocks, run_layers)

# a letter of `hybrid_override_pattern` -> the trunk's name of the layer
KINDS = {"M": "mamba", "*": "attention", "E": "mlp"}
# keys no published config.json of the tower holds: the second tower's
DENOISER_KEYS = ("denoiser", "num_towers", "block_length", "block_size",
                 "noise_schedule", "adaln", "diffusion")


def pattern_of(c):
    """The first ``num_hidden_layers`` letters of the pattern."""
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]]


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Nemotron-H ``config.json`` (a mapping, or a ``transformers``
    config) -> TransformerConfig; refuses variants the trunk does not
    implement (importing them would run and be numerically wrong).
    ``router_bias_rate`` among the overrides sets ``Router.bias_rate`` (the
    rule that moves the selection bias is not a key of config.json)."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    tower = [k for k in c if any(k.startswith(d) for d in DENOISER_KEYS)]
    if tower:
        raise NotImplementedError(
            f"{tower}: the denoiser tower of a two-tower model (adaLN from a "
            "noise level, bidirectional attention inside a block, "
            "conditioning on the causal tower, a block-diffusion objective) "
            "is not supported: the trunk runs the causal nemotron_h tower "
            "under next-token cross-entropy alone")
    layers, D = c["num_hidden_layers"], c["hidden_size"]
    pattern = pattern_of(c)
    if len(pattern) != layers or set(pattern) - set(KINDS):
        raise NotImplementedError(
            f"hybrid_override_pattern[:{layers}]={pattern!r}: M (Mamba-2), * "
            "(attention) or E (experts) a layer; a '-' layer (a dense MLP) "
            "has no rule here")
    for key in ("use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias",
                "attention_dropout", "sliding_window"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path")
    if not c.get("use_conv_bias", True):
        raise NotImplementedError("use_conv_bias=False: the mixer's "
                                  "convolution has a trained bias")
    if (c.get("mlp_hidden_act", "relu2") != "relu2"
            or c.get("mamba_hidden_act", "silu") != "silu"):
        raise NotImplementedError(
            f"mlp_hidden_act={c.get('mlp_hidden_act')!r}, mamba_hidden_act="
            f"{c.get('mamba_hidden_act')!r}: relu2 experts, a SiLU mixer")
    if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise NotImplementedError(
            f"n_group={c.get('n_group')}, topk_group={c.get('topk_group')}: "
            "group-limited routing is not written (a token picks among all "
            "the experts)")
    if tuple(c.get("time_step_limit") or (0, None)) not in (
            (0, None), (0.0, None), (0.0, float("inf"))):
        raise NotImplementedError(
            f"time_step_limit={c['time_step_limit']!r}: dt is not clamped")
    if c.get("n_shared_experts", 1) not in (0, 1) or not c.get(
            "norm_topk_prob", True):
        raise NotImplementedError(
            f"n_shared_experts={c.get('n_shared_experts')}, norm_topk_prob="
            f"{c.get('norm_topk_prob')}: ONE shared expert or none, the "
            "picks' weights normalised")
    ssm = SSMConfig(
        n_heads=c["mamba_num_heads"], head_dim=c["mamba_head_dim"],
        d_state=c["ssm_state_size"], n_groups=c["n_groups"],
        d_conv=c["conv_kernel"], chunk=c["chunk_size"],
        norm_groups=c["n_groups"],
        dt_init=(c.get("time_step_min", 0.001), c.get("time_step_max", 0.1),
                 c.get("time_step_floor", 1e-4)))
    # d_inner is heads x head_dim (4,096 on a 2,688 stream): `expand` is the
    # config class's default and, like `rope_theta`, unused by the family
    heads = c["num_attention_heads"]
    kv_heads = c.get("num_key_value_heads") or heads
    held = c.get("n_routed_experts", 0) if "E" in pattern else 0
    width = c.get("num_routed_experts", held)
    bias_rate = overrides.pop("router_bias_rate", 0.0)
    shared = (c.get("moe_shared_expert_intermediate_size", 0)
              if held and c.get("n_shared_experts", 1) else 0)
    kw = dict(
        vocab_size=c["vocab_size"], d_model=D, n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads,
        d_head=c.get("head_dim") or 0, n_layers=layers,
        d_ff=c["intermediate_size"],
        d_ff_expert=c.get("moe_intermediate_size", 0), d_ff_shared=shared,
        max_seq_len=c["max_position_embeddings"],
        n_experts=held, n_experts_per_tok=c.get("num_experts_per_tok", 1),
        ln_eps=c.get("layer_norm_epsilon", 1e-5), norm="rmsnorm",
        rope=False, mlp="relu2", use_pos_emb=False, causal=True,
        tied_head=bool(c.get("tie_word_embeddings", False)),
        layer_types=tuple(KINDS[x] for x in pattern), single_sublayer=True,
        ssm=ssm,
        router=Router(
            score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
            scale=float(c.get("routed_scaling_factor", 1.0)),
            aux_losses=False, bias_rate=bias_rate,
            width=0 if width == held else width,
            first_held=c.get("first_expert_held", 0)) if held else Router(),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under
# backbone.layers.N.: the layer's ONE norm, then the mixer's own
NORM = "norm.weight"
MAMBA_VECTORS = {"conv_b": "mixer.conv1d.bias", "dt_bias": "mixer.dt_bias",
                 "A_log": "mixer.A_log", "D": "mixer.D",
                 "ssm_norm": "mixer.norm.weight"}
MAMBA_LINEARS = {"w_in": "mixer.in_proj.weight",
                 "w_out": "mixer.out_proj.weight"}
CONV_W = "mixer.conv1d.weight"       # (channels, 1, width) <-> (width, ch.)
QKV = tuple(f"mixer.{x}_proj.weight" for x in "qkv")
WO = "mixer.o_proj.weight"
MLP = {"w1": "up_proj.weight", "w2": "down_proj.weight"}
SHARED = {"ws1": "w1", "ws2": "w2"}
ROUTER, EXPERT_BIAS = ("mixer.gate.weight",
                       "mixer.gate.e_score_correction_bias")
EMBED, FINAL_NORM, HEAD = ("backbone.embeddings.weight",
                           "backbone.norm_f.weight", "lm_head.weight")


def hf_name(i, part):
    """``backbone.layers.<i>.<part>``."""
    return f"backbone.layers.{i}.{part}"


def expert_name(i, e, w):
    """Layer ``i``'s expert ``e`` (the MODEL's index), ``w`` a key of MLP."""
    return hf_name(i, f"mixer.experts.{e}.{MLP[w]}")


def shared_name(i, w):
    """Layer ``i``'s shared expert, ``w`` a key of MLP."""
    return hf_name(i, f"mixer.shared_experts.{MLP[w]}")


def _norm_leaf(kind):
    """The trunk's name of a layer's one norm: a mixer's is ``ln1``, the
    MLP half's ``ln2``."""
    return "ln2" if mixer_of(kind) == "mlp" else "ln1"


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (numpy or jax arrays; an expert's index the model's)
    -> the trunk's params: one stacked dict a run of ``layer_runs``.
    ``xp=jnp`` keeps device arrays on the device."""
    D, first = cfg.d_model, cfg.router.first_held
    runs = []
    for kind, layers in run_layers(cfg):
        stack = lambda part, f=lambda w: w: xp.stack(
            [f(sd[hf_name(i, part)]) for i in layers])
        n, mixer, ln = len(layers), mixer_of(kind), _norm_leaf(kind)
        blocks = {ln + "_scale": stack(NORM),
                  ln + "_bias": xp.zeros((n, D), xp.float32)}  # unused
        if mixer == "mamba":
            for name, part in MAMBA_VECTORS.items():
                blocks[name] = stack(part)
            for name, part in MAMBA_LINEARS.items():
                blocks[name] = stack(part, lambda w: w.T)
            blocks["conv_w"] = stack(CONV_W, lambda w: w[:, 0, :].T)
        elif mixer == "attention":
            blocks["wqkv"] = xp.stack([xp.concatenate(
                [sd[hf_name(i, part)].T for part in QKV], -1)
                for i in layers])
            blocks["wo"] = stack(WO, lambda w: w.T)
        else:
            for w in MLP:
                blocks[w] = xp.stack([xp.stack(
                    [sd[expert_name(i, first + e, w)].T
                     for e in range(cfg.n_experts)]) for i in layers])
            blocks["router"] = stack(ROUTER, lambda w: w.T)
            blocks[ROUTER_BIAS] = stack(EXPERT_BIAS)
            for name, w in (SHARED.items() if cfg.d_ff_shared else ()):
                blocks[name] = xp.stack(
                    [sd[shared_name(i, w)].T for i in layers])
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
    for (kind, layers), b in zip(run_layers(cfg),
                                 run_blocks(cfg, params["blocks"])):
        mixer = mixer_of(kind)
        for j, i in enumerate(layers):
            sd[hf_name(i, NORM)] = b[_norm_leaf(kind) + "_scale"][j]
            if mixer == "mamba":
                for name, part in MAMBA_VECTORS.items():
                    sd[hf_name(i, part)] = b[name][j]
                for name, part in MAMBA_LINEARS.items():
                    sd[hf_name(i, part)] = b[name][j].T
                sd[hf_name(i, CONV_W)] = b["conv_w"][j].T[:, None, :]
            elif mixer == "attention":
                w = b["wqkv"][j]
                for part, m in zip(QKV, (w[:, :cuts[0]],
                                         w[:, cuts[0]:cuts[1]],
                                         w[:, cuts[1]:])):
                    sd[hf_name(i, part)] = m.T
                sd[hf_name(i, WO)] = b["wo"][j].T
            else:
                for e in range(cfg.n_experts):
                    for w in MLP:
                        sd[expert_name(i, first + e, w)] = b[w][j, e].T
                sd[hf_name(i, ROUTER)] = b["router"][j].T
                sd[hf_name(i, EXPERT_BIAS)] = b[ROUTER_BIAS][j]
                for name, w in (SHARED.items() if cfg.d_ff_shared else ()):
                    sd[shared_name(i, w)] = b[name][j].T
    return sd
