"""HuggingFace Granite 4.0-H import: the flagship trunk's hybrid dialect.

``GraniteMoeHybridForCausalLM`` (IBM Granite 4.0-H, ``model_type``
``granitemoehybrid``; ``transformers``
``models/granitemoehybrid/modeling_granitemoehybrid.py``) is a pre-norm
RMSNorm decoder whose layers are of two kinds in ONE stack, named a layer by
``layer_types``:

- ``"mamba"``: a Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060;
  ``transformer._mamba``): in-projection to [z | x B C | dt], a causal
  depthwise convolution with bias and SiLU over [x B C], the selective
  state-space recurrence a head, a gated RMSNorm, the out-projection;
- ``"attention"``: grouped-query softmax attention WITHOUT position
  embeddings (``position_embedding_type`` ``"nope"``) at the softmax scale
  ``attention_multiplier`` (not 1/sqrt(head_dim));

each followed by the same SwiGLU MLP (``shared_mlp``; the dense models have
``num_local_experts`` 0 and no router). Four scalars ride along
(``transformer.Multipliers``): ``embedding_multiplier`` on the token
embeddings, ``residual_multiplier`` on every sublayer's output,
``attention_multiplier``, and ``logits_scaling`` dividing the logits of the
tied head.

Import is a pure weight relayout on a mapping of names to arrays.
``benchmark/configs/granite-4.0-h-micro/reference.py`` is the float32
reference the tests and the benchmark compare against; tier-1 holds that
reference to ``transformers``' own ``torch_forward``.
"""
from __future__ import annotations

from collections.abc import Mapping

import numpy as np
import jax.numpy as jnp

from .hf_common import np_f32, tree_to_jnp
from .transformer import (Multipliers, SSMConfig, TransformerConfig,
                          blocks_of_runs, layer_runs, run_blocks)


def config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """A Granite 4.0-H ``config.json`` (a mapping, or a ``transformers``
    config) -> TransformerConfig; refuses variants the trunk does not
    implement (importing them would run and be numerically wrong). Of
    ``layer_types`` the first ``num_hidden_layers`` entries are read."""
    c = (hf_config if isinstance(hf_config, Mapping)
         else hf_config.to_dict())
    if c.get("hidden_act", "silu") not in ("silu", "swish"):
        raise NotImplementedError(f"hidden_act={c['hidden_act']!r}: only silu")
    for key in ("num_local_experts", "attention_bias", "mamba_proj_bias",
                "rope_scaling", "attention_dropout"):
        if c.get(key):
            raise NotImplementedError(
                f"{key}={c[key]!r}: the trunk has no such path")
    if not c.get("mamba_conv_bias", True):
        raise NotImplementedError("mamba_conv_bias=False: the mixer's "
                                  "convolution has a trained bias")
    if c.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise NotImplementedError(
            f"normalization_function={c['normalization_function']!r}")
    if c.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError(
            f"position_embedding_type={c['position_embedding_type']!r}: "
            "the hybrid models' attention takes no positions")
    layers, D = c["num_hidden_layers"], c["hidden_size"]
    kinds = tuple(c["layer_types"][:layers])
    if len(kinds) != layers or set(kinds) - {"mamba", "attention"}:
        raise NotImplementedError(
            f"layer_types[:{layers}]={kinds}: 'mamba' | 'attention' a layer")
    ssm = SSMConfig(
        n_heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
        d_state=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
        d_conv=c["mamba_d_conv"], chunk=c["mamba_chunk_size"])
    if ssm.d_inner != c["mamba_expand"] * D:
        raise NotImplementedError(
            f"mamba_n_heads x mamba_d_head = {ssm.d_inner} != mamba_expand x "
            f"hidden_size = {c['mamba_expand'] * D}")
    heads = c["num_attention_heads"]
    kv_heads = c.get("num_key_value_heads") or heads
    kw = dict(
        vocab_size=c["vocab_size"], d_model=D, n_heads=heads,
        n_kv_heads=0 if kv_heads == heads else kv_heads, n_layers=layers,
        d_ff=c["shared_intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        ln_eps=c.get("rms_norm_eps", 1e-5), norm="rmsnorm",
        rope=False, mlp="swiglu", use_pos_emb=False,
        tied_head=bool(c.get("tie_word_embeddings", True)), causal=True,
        layer_types=kinds, ssm=ssm,
        multipliers=Multipliers(
            embedding=float(c.get("embedding_multiplier", 1.0)),
            residual=float(c.get("residual_multiplier", 1.0)),
            attention=float(c["attention_multiplier"]),
            logits=float(c.get("logits_scaling", 1.0))),
        dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


# the trunk's per-layer tensors and their HF names under model.layers.N.:
# a norm's scale (1-D, as it is), a Linear (transposed to (in, out))
NORMS = {"ln1_scale": "input_layernorm.weight",
         "ln2_scale": "post_attention_layernorm.weight"}
MAMBA_VECTORS = {"conv_b": "mamba.conv1d.bias", "dt_bias": "mamba.dt_bias",
                 "A_log": "mamba.A_log", "D": "mamba.D",
                 "ssm_norm": "mamba.norm.weight"}
MAMBA_LINEARS = {"w_in": "mamba.in_proj.weight",
                 "w_out": "mamba.out_proj.weight"}
CONV_W = "mamba.conv1d.weight"       # (channels, 1, width) <-> (width, ch.)
MLP_IN, MLP_OUT = ("shared_mlp.input_linear.weight",    # rows [gate | up]
                   "shared_mlp.output_linear.weight")


def hf_name(i, part):
    """``model.layers.<i>.<part>``."""
    return f"model.layers.{i}.{part}"


def _layers_of_runs(cfg: TransformerConfig):
    """[(kind, [the stack's layer indices of that run])] in order."""
    out, first = [], 0
    for kind, n in layer_runs(cfg):
        out.append((kind, list(range(first, first + n))))
        first += n
    return out


def params_from_state_dict(sd, cfg: TransformerConfig, xp=np):
    """HF-named arrays (``GraniteMoeHybridForCausalLM.state_dict()`` names,
    with or without the ``model.`` scope; numpy or jax arrays) -> the trunk's
    params: one stacked dict a run of ``layer_types``, q|k|v fused into
    ``wqkv``, the MLP's fused input cut into ``w1`` (gate) and ``w3`` (up),
    every Linear transposed to (in, out). ``xp=jnp`` keeps device arrays on
    the device."""
    sd = {(k if k.startswith(("model.", "lm_head.")) else "model." + k): v
          for k, v in sd.items()}
    D, F = cfg.d_model, cfg.d_ff
    runs = []
    for kind, layers in _layers_of_runs(cfg):
        stack = lambda part, f=lambda w: w: xp.stack(
            [f(sd[hf_name(i, part)]) for i in layers])
        n = len(layers)
        blocks = {
            "w1": stack(MLP_IN, lambda w: w[:F].T),
            "w3": stack(MLP_IN, lambda w: w[F:].T),
            "w2": stack(MLP_OUT, lambda w: w.T),
            "b1": xp.zeros((n, F), xp.float32),      # unused (swiglu)
            "b2": xp.zeros((n, D), xp.float32)}
        for name, part in NORMS.items():
            blocks[name] = stack(part)
            blocks[name[:-len("scale")] + "bias"] = xp.zeros(
                (n, D), xp.float32)                  # unused (rmsnorm)
        if kind == "mamba":
            for name, part in MAMBA_VECTORS.items():
                blocks[name] = stack(part)
            for name, part in MAMBA_LINEARS.items():
                blocks[name] = stack(part, lambda w: w.T)
            blocks["conv_w"] = stack(CONV_W, lambda w: w[:, 0, :].T)
        else:
            blocks["wqkv"] = xp.stack([xp.concatenate(
                [sd[hf_name(i, f"self_attn.{p}_proj.weight")].T
                 for p in "qkv"], axis=1) for i in layers])
            blocks["wo"] = stack("self_attn.o_proj.weight", lambda w: w.T)
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
    """The inverse relayout: params -> HF-named arrays (of whatever array
    type ``params`` holds)."""
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    xp = jnp if isinstance(params["embed"], jnp.ndarray) else np
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["lnf_scale"],
          "lm_head.weight": (params["embed"] if cfg.tied_head
                             else params["head"].T)}
    for (kind, layers), b in zip(_layers_of_runs(cfg),
                                 run_blocks(cfg, params["blocks"])):
        for j, i in enumerate(layers):
            sd[hf_name(i, MLP_IN)] = xp.concatenate(
                [b["w1"][j].T, b["w3"][j].T], axis=0)
            sd[hf_name(i, MLP_OUT)] = b["w2"][j].T
            for name, part in NORMS.items():
                sd[hf_name(i, part)] = b[name][j]
            if kind == "mamba":
                for name, part in MAMBA_VECTORS.items():
                    sd[hf_name(i, part)] = b[name][j]
                for name, part in MAMBA_LINEARS.items():
                    sd[hf_name(i, part)] = b[name][j].T
                sd[hf_name(i, CONV_W)] = b["conv_w"][j].T[:, None, :]
            else:
                wqkv = b["wqkv"][j]
                for p, cols in (("q", wqkv[:, :nq]),
                                ("k", wqkv[:, nq:nq + nkv]),
                                ("v", wqkv[:, nq + nkv:])):
                    sd[hf_name(i, f"self_attn.{p}_proj.weight")] = cols.T
                sd[hf_name(i, "self_attn.o_proj.weight")] = b["wo"][j].T
    return sd
