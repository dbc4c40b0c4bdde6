"""Shared helpers for the HuggingFace checkpoint importers
(``hf_bert.py``, ``hf_gpt2.py``) — one place for the torch->numpy->jnp
conversion so dtype handling cannot drift between model families; and what
the importers of DeepSeek-V3's dialect share (``hf_deepseek_v3.py``,
``hf_kimi_linear.py``): latent attention's sizes, names and relayout, the
sigmoid router with a selection bias, each with its refusals by name."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def np_f32(t) -> np.ndarray:
    """torch tensor -> float32 numpy (covers f16/bf16 checkpoints)."""
    return t.detach().to("cpu").float().numpy()


def tree_to_jnp(params: dict) -> dict:
    """A params tree (``blocks`` one stacked dict, or a tuple of them, one a
    run of kinds) -> jnp arrays."""
    return jax.tree.map(jnp.asarray, params)


def load_into_hf(sd: dict, model, scope: str, skip_target=lambda k: False,
                 droppable=()):
    """Load an unscoped HF-named numpy state dict into a live transformers
    ``model``, shared by both exporters so the validation cannot drift.

    Validates BOTH directions, so a silently partial deploy cannot happen:
    - every exported key must land in the target (an unmatched trunk key —
      e.g. ``encoder.layer.8.*`` against a 6-layer model — is an
      architecture mismatch and raises; keys under a ``droppable`` prefix,
      i.e. heads the target model class does not have, may be dropped);
    - every target key must be filled (except ``skip_target`` buffers);
    - shape mismatches raise inside ``load_state_dict`` itself.
    """
    import torch
    target = model.state_dict()
    scoped, unmatched = {}, []
    for k, v in sd.items():
        name = (k if k in target
                else scope + k if scope + k in target else None)
        if name is None:
            if not k.startswith(tuple(droppable)):
                unmatched.append(k)
            continue
        # owning copy: jax->numpy views are read-only, torch warns on them
        scoped[name] = torch.tensor(np.asarray(v))
    if unmatched:
        raise ValueError(
            f"export keys with no slot in the target model (architecture "
            f"mismatch?): {unmatched[:6]}{'...' if len(unmatched) > 6 else ''}")
    missing = [k for k in target if k not in scoped and not skip_target(k)]
    if missing:
        raise ValueError(f"export cannot fill target keys: {missing}")
    model.load_state_dict(scoped, strict=False)
    return model


# latent attention (DeepSeek-V2's MLA, ``transformer._mla``) under
# ``model.layers.<i>.``: a Linear (transposed to (in, out)), the latent's
# norm (as it is), ``kv_b_proj`` (transposed, its columns regrouped)
MLA_LINEARS = {"wq": "self_attn.q_proj.weight",
               "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
               "wo": "self_attn.o_proj.weight"}
MLA_KV_NORM = "self_attn.kv_a_layernorm.weight"
MLA_KV_B = "self_attn.kv_b_proj.weight"


def mla_from_hf(c, rotate=True):
    """The latent-attention keys of a config mapping -> ``MLAConfig``;
    refuses by name what ``transformer._mla`` does not compute."""
    from .transformer import MLAConfig
    if c.get("q_lora_rank"):
        raise NotImplementedError(
            f"q_lora_rank={c['q_lora_rank']!r}: the trunk has no low-rank q "
            "projection")
    heads = c["num_attention_heads"]
    if c.get("num_key_value_heads", heads) != heads:
        raise NotImplementedError(
            f"num_key_value_heads={c['num_key_value_heads']}: latent "
            "attention's keys and values are every head's own")
    mla = MLAConfig(kv_rank=c["kv_lora_rank"],
                    nope_dim=c["qk_nope_head_dim"],
                    rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                    rotate=rotate)
    if c.get("qk_head_dim", mla.qk_dim) != mla.qk_dim:
        raise NotImplementedError(
            f"qk_head_dim={c['qk_head_dim']} is not qk_nope_head_dim + "
            f"qk_rope_head_dim = {mla.qk_dim}")
    return mla


def kv_b_columns(cfg):
    """The columns of HF's ``kv_b_proj`` output (a head [k_nope | v], the
    heads side by side) in the trunk's order: [every head's k_nope | every
    head's v]."""
    m, nh = cfg.mla, cfg.n_heads
    cols = np.arange(nh * (m.nope_dim + m.v_dim)).reshape(nh, -1)
    return np.concatenate([cols[:, :m.nope_dim].reshape(-1),
                           cols[:, m.nope_dim:].reshape(-1)])


def mla_leaves_from_hf(stack, cfg):
    """A run's latent-attention leaves; ``stack(part, f)`` stacks f(the
    run's layers' tensors called ``part``)."""
    cols = kv_b_columns(cfg)
    return {**{name: stack(part, lambda w: w.T)
               for name, part in MLA_LINEARS.items()},
            "kv_norm": stack(MLA_KV_NORM, lambda w: w),
            "wkv_b": stack(MLA_KV_B, lambda w: w.T[:, cols])}


def mla_leaves_to_hf(b, j, cfg):
    """The inverse for layer ``j`` of a run's stacked leaves ``b`` -> {part
    under ``model.layers.<i>.``: tensor}."""
    back = np.argsort(kv_b_columns(cfg))
    return {**{part: b[name][j].T for name, part in MLA_LINEARS.items()},
            MLA_KV_NORM: b["kv_norm"][j],
            MLA_KV_B: b["wkv_b"][j][:, back].T}


def sigmoid_router_from_hf(c, *, groups, held, normalize, bias_rate):
    """DeepSeek-V3's router (sigmoid scores, the picks by score + a selection
    bias that enters nowhere else, their scores over their sum + 1e-20, times
    ``routed_scaling_factor``) -> ``transformer.Router``, a share where
    ``num_routed_experts`` says the router is wider than the ``held`` experts.
    ``groups``: the config's two keys of group-limited selection, refused by
    name unless both are 1."""
    from .transformer import Router
    if any(c.get(key, 1) != 1 for key in groups):
        raise NotImplementedError(
            ", ".join(f"{key}={c.get(key)}" for key in groups)
            + ": group-limited selection (the picks from the best groups of "
            "experts only) is not written; 1 and 1 make it the identity")
    width = c.get("num_routed_experts", held)
    return Router(score="sigmoid", bias=True, normalize=bool(normalize),
                  normalize_eps=1e-20,
                  scale=float(c.get("routed_scaling_factor", 1.0)),
                  aux_losses=False, bias_rate=bias_rate,
                  width=0 if width == held else width,
                  first_held=c.get("first_expert_held", 0))
