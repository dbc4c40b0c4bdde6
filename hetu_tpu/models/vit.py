"""Vision Transformer on the flagship trunk — TPU-native, HF-compatible.

The reference's vision coverage is the CNN zoo plus a graph-API ViT
example (``examples/cnn/models/ViT.py``); this module is the FLAGSHIP
functional ViT: the same ``models/transformer.py`` trunk that runs the
LM/BERT paths (lax.scan over stacked layers, remat, Megatron tp specs,
flash attention for block-divisible sequence lengths) under a
patch-embedding front end. Architecturally HF ViT is the trunk's pre-LN
dialect with projection biases (``layernorm_before`` -> ln1 before
attention, ``layernorm_after`` -> ln2 before the MLP, erf gelu,
eps 1e-12, final LayerNorm -> lnf), so ``models/hf_vit.py`` loads
``transformers`` ViT checkpoints weight-for-weight.

Patch embedding is expressed as reshape + ONE matmul (the stride=P conv
is exactly a linear map over non-overlapping patches) — MXU-shaped, no
conv lowering needed at inference or training time.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry.tracing import SCOPE_FWD, SCOPE_OPT, scoped
from . import transformer as tfm


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    n_channels: int = 3
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    n_classes: int = 0          # 0 = no classification head
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"
    # canonical ViT dialect (HF-compatible); the trunk stays pre-LN
    ln_eps: float = 1e-12
    gelu_exact: bool = True

    @property
    def n_patches(self) -> int:
        assert self.image_size % self.patch_size == 0
        return (self.image_size // self.patch_size) ** 2

    @property
    def seq_len(self) -> int:
        return self.n_patches + 1   # + [CLS]

    def trunk(self) -> tfm.TransformerConfig:
        return tfm.TransformerConfig(
            vocab_size=2,            # unused (no token embedding)
            d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, d_ff=self.d_ff,
            max_seq_len=self.seq_len, dtype=self.dtype, remat=self.remat,
            attn_impl=self.attn_impl, causal=False,
            ln_eps=self.ln_eps, gelu_exact=self.gelu_exact,
            attn_proj_bias=True)


VIT_BASE = ViTConfig()


def init_params(rng, cfg: ViTConfig):
    D = cfg.d_model
    pdim = cfg.patch_size * cfg.patch_size * cfg.n_channels
    ks = jax.random.split(rng, 5)
    # blocks + final norm only: no dead token-embedding/pos/head tensors
    trunk = tfm.init_trunk_params(ks[0], cfg.trunk())
    params = {
        "patch_w": jax.random.normal(ks[1], (pdim, D), jnp.float32) * 0.02,
        "patch_b": jnp.zeros((D,), jnp.float32),
        "cls_token": jax.random.normal(ks[2], (1, 1, D), jnp.float32) * 0.02,
        "pos": jax.random.normal(ks[3], (cfg.seq_len, D), jnp.float32) * 0.02,
        "blocks": trunk["blocks"],
        "lnf_scale": trunk["lnf_scale"],
        "lnf_bias": trunk["lnf_bias"],
    }
    if cfg.n_classes:
        params["cls_w"] = jax.random.normal(
            ks[4], (D, cfg.n_classes), jnp.float32) * 0.02
        params["cls_b"] = jnp.zeros((cfg.n_classes,), jnp.float32)
    return params


def param_specs(cfg: ViTConfig):
    trunk = tfm.param_specs(cfg.trunk())
    specs = {
        "patch_w": P(None, "tp"),
        "patch_b": P("tp"),
        "cls_token": P(None, None, None),
        "pos": P(None, "tp"),
        "blocks": trunk["blocks"],
        "lnf_scale": P(None),
        "lnf_bias": P(None),
    }
    if cfg.n_classes:
        specs["cls_w"] = P(None, None)
        specs["cls_b"] = P(None)
    return specs


def patchify(images, cfg: ViTConfig):
    """images (B, C, H, W) -> (B, N, P*P*C) non-overlapping patches, each
    flattened in (c, ph, pw) order — the stride=P conv's receptive field
    layout, so HF conv kernels map onto ``patch_w`` by pure reshape."""
    B, C, H, W = images.shape
    Ps = cfg.patch_size
    x = images.reshape(B, C, H // Ps, Ps, W // Ps, Ps)
    x = x.transpose(0, 2, 4, 1, 3, 5)          # (B, gh, gw, C, Ps, Ps)
    return x.reshape(B, (H // Ps) * (W // Ps), C * Ps * Ps)


def encode(params, images, cfg: ViTConfig, mesh: Optional[Mesh] = None):
    """images (B, C, H, W) f32 -> final hidden states (B, N+1, D) after
    the final LayerNorm ([CLS] first, as in HF)."""
    B = images.shape[0]
    patches = patchify(images.astype(jnp.float32), cfg)
    h = (jnp.einsum("bnp,pd->bnd", patches,
                    params["patch_w"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32)
         + params["patch_b"]).astype(cfg.dtype)
    cls = jnp.broadcast_to(params["cls_token"].astype(cfg.dtype),
                           (B, 1, cfg.d_model))
    h = jnp.concatenate([cls, h], axis=1)
    h = h + params["pos"].astype(cfg.dtype)[None]
    h, _aux = tfm.encode(params, h, cfg.trunk(), mesh)
    return tfm._layer_norm(h, params["lnf_scale"], params["lnf_bias"],
                           cfg.ln_eps)


def classify_logits(params, images, cfg: ViTConfig, mesh=None):
    """-> (B, n_classes) f32 from the [CLS] hidden state (HF's
    ViTForImageClassification head: classifier on hidden[:, 0])."""
    h = encode(params, images, cfg, mesh)
    return (h[:, 0, :].astype(jnp.float32) @ params["cls_w"]
            + params["cls_b"])


def make_train_step(cfg: ViTConfig, lr: float = 1e-3,
                    mesh: Optional[Mesh] = None):
    """Jitted (params, opt_state, images, labels) ->
    (loss, acc, params, opt_state); AdamW fused in, buffers donated."""
    assert cfg.n_classes > 0, "training needs a classification head"

    def step(params, opt_state, images, labels):
        def loss_fn(params):
            logits = classify_logits(params, images, cfg, mesh)
            lp = jax.nn.log_softmax(logits, -1)
            loss = -jnp.mean(jnp.take_along_axis(
                lp, labels[:, None], -1)[:, 0])
            acc = jnp.mean((jnp.argmax(logits, -1) == labels)
                           .astype(jnp.float32))
            return loss, acc
        (loss, acc), grads = jax.value_and_grad(
            scoped(SCOPE_FWD, loss_fn), has_aux=True)(params)
        new_params, new_opt = scoped(SCOPE_OPT, tfm.adamw_update)(
            params, grads, opt_state, lr=lr)
        return loss, acc, new_params, new_opt

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          param_specs(cfg),
                          is_leaf=lambda x: isinstance(x, P))
    opt_shard = {"m": pshard, "v": pshard, "t": NamedSharding(mesh, P())}
    dshard = NamedSharding(mesh, P(("dp",)))
    scalar = NamedSharding(mesh, P())
    return jax.jit(step,
                   in_shardings=(pshard, opt_shard, dshard, dshard),
                   out_shardings=(scalar, scalar, pshard, opt_shard),
                   donate_argnums=(0, 1))


init_opt_state = tfm.init_opt_state
count_params = tfm.count_params
