"""BERT — bidirectional encoder pretraining (MLM + NSP), TPU-native.

The reference's NLP suite stops at a causal Transformer example plus the
WordPiece tokenizer and the pretrain data pipeline
(``python/hetu/tokenizers/bert_tokenizer.py``,
``examples/nlp/processBertData.py``); BASELINE.md names BERT-base pretrain
as a north-star config. This module completes the path: the encoder reuses
the flagship transformer trunk (``models/transformer.py``) with
``causal=False`` — same Pallas flash-attention kernel (bidirectional mask),
same lax.scan-over-stacked-layers + remat structure, same Megatron tp
sharding — and adds what BERT needs on top:

- token-type (segment) embeddings,
- MLM head: transform (dense+gelu+LN) then decode TIED to the token
  embedding, plus an output bias,
- NSP head on the pooled [CLS] vector,
- a fused pretrain step consuming exactly the data pipeline's rows
  (input_ids, input_mask, segment_ids, mlm_positions, mlm_ids, nsp_label).

Padded batches: ``input_mask`` becomes an additive attention bias on the
unfused path (the fused kernel assumes packed/dense batches, standard for
pretrain throughput).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry.tracing import (SCOPE_BLK_NORM, SCOPE_EMBED, SCOPE_FWD,
                                 SCOPE_HEAD, SCOPE_OPT, scoped)
from . import transformer as tfm


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_impl: str = "auto"
    # MLM loss through the fused Pallas linear+softmax-CE kernel
    # (kernels/fused_ce.py) — never materializes the (B*P, V) logits in
    # HBM. "auto": engaged on the single-program TPU path (under a mesh
    # the vocab-sharded decode rides the einsum form — GSPMD cannot
    # partition the custom kernel; off-TPU interpret mode would be slower
    # than the einsum). True forces it (tests), False disables.
    fused_mlm_ce: Any = "auto"
    # Architecture dialect. The default is the modern pre-LN trunk (the
    # training-throughput configuration the benchmark and tests use). ``hf()``
    # flips all four knobs to the canonical Devlin/HuggingFace BERT
    # architecture — post-LN blocks, embedding LayerNorm (the trunk's lnf
    # params, applied after the embedding sum instead of after the last
    # block), erf gelu, eps 1e-12, qkv/out projection biases — so
    # ``models/hf_bert.py`` can load HF checkpoints weight-for-weight.
    post_ln: bool = False
    ln_eps: float = 1e-5
    gelu_exact: bool = False
    attn_proj_bias: bool = False

    @classmethod
    def hf(cls, **overrides) -> "BertConfig":
        """The canonical (HuggingFace-compatible) BERT architecture."""
        overrides.setdefault("post_ln", True)
        overrides.setdefault("ln_eps", 1e-12)
        overrides.setdefault("gelu_exact", True)
        overrides.setdefault("attn_proj_bias", True)
        return cls(**overrides)

    def trunk(self) -> tfm.TransformerConfig:
        return tfm.TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_heads=self.n_heads, n_layers=self.n_layers, d_ff=self.d_ff,
            max_seq_len=self.max_seq_len, dtype=self.dtype, remat=self.remat,
            attn_impl=self.attn_impl, causal=False,
            post_ln=self.post_ln, ln_eps=self.ln_eps,
            gelu_exact=self.gelu_exact, attn_proj_bias=self.attn_proj_bias)


BERT_BASE = BertConfig()


def init_params(rng, cfg: BertConfig):
    D, V = cfg.d_model, cfg.vocab_size
    ks = jax.random.split(rng, 5)
    params = tfm.init_params(ks[0], cfg.trunk())
    del params["head"]   # MLM decode is TIED to the token embedding
    params["type_emb"] = jax.random.normal(
        ks[1], (cfg.type_vocab_size, D), jnp.float32) * 0.02
    params["mlm_dense"] = jax.random.normal(ks[2], (D, D), jnp.float32) * 0.02
    if cfg.attn_proj_bias:   # the "biases everywhere" (canonical) dialect
        params["mlm_dense_b"] = jnp.zeros((D,), jnp.float32)
    params["mlm_ln_scale"] = jnp.ones((D,), jnp.float32)
    params["mlm_ln_bias"] = jnp.zeros((D,), jnp.float32)
    params["mlm_bias"] = jnp.zeros((V,), jnp.float32)
    params["pool_w"] = jax.random.normal(ks[3], (D, D), jnp.float32) * 0.02
    params["pool_b"] = jnp.zeros((D,), jnp.float32)
    params["nsp_w"] = jax.random.normal(ks[4], (D, 2), jnp.float32) * 0.02
    params["nsp_b"] = jnp.zeros((2,), jnp.float32)
    return params


def param_specs(cfg: BertConfig):
    specs = tfm.param_specs(cfg.trunk())
    del specs["head"]
    if cfg.attn_proj_bias:
        specs["mlm_dense_b"] = P("tp")
    specs.update({
        "type_emb": P(None, None),
        "mlm_dense": P(None, "tp"),
        "mlm_ln_scale": P(None),
        "mlm_ln_bias": P(None),
        "mlm_bias": P("tp"),
        "pool_w": P(None, None),
        "pool_b": P(None),
        "nsp_w": P(None, None),
        "nsp_b": P(None),
    })
    return specs


def encode(params, input_ids, segment_ids, cfg: BertConfig,
           mesh: Optional[Mesh] = None, input_mask=None):
    """-> final hidden states (B, T, D). Pre-LN (default): trunk then the
    final LN (lnf). Post-LN (canonical BERT): lnf is the EMBEDDING
    LayerNorm — applied after the word+pos+type sum, as HF's
    ``BertEmbeddings.LayerNorm`` — and the trunk output is final as-is
    (each block already ends in a LayerNorm)."""
    trunk = cfg.trunk()
    h = tfm.embed_tokens(params, input_ids, trunk)
    with jax.named_scope(SCOPE_EMBED):
        h = h + params["type_emb"][segment_ids].astype(h.dtype)
        if cfg.post_ln:
            h = tfm._layer_norm(h, params["lnf_scale"], params["lnf_bias"],
                                cfg.ln_eps)
    attn_bias = None
    if input_mask is not None:
        # (B, T) 1/0 -> additive (B, 1, 1, T): padded keys get -1e30
        attn_bias = (1.0 - input_mask.astype(jnp.float32)
                     )[:, None, None, :] * -1e30
    h, _aux = tfm.encode(params, h, trunk, mesh, attn_bias)
    if cfg.post_ln:
        return h
    with jax.named_scope(SCOPE_BLK_NORM):
        return tfm._layer_norm(h, params["lnf_scale"], params["lnf_bias"],
                               cfg.ln_eps)


def mlm_transform(params, h, positions, cfg: BertConfig):
    """Gather (B, P) masked positions from h (B, T, D) and run the MLM
    transform (dense + bias + gelu + LN) -> (B, P, D). ``cfg`` is required:
    the gelu flavor and LN eps are dialect-dependent, and HF-imported
    params silently lose checkpoint parity under the wrong dialect."""
    g = jnp.take_along_axis(h, positions[..., None], axis=1)      # (B, P, D)
    g = jnp.einsum("bpd,de->bpe", g, params["mlm_dense"].astype(g.dtype),
                   preferred_element_type=jnp.float32).astype(g.dtype)
    if "mlm_dense_b" in params:
        g = g + params["mlm_dense_b"].astype(g.dtype)
    g = tfm._gelu(g, cfg)
    return tfm._layer_norm(g, params["mlm_ln_scale"], params["mlm_ln_bias"],
                           cfg.ln_eps)


def mlm_logits(params, h, positions, cfg: BertConfig):
    """MLM transform + decode tied to the token embedding -> (B, P, V) f32
    (the materializing form; the fused path skips this tensor entirely)."""
    g = mlm_transform(params, h, positions, cfg)
    logits = jnp.einsum("bpd,vd->bpv", g, params["embed"].astype(g.dtype),
                        preferred_element_type=jnp.float32)
    return logits + params["mlm_bias"]


def _pool(params, h):
    """Tanh-dense pooling of the [CLS] vector -> (B, D) f32."""
    return jnp.tanh(h[:, 0, :].astype(jnp.float32) @ params["pool_w"]
                    + params["pool_b"])


def nsp_logits(params, h):
    """Pooled [CLS] -> (B, 2) f32."""
    return _pool(params, h) @ params["nsp_w"] + params["nsp_b"]


def pretrain_loss(params, batch, cfg: BertConfig, mesh=None):
    """batch: dict with the data pipeline's rows. Returns (loss, (mlm, nsp))
    where mlm is averaged over real (weighted) prediction slots."""
    h = encode(params, batch["input_ids"], batch["segment_ids"], cfg, mesh,
               batch.get("input_mask"))
    with jax.named_scope(SCOPE_HEAD):
        return _pretrain_heads(params, h, batch, cfg, mesh)


def _pretrain_heads(params, h, batch, cfg: BertConfig, mesh):
    """``pretrain_loss`` from the final hidden states on: the MLM transform,
    the tied decoder (fused or einsum) and the NSP head, with both losses."""
    from ..kernels.fused_ce import should_fuse
    if should_fuse(cfg.fused_mlm_ce, mesh):
        from ..kernels.fused_ce import fused_linear_nll
        g = mlm_transform(params, h, batch["mlm_positions"], cfg)
        B, Pm, D = g.shape
        per_slot = fused_linear_nll(
            g.reshape(B * Pm, D),
            params["embed"].astype(g.dtype), params["mlm_bias"],
            batch["mlm_ids"].reshape(-1)).reshape(B, Pm)
    else:
        logits = mlm_logits(params, h, batch["mlm_positions"], cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        per_slot = -jnp.take_along_axis(
            logp, batch["mlm_ids"][..., None], -1)[..., 0]        # (B, P)
    w = batch["mlm_weights"].astype(jnp.float32)
    mlm = jnp.sum(per_slot * w) / jnp.maximum(jnp.sum(w), 1.0)
    nl = jax.nn.log_softmax(nsp_logits(params, h), -1)
    nsp = -jnp.mean(jnp.take_along_axis(nl, batch["nsp_label"][:, None],
                                        -1)[:, 0])
    return mlm + nsp, (mlm, nsp)


def make_pretrain_step(cfg: BertConfig, mesh: Optional[Mesh] = None,
                       lr: float = 1e-4):
    """Jitted (params, opt_state, batch) -> (loss, (mlm, nsp), params, opt);
    AdamW fused into the step, buffers donated, GSPMD dp/tp sharding.
    Without a mesh the state may be committed or not: ``tfm.StateStep``
    compiles one program either way."""

    def step(params, opt_state, batch):
        (loss, parts), grads = jax.value_and_grad(
            scoped(SCOPE_FWD, pretrain_loss), has_aux=True)(
                params, batch, cfg, mesh)
        new_params, new_opt = scoped(SCOPE_OPT, tfm.adamw_update)(
            params, grads, opt_state, lr=lr)
        return loss, parts, new_params, new_opt

    if mesh is None:
        return tfm.StateStep(step)
    specs = param_specs(cfg)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    opt_shard = {"m": pshard, "v": pshard, "t": NamedSharding(mesh, P())}
    # pytree-prefix sharding: every batch leaf is (B, ...), dp-sharded on
    # dim 0, whether or not the optional input_mask key is present
    dshard = NamedSharding(mesh, P(("dp",)))
    scalar = NamedSharding(mesh, P())
    return jax.jit(step,
                   in_shardings=(pshard, opt_shard, dshard),
                   out_shardings=(scalar, (scalar, scalar), pshard,
                                  opt_shard),
                   donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# fine-tuning: swap the pretrain heads for a task head on the pooled [CLS]
# (the standard BERT downstream recipe; no reference counterpart — its nlp
# suite stops at pretraining machinery)
# ---------------------------------------------------------------------------

def init_classifier_params(rng, cfg: BertConfig, n_classes: int,
                           pretrained=None):
    """Task params: the (possibly pretrained) encoder trunk + pooler, with a
    fresh classification head. ``pretrained``: params from
    ``init_params``/pretraining — trunk and pooler are reused, MLM/NSP
    heads dropped."""
    k_trunk, k_head = jax.random.split(rng)
    base = pretrained if pretrained is not None else init_params(k_trunk, cfg)
    # deep-copy reused leaves: the fine-tune step donates its params, and a
    # donated alias would invalidate the caller's pretrained tree
    params = {k: jax.tree.map(jnp.array, v) for k, v in base.items()
              if k not in ("mlm_dense", "mlm_dense_b", "mlm_ln_scale",
                           "mlm_ln_bias", "mlm_bias", "nsp_w", "nsp_b")}
    D = cfg.d_model
    params["cls_w"] = jax.random.normal(k_head, (D, n_classes),
                                        jnp.float32) * 0.02
    params["cls_b"] = jnp.zeros((n_classes,), jnp.float32)
    return params


def classify_logits(params, input_ids, segment_ids, cfg: BertConfig,
                    mesh=None, input_mask=None):
    h = encode(params, input_ids, segment_ids, cfg, mesh, input_mask)
    with jax.named_scope(SCOPE_HEAD):
        return _pool(params, h) @ params["cls_w"] + params["cls_b"]


def make_finetune_step(cfg: BertConfig, lr: float = 2e-5, mesh=None):
    """Jitted (params, opt_state, batch{input_ids, segment_ids, label,
    [input_mask]}) -> (loss, acc, params, opt), one program whether the
    state comes committed or not (``tfm.StateStep``)."""

    def step(params, opt_state, batch):
        def loss_fn(params):
            logits = classify_logits(params, batch["input_ids"],
                                     batch["segment_ids"], cfg, mesh,
                                     batch.get("input_mask"))
            lp = jax.nn.log_softmax(logits, -1)
            loss = -jnp.mean(jnp.take_along_axis(
                lp, batch["label"][:, None], -1)[:, 0])
            acc = jnp.mean((jnp.argmax(logits, -1) ==
                            batch["label"]).astype(jnp.float32))
            return loss, acc
        (loss, acc), grads = jax.value_and_grad(
            scoped(SCOPE_FWD, loss_fn), has_aux=True)(params)
        new_params, new_opt = scoped(SCOPE_OPT, tfm.adamw_update)(
            params, grads, opt_state, lr=lr)
        return loss, acc, new_params, new_opt

    return tfm.StateStep(step)


def batch_from_instances(instances):
    """Stack rows from the pretrain data pipeline
    (examples/nlp/processBertData.create_instances_from_document) into the
    batch dict ``pretrain_loss`` consumes. Prediction-slot weights are
    derived from the position padding (index 0 is always [CLS], which the
    masker never selects, so pos==0 marks a padded slot)."""
    cols = list(zip(*instances))
    ids, mask, seg, pos, mids = (np.stack(c).astype(np.int32)
                                 for c in cols[:5])
    return {"input_ids": ids, "input_mask": mask, "segment_ids": seg,
            "mlm_positions": pos, "mlm_ids": mids,
            "mlm_weights": (pos != 0).astype(np.float32),
            "nsp_label": np.asarray(cols[5], np.int32)}


init_opt_state = tfm.init_opt_state


count_params = tfm.count_params
