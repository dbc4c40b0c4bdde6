"""Plain float32 reference of BERT pretraining's forward pass and loss
(Devlin et al. 2018; the HuggingFace `BertForPreTraining` layout): post-LN
blocks, embedding LayerNorm, erf GELU, biases on every projection, MLM
decode tied to the token embedding, NSP on the tanh-pooled [CLS].

Straightforward `jax.numpy`: no kernels, no scan, no recomputation, no
bfloat16, matmuls at "highest" precision (on a TPU a float32 matmul is
otherwise computed in bfloat16 passes). It reads the program's parameter
tree (blocks stacked on a leading layer axis; `wqkv` is q|k|v along its
last axis; `lnf_*` is the embedding LayerNorm) and nothing else of it.
Departure from the published model: no dropout, as in the system.
"""
import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def _nll(logits, targets):
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def _forward(params, batch, n_heads, eps):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    ids, seg = batch["input_ids"], batch["segment_ids"]
    B, T = ids.shape
    D = params["embed"].shape[1]
    hd = D // n_heads
    h = (f32(params["embed"])[ids] + f32(params["pos"])[:T]
         + f32(params["type_emb"])[seg])
    h = _ln(h, f32(params["lnf_scale"]), f32(params["lnf_bias"]), eps)
    key_bias = (1.0 - f32(batch["input_mask"]))[:, None, None, :] * -1e30
    blocks = params["blocks"]
    for i in range(blocks["wqkv"].shape[0]):
        p = {k: f32(v[i]) for k, v in blocks.items()}
        qkv = h @ p["wqkv"] + p["bqkv"]
        q, k, v = (t.reshape(B, T, n_heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(hd) + key_bias
        ctx = jax.nn.softmax(scores, -1) @ v
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
        h = _ln(h + ctx @ p["wo"] + p["bo"], p["ln1_scale"], p["ln1_bias"],
                eps)
        u = _gelu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        h = _ln(h + u, p["ln2_scale"], p["ln2_bias"], eps)

    g = jnp.take_along_axis(h, batch["mlm_positions"][..., None], axis=1)
    g = _gelu(g @ f32(params["mlm_dense"]) + f32(params["mlm_dense_b"]))
    g = _ln(g, f32(params["mlm_ln_scale"]), f32(params["mlm_ln_bias"]), eps)
    logits = g @ f32(params["embed"]).T + f32(params["mlm_bias"])
    w = f32(batch["mlm_weights"])
    mlm = jnp.sum(_nll(logits, batch["mlm_ids"]) * w) / jnp.maximum(
        jnp.sum(w), 1.0)
    pooled = jnp.tanh(h[:, 0, :] @ f32(params["pool_w"])
                      + f32(params["pool_b"]))
    nsp = jnp.mean(_nll(pooled @ f32(params["nsp_w"]) + f32(params["nsp_b"]),
                        batch["nsp_label"]))
    return mlm + nsp, mlm, nsp, h


def loss_and_hidden(params, batch, n_heads, eps):
    """(loss, mlm, nsp, final hidden states (B, T, D))."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(_forward, static_argnames=("n_heads", "eps"))(
            params, batch, n_heads=n_heads, eps=eps)
