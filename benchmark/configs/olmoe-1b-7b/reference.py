"""Plain float32 reference of OLMoE's forward pass, training loss and
gradients (Muennighoff et al. 2024, arXiv:2409.02060; the HuggingFace
`OlmoeForCausalLM` layer and its parameter names).

Per layer, for hidden states x (B, T, D):
  a = RMSNorm(x; input_layernorm); q = RMSNorm(a Wq; q_norm) and
  k = RMSNorm(a Wk; k_norm), each over the WHOLE projection before the split
  into heads; v = a Wv; RoPE (rotate_half, theta) on q and k; causal
  softmax(q k^T / sqrt(hd)) v; x = x + (.) Wo.
  m = RMSNorm(x; post_attention_layernorm); r = m Wr; p = softmax(r) over all
  experts; the k largest p are the token's experts and their UNnormalised p
  the weights; x = x + sum_e p_e W_down,e(silu(W_gate,e m) * W_up,e m).
After the last layer RMSNorm(x; norm) and the untied head. Loss = next-token
cross-entropy + `router_aux_loss_coef` * sum over layers of
E * sum_e f_e P_e (f_e picks of expert e over tokens, P_e its mean
probability) + `router_z_loss_coef` * sum over layers of the mean over tokens
of logsumexp(r)^2.

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over the
layers, every expert computed on every token and masked by the picks'
weights, the top k found by k argmaxes: no sort, no gather, no grouped
matmul, no kernel, no scan, no recomputation. The experts are one batched
matmul a projection over a stacked expert axis and not a Python loop of 64:
a float32 "highest" matmul costs the TPU's compiler seconds apiece, and the
unrolled loop (197 of them) took it 72 s, longer than a benchmark run may
last, against 31 s for this form (both compiled for a described v5e).
Departures from the HuggingFace code: its `load_balancing_loss_func` pools
the tokens of all layers into one f and P (the same number at one layer); it
has no z-loss (the paper's training code has).
"""
import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, H, T, hd), HF rotate_half convention."""
    hd, T = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], -1)
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], -1)
    rotated = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rotated * sin


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> (values, indices)."""
    values, indices = [], []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        values.append(jnp.take_along_axis(p, i[:, None], -1)[:, 0])
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -1.0, p)
    return jnp.stack(values, -1), jnp.stack(indices, -1)


def _forward(sd, tokens, targets, config):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    H, E = config["num_attention_heads"], config["num_experts"]
    k, eps = config["num_experts_per_tok"], config["rms_norm_eps"]
    B, T = tokens.shape
    x = f32(sd["model.embed_tokens.weight"])[tokens]
    D = x.shape[-1]
    hd = D // H
    causal = jnp.tril(jnp.ones((T, T), bool))
    balance = z = 0.0
    experts = []
    for i in range(config["num_hidden_layers"]):
        w = lambda name: f32(sd[f"model.layers.{i}.{name}.weight"])
        a = _rms(x, w("input_layernorm"), eps)
        q = _rms(a @ w("self_attn.q_proj").T, w("self_attn.q_norm"), eps)
        kk = _rms(a @ w("self_attn.k_proj").T, w("self_attn.k_norm"), eps)
        v = a @ w("self_attn.v_proj").T
        q, kk, v = (t.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                    for t in (q, kk, v))
        q, kk = _rope(q, config["rope_theta"]), _rope(kk, config["rope_theta"])
        scores = q @ kk.transpose(0, 1, 3, 2) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(B, T, D)
        x = x + ctx @ w("self_attn.o_proj").T

        m = _rms(x, w("post_attention_layernorm"), eps).reshape(B * T, D)
        r = m @ w("mlp.gate").T
        p = jax.nn.softmax(r, -1)
        top_p, top_e = _top_k(p, k)
        # every expert on every token, masked by the picks' weights
        gate, up, down = (jnp.stack([w(f"mlp.experts.{e}.{proj}_proj")
                                     for e in range(E)])
                          for proj in ("gate", "up", "down"))
        weight = jnp.sum(jnp.where(top_e[None] == jnp.arange(E)[:, None, None],
                                   top_p[None], 0.0), -1)            # (E, S)
        u = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
             * jnp.einsum("sd,efd->esf", m, up))
        y = jnp.einsum("es,esf,edf->sd", weight, u, down)
        x = x + y.reshape(B, T, D)
        f = jnp.sum(jax.nn.one_hot(top_e, E), (0, 1)) / (B * T)
        balance = balance + E * jnp.sum(f * jnp.mean(p, 0))
        z = z + jnp.mean(jax.scipy.special.logsumexp(r, -1) ** 2)
        experts.append(top_e)
    logits = _rms(x, f32(sd["model.norm.weight"]), eps) @ f32(
        sd["lm_head.weight"]).T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    ce = jnp.mean(-jnp.take_along_axis(logp, targets[..., None], -1))
    loss = (ce + config["assumed"]["router_aux_loss_coef"] * balance
            + config["assumed"]["router_z_loss_coef"] * z)
    return loss, {"ce": ce, "balance": balance, "z": z, "hidden": x,
                  "logits": logits, "experts": jnp.stack(experts)}


def loss_and_hidden(sd, tokens, targets, config):
    """(loss, {ce, balance, z, hidden (B, T, D) before the final norm,
    logits (B, T, V), experts (L, B*T, k)}) from HF-named weights."""
    with jax.default_matmul_precision("highest"):
        return _forward(sd, tokens, targets, config)


def grads_of(names):
    """-> f(sd, tokens, targets, config): `jax.grad` of the reference's own
    loss with respect to the weights called `names`, as a dict."""
    def grads(sd, tokens, targets, config):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _forward({**rest, **part}, tokens, targets, config)[0]

        with jax.default_matmul_precision("highest"):
            return jax.grad(loss)({n: sd[n] for n in names})
    return grads
