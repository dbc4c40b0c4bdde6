"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model: forward
pass, the training loss with the indexer's own term, and gradients, on the
checkpoint's parameter names (`hetu_tpu/models/hf_keye.py`), for ONE CHIP'S
SHARE of each expert layer.

With h = embed[tokens], for layer l (N1 `input_layernorm`, N2
`post_attention_layernorm`, RMSNorm eps `rms_norm_eps`, no bias anywhere):
  a = h + DSA_l(N1_l(h));  h = a + MoE_l(N2_l(a))
DSA, u = N1(h), H = `num_attention_heads` query heads on G =
`num_key_value_heads` k/v heads of d = `head_dim` columns (head i reads k/v
head i // (H / G)):
  q_i = RoPE(RMSNorm_d((u Wq^T)_i; `q_norm`)),  k_g = RoPE(RMSNorm_d((u
  Wk^T)_g; `k_norm`)),  v_g = (u Wv^T)_g;  rotate-half RoPE at `rope_theta`
  (text only: the three M-RoPE streams carry one position).
  The indexer (DeepSeek-V3.2-Exp's; `sa_config`), on ud = stop_gradient(u),
  J = `indexer_num_heads` heads of c = `indexer_head_dim` columns:
  qI_j = RoPE((ud Wq_idx^T)_j);  kI = RoPE(LayerNorm(ud Wk_idx^T; `k_norm`
  weight and bias, eps `rms_norm_eps`));  w = (ud Ww^T) / sqrt(J c);
  I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])  for s <= t.
  S_t = the `topk` keys s <= t of largest I[t, s] (all while t < topk; ties
  to the lower s): `lax.top_k` a block of query rows, then a boolean mask.
  OR `kept`, a mask (B, T, T) a layer handed in, taken as it is.
  a[t, i, .] = softmax over S_t of q_i[t] . k_g[s] / sqrt(d);  o_i = a v_g;
  out = concat_heads(o) Wo^T.
  L_I(l) = mean over (b, t) of KL(p_t || softmax over S_t of I[t, .]),
  p = stop_gradient(mean_i a[t, i, .]).
MoE, m = N2(a):  r = m Wg^T, p = softmax(r) over the `num_routed_experts`
  experts in float32; the picks the `num_experts_per_tok` largest (OR
  `picks`, handed in); weights the picks' p over their sum
  (`norm_topk_prob`); out = sum over the picks HELD HERE of w_e E_e(m), E_e a
  SwiGLU of `moe_intermediate_size`. This chip holds experts
  [`first_expert_held`, + `num_experts`); what the others would add is left
  out and the partial h goes on. balance = E sum_e f_e P_e (f the picks an
  expert takes over tokens, P its mean probability, E the router's width), z
  = mean logsumexp(r)^2, as `olmoe-1b-7b`'s reference has them.
Logits = Nf(h) lm_head^T over the vocabulary held; loss = mean next-token
cross-entropy + `router_aux_loss_coef` sum_l balance + `router_z_loss_coef`
sum_l z + `indexer_loss_coef` sum_l L_I (config.json `assumed`).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision, a
Python loop over layers, every held expert on EVERY token masked by the
picks' weights, full logits: no packed mask, no threshold search, no kernel,
no scan over layers, no fused cross-entropy, no `custom_vjp` but the one
below. Departures from a line-by-line script, none to the arithmetic:
- scores, selection, softmax and L_I run on blocks of `QUERY_ROWS` query
  rows against every key (`lax.map` over a checkpointed block): 32 heads of
  16,384 x 16,384 scores are 34 GB.
- the held experts are one batched matmul a projection over a stacked
  expert axis, not a Python loop.
- the layer and the head are ONE jitted function each, called eagerly, and
  `grads_of` keeps only each call's INPUTS for the backward pass and runs
  the layer, or the head, again there under `jax.vjp` (`_jitted`), as
  kanana-2-30b-a3b's reference does.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 128
_STATIC = ("num_attention_heads", "num_key_value_heads", "head_dim",
           "rms_norm_eps", "rope_theta", "num_experts", "num_experts_per_tok",
           "norm_topk_prob")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x (B, T, heads, d) -> rotate-half RoPE at positions 0..T-1."""
    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def _dsa_math(u, w, c, sa, kept):
    """-> (the mixer's output (B, T, D), L_I, the kept set (B, T, T) bool)."""
    B, T, _ = u.shape
    H, G, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    J, ci, topk = sa
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q = _rope(_rms((u @ w["self_attn.q_proj.weight"].T).reshape(B, T, H, d),
                   w["self_attn.q_norm.weight"], eps), theta)
    k = _rope(_rms((u @ w["self_attn.k_proj.weight"].T).reshape(B, T, G, d),
                   w["self_attn.k_norm.weight"], eps), theta)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(B, T, G, d)
    # head i reads k/v head i // (H / G)
    k, v = (jnp.repeat(x, H // G, axis=2) for x in (k, v))
    ud = jax.lax.stop_gradient(u)
    qI = _rope((ud @ w["self_attn.indexer.wq.weight"].T).reshape(B, T, J, ci),
               theta)
    kI = _rope(_layer_norm(ud @ w["self_attn.indexer.wk.weight"].T,
                           w["self_attn.indexer.k_norm.weight"],
                           w["self_attn.indexer.k_norm.bias"],
                           eps)[:, :, None, :], theta)[:, :, 0]
    wI = (ud @ w["self_attn.indexer.weights_proj.weight"].T) * (J * ci) ** -0.5
    rows = min(QUERY_ROWS, T)
    given = kept is not None

    @jax.checkpoint
    def block(first):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, first, rows, 1)
        index = jnp.einsum(
            "btj,btjs->bts", cut(wI), jax.nn.relu(
                jnp.einsum("btjc,bsc->btjs", cut(qI), kI)))     # (B, rows, T)
        qpos = first + jnp.arange(rows)[:, None]
        causal = jnp.arange(T)[None, :] <= qpos
        if given:
            keep = cut(kept)
        else:
            _, best = jax.lax.top_k(jnp.where(causal, index, -jnp.inf),
                                    min(topk, T))
            keep = jnp.any(jax.nn.one_hot(best, T, dtype=bool), -2) & causal
        scores = jnp.einsum("bthd,bshd->bhts", cut(q), k) / np.sqrt(d)
        a = jax.nn.softmax(jnp.where(keep[:, None], scores, -jnp.inf), -1)
        o = jnp.einsum("bhts,bshd->bthd", a, v).reshape(B, rows, H * d)
        p = jax.lax.stop_gradient(jnp.mean(a, 1))               # (B, rows, T)
        log_q = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), -1)
        seen = p > 0
        kl = jnp.sum(jnp.where(seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                                          - jnp.where(seen, log_q, 0.0)),
                               0.0))
        return o, kl, keep

    o, kl, keep = jax.lax.map(block, jnp.arange(0, T, rows))
    o = o.transpose(1, 0, 2, 3).reshape(B, T, H * d)
    keep = keep.transpose(1, 0, 2, 3).reshape(B, T, T)
    return (o @ w["self_attn.o_proj.weight"].T, jnp.sum(kl) / (B * T), keep)


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(indices, -1)


def _moe_math(m, w, c, first, picks):
    """The held experts' part of the routed sum on rows m (S, D) -> (it, the
    picks' experts (S, k), [balance, z])."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    r = m @ w["mlp.gate.weight"].T
    p = jax.nn.softmax(r, -1)
    E = p.shape[-1]
    top_e = _top_k(p, k) if picks is None else picks
    top_p = jnp.take_along_axis(p, top_e, -1)
    if c["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    held = first + jnp.arange(n)
    gate, up, down = (jnp.stack([w[f"mlp.experts.{first + e}.{x}.weight"]
                                 for e in range(n)])
                      for x in ("gate_proj", "up_proj", "down_proj"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_p[None], 0.0), -1)            # (E, S)
    u = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
         * jnp.einsum("sd,efd->esf", m, up))
    f = jnp.sum(jax.nn.one_hot(top_e, E), (0, 1)) / m.shape[0]
    aux = jnp.stack([E * jnp.sum(f * jnp.mean(p, 0)),
                     jnp.mean(jax.scipy.special.logsumexp(r, -1) ** 2)])
    return jnp.einsum("es,esf,edf->sd", weight, u, down), top_e, aux


def _layer_math(h, w, kept, picks, c, sa, first):
    """One decoder layer -> (h, [balance, z, L_I], the kept set, the
    picks)."""
    B, T, D = h.shape
    out, index_loss, keep = _dsa_math(
        _rms(h, w["input_layernorm.weight"], c["rms_norm_eps"]), w, c, sa,
        kept)
    a = h + out
    m = _rms(a, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    out, top_e, aux = _moe_math(m.reshape(B * T, D), w, c, first, picks)
    return (a + out.reshape(B, T, D), jnp.append(aux, index_loss), keep,
            top_e)


def _nll_math(h, norm, head, targets, c):
    logits = _rms(h, norm, c["rms_norm_eps"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


@functools.lru_cache(maxsize=None)
def _jitted(static, sa, first, given):
    """-> (plain, lean): {"layer" | "nll": a jitted function}, compiled once
    for one architecture at "highest" precision. `given` = (a kept set is
    handed in, picks are): what is not handed in is None."""
    c = dict(static)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def lean(math, n):
        """`math` for `jax.grad`: keeps its inputs alone and runs again under
        `jax.vjp`, in ONE jitted program, in the backward pass; the first
        `n` arguments are differentiated, the rest (masks, integers) not."""
        fn = highest(math)
        vjp = highest(lambda args, g: jax.vjp(
            lambda *diff: math(*diff, *args[n:]), *args[:n])[1](g))
        lean_fn = jax.custom_vjp(fn)
        lean_fn.defvjp(lambda *args: (fn(*args), args),
                       lambda args, g: vjp(args, g) + (None,) * (
                           len(args) - n))
        return lean_fn

    def layer(h, w, *handed):
        handed = iter(handed)
        kept, picks = (next(handed) if there else None for there in given)
        return _layer_math(h, w, kept, picks, c, sa, first)

    # for the gradient: h and the two losses' terms; the sets are constants
    layer_terms = lambda *args: layer(*args)[:2]
    nll = functools.partial(_nll_math, c=c)
    return ({"layer": highest(layer), "nll": highest(nll)},
            {"layer": lean(layer_terms, 2), "nll": lean(nll, 3)})


def _loss(sd, tokens, targets, config, kept=None, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    sa = config["sa_config"]
    fns = _jitted(
        tuple((k, config[k]) for k in _STATIC),
        (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]),
        config.get("first_expert_held", 0),
        (kept is not None, picks is not None))[int(lean)]
    h = f32(sd["model.embed_tokens.weight"])[tokens]
    after, aux, kept_sets, experts = [], [], [], []
    for i in range(config["num_hidden_layers"]):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        handed = [x[i] for x in (kept, picks) if x is not None]
        out = fns["layer"](h, w, *handed)
        h = out[0]
        after.append(h)
        aux.append(out[1])
        if not lean:
            kept_sets.append(out[2])
            experts.append(out[3])
    nll = fns["nll"](h, f32(sd["model.norm.weight"]), f32(sd["lm_head.weight"]),
                     targets)
    aux = jnp.stack(aux)                                   # (L, 3)
    a = config["assumed"]
    loss = jnp.mean(nll) + jnp.sum(aux @ jnp.asarray(
        [a["router_aux_loss_coef"], a["router_z_loss_coef"],
         a["indexer_loss_coef"]], jnp.float32))
    terms = {"nll": nll, "hidden": after, "balance": aux[:, 0], "z": aux[:, 1],
             "index_loss": aux[:, 2]}
    if not lean:
        terms.update(kept=kept_sets, experts=jnp.stack(experts))
    return loss, terms


def loss_terms(sd, tokens, targets, config, kept=None, picks=None):
    """(loss, {nll (B, T), hidden [L x (B, T, D)] the residual stream after
    each layer, balance, z, index_loss (L,) each layer's terms, kept [L x (B,
    T, T) bool] the kept sets, experts (L, B*T, k) the picks}) from HF-named
    weights. `kept` [L x (B, T, T) bool] and `picks` [L x (B*T, k) int]: the
    selection and the routing handed in, taken as they are where given (the
    reference's own indexer and router otherwise). Call it eagerly: its
    layer and head are jitted inside."""
    return _loss(sd, tokens, targets, config, kept, picks)


def index_scores_f64(q_idx, k_idx, w):
    """numpy float64 index scores of ONE sequence from index queries (T, J,
    c), index keys (T, c) and weights (T, J), whatever computed them -> (T, T)
    float64: I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])."""
    q, k, w = (np.asarray(x, np.float64) for x in (q_idx, k_idx, w))
    return np.einsum("tj,tjs->ts", w, np.maximum(
        np.einsum("tjc,sc->tjs", q, k), 0.0))


def grads_of(names):
    """-> f(sd, tokens, targets, config, kept=None, picks=None) -> (loss,
    grads): the reference's own loss and its `jax.grad` with respect to the
    weights called `names`, as a dict. Call it eagerly too."""
    def grads(sd, tokens, targets, config, kept=None, picks=None):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _loss({**rest, **part}, tokens, targets, config, kept,
                         picks, lean=True)[0]

        return jax.value_and_grad(loss)({n: jnp.asarray(sd[n], jnp.float32)
                                         for n in names})
    return grads
