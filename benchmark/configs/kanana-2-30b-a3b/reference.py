"""Plain float32 reference of kanana-2-30b-a3b's forward pass, next-token loss
and gradients (Kakao, `model_type` `deepseek_v3`; every equation is
`transformers` 4.57.6 `models/deepseek_v3/modeling_deepseek_v3.py`:
`DeepseekV3Attention`, `apply_rotary_pos_emb_interleave`, `DeepseekV3MLP`,
`DeepseekV3TopkRouter`, `DeepseekV3MoE`, `DeepseekV3DecoderLayer`), on the
checkpoint's parameter names, for ONE CHIP'S SHARE of each expert layer.

With h = embed[tokens], for layer l (N1 `input_layernorm`, N2
`post_attention_layernorm`, RMSNorm eps `rms_norm_eps`, no bias anywhere):
  a = h + MLA_l(N1_l(h));  h = a + FFN_l(N2_l(a))
MLA (`q_lora_rank` null), H = `num_attention_heads` heads:
  q = u Wq^T, a head = [q_nope `qk_nope_head_dim` | q_rope
  `qk_rope_head_dim`];  [c | k_rope] = u Wkv_a^T, c of `kv_lora_rank`
  columns, k_rope ONE rotary key a token;  [k_nope | v] a head =
  RMSNorm(c; `kv_a_layernorm`) Wkv_b^T, v `v_head_dim` wide;  q_rope and
  k_rope through `apply_rotary_pos_emb_interleave` (a head's even columns
  to its first half, the odd ones to its second, then rotate-half RoPE at
  `rope_theta` over `qk_rope_head_dim`);  k = [k_nope | k_rope], the one
  k_rope broadcast to the H heads;  o = softmax(q k^T / sqrt(`qk_head_dim`)
  + causal mask) v;  out = concat_heads(o) Wo^T.
FFN_l, l < `first_k_dense_replace`:  down(silu(gate u) . up u), width
  `intermediate_size`.
FFN_l otherwise:  s = sigmoid(u Wg^T) in float32, one score for each of the
  `num_routed_experts` experts; the token's picks are the
  `num_experts_per_tok` largest of s + b (`e_score_correction_bias`; it
  enters nowhere else; `n_group` = `topk_group` = 1 make the group mask the
  identity); w_i = s_i / (sum over ALL the picks of s + 1e-20) *
  `routed_scaling_factor`; out = sum over the picks HELD HERE of w_i E_i(u)
  + S(u), E_i a SwiGLU of `moe_intermediate_size`, S (`shared_experts`) ONE
  SwiGLU of `n_shared_experts` x `moe_intermediate_size` on every token.
  This chip holds experts [`first_expert_held`, + `n_routed_experts`); what
  the others would add is left out, the shared expert is computed whole,
  and the partial h goes on. With every expert held (`num_routed_experts`
  absent) that is the whole model's layer.
Logits = Nf(h) lm_head^T (`model.norm`; untied), loss the mean next-token
cross-entropy; the config has no auxiliary loss (`noaux_tc`).
After a step the bias moves by `bias_after_step`: b_e += u sign(mean(c) -
c_e), c the picks each of the routed experts took (Wang et al. 2024,
arXiv:2408.15664; `assumed`: the rule is not in config.json).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over
layers, q, k and v built a head at a time and concatenated as the equations
read, every held expert on EVERY token masked by the picks' weights, the top
k by k argmaxes, full logits over the vocabulary held: no sort, no gather of
rows, no grouped matmul, no kernel, no scan, no fused cross-entropy.
Departures from the published code, none to the arithmetic:
- the softmax runs on blocks of at most 1,024 query rows against every key
  (`lax.map`): 32 heads of 8,192 x 8,192 scores are 8.6 GB on a 16 GB chip.
- the held experts are one batched matmul a projection over a stacked
  expert axis, not a Python loop (a float32 "highest" matmul costs the TPU's
  compiler about a second apiece).
- each kind of layer and the head are ONE jitted function, called eagerly,
  and `grads_of` keeps only each call's INPUTS for the backward pass and
  runs the layer, or the head, again there under `jax.vjp` in one jitted
  program. `loss_terms` is the plain forward, and the tests hold `grads_of`
  to `jax.grad` of it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 1024
_STATIC = ("hidden_size", "num_attention_heads", "rms_norm_eps", "rope_theta",
           "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
           "v_head_dim", "n_routed_experts", "num_experts_per_tok",
           "norm_topk_prob", "routed_scaling_factor", "n_shared_experts")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_interleave(x, theta):
    """x (B, H, T, d) -> `apply_rotary_pos_emb_interleave`: the even columns
    first, the odd ones after them, then rotate-half RoPE."""
    d, T = x.shape[-1], x.shape[-2]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], -1)
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], -1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def _mla_math(u, w, c):
    B, T, _ = u.shape
    H, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(B, T, H, nope + rope)
    compressed = u @ w["self_attn.kv_a_proj_with_mqa.weight"].T
    latent = _rms(compressed[..., :rank], w["self_attn.kv_a_layernorm.weight"],
                  c["rms_norm_eps"])
    kv = (latent @ w["self_attn.kv_b_proj.weight"].T).reshape(
        B, T, H, nope + vd)
    k_rot = _rope_interleave(compressed[:, None, :, rank:], c["rope_theta"])
    # a head at a time, as the equations read
    qs, ks, vs = [], [], []
    for i in range(H):
        q_rot = _rope_interleave(q[:, None, :, i, nope:], c["rope_theta"])
        qs.append(jnp.concatenate([q[:, None, :, i, :nope], q_rot], -1))
        ks.append(jnp.concatenate([kv[:, None, :, i, :nope], k_rot], -1))
        vs.append(kv[:, None, :, i, nope:])
    q, k, v = (jnp.concatenate(t, 1) for t in (qs, ks, vs))   # (B, H, T, .)
    rows = min(QUERY_ROWS, T)

    def block(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows, 2)
        scores = qb @ k.transpose(0, 1, 3, 2) / np.sqrt(nope + rope)
        qpos = first + jnp.arange(rows)[:, None]
        scores = jnp.where(jnp.arange(T)[None, :] <= qpos, scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v

    ctx = jax.lax.map(block, jnp.arange(0, T, rows))   # (T/rows, B, H, rows, vd)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, T, H * vd)
    return ctx @ w["self_attn.o_proj.weight"].T


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(indices, -1)


def _picks(m, w, c):
    """The router on rows m (S, D) -> (the picks' weights (S, k), normalised
    over all k picks; their experts (S, k), the model's indices)."""
    s = jax.nn.sigmoid(m @ w["mlp.gate.weight"].T)
    top_e = _top_k(s + w["mlp.gate.e_score_correction_bias"],
                   c["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, -1)
    if c["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_s * c["routed_scaling_factor"], top_e


def _swiglu(m, w, scope):
    return (jax.nn.silu(m @ w[scope + "gate_proj.weight"].T)
            * (m @ w[scope + "up_proj.weight"].T)
            ) @ w[scope + "down_proj.weight"].T


def _routed_math(m, w, c, first):
    """The held experts' part of the routed sum on rows m (S, D) -> (it, the
    picks' (weights, experts))."""
    n = c["n_routed_experts"]
    held = first + jnp.arange(n)
    top_w, top_e = _picks(m, w, c)
    gate, up, down = (jnp.stack([w[f"mlp.experts.{first + e}.{p}.weight"]
                                 for e in range(n)])
                      for p in ("gate_proj", "up_proj", "down_proj"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    u = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
         * jnp.einsum("sd,efd->esf", m, up))
    return jnp.einsum("es,esf,edf->sd", weight, u, down), (top_w, top_e)


def _experts_math(m, w, c, first):
    """An expert layer's MLP half on rows m (S, D): the held experts' part
    of the routed sum and the shared expert -> (it, the picks)."""
    out, routed = _routed_math(m, w, c, first)
    if c["n_shared_experts"]:
        out = out + _swiglu(m, w, "mlp.shared_experts.")
    return out, routed


def _layer_math(h, w, c, kind, picks=False):
    """One decoder layer; `kind` = None for the dense MLP or the first
    expert held. `picks`: of an expert layer -> (h, (the picks' weights,
    their experts), (S, k) each)."""
    B, T, D = h.shape
    a = h + _mla_math(_rms(h, w["input_layernorm.weight"],
                           c["rms_norm_eps"]), w, c)
    m = _rms(a, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    if kind is None:
        return a + _swiglu(m, w, "mlp.")
    out, routed = _experts_math(m.reshape(B * T, D), w, c, kind)
    h = a + out.reshape(B, T, D)
    return (h, routed) if picks else h


def _head_math(h, norm, head, c):
    return _rms(h, norm, c["rms_norm_eps"]) @ head.T


def _nll_math(h, norm, head, targets, c):
    logits = _head_math(h, norm, head, c)
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def kinds_of(config):
    """[None | first expert held] a layer of the stack."""
    held = config.get("first_expert_held", 0)
    return [None if i < config["first_k_dense_replace"] else held
            for i in range(config["num_hidden_layers"])]


@functools.lru_cache(maxsize=None)
def _jitted(static, kinds):
    """-> (plain, lean): {kind | "head" | "nll": a jitted function},
    compiled once for one architecture at "highest" precision."""
    c = dict(static)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def lean(math, n):
        """`math` for `jax.grad`: keeps its inputs alone and runs again under
        `jax.vjp`, in ONE jitted program, in the backward pass; the first
        `n` arguments are differentiated, the rest (integers) are not."""
        fn = highest(math)
        vjp = highest(lambda args, g: jax.vjp(
            lambda *diff: math(*diff, *args[n:]), *args[:n])[1](g))
        lean_fn = jax.custom_vjp(fn)
        lean_fn.defvjp(lambda *args: (fn(*args), args),
                       lambda args, g: vjp(args, g) + (None,) * (
                           len(args) - n))
        return lean_fn

    maths = {kind: functools.partial(_layer_math, c=c, kind=kind)
             for kind in kinds}
    maths["head"] = functools.partial(_head_math, c=c)
    nll = functools.partial(_nll_math, c=c)
    # the plain path's expert layers hand their picks out beside h
    plain = {name: highest(functools.partial(math, picks=True)
                           if name not in ("head", None) else math)
             for name, math in maths.items()}
    return ({**plain, "nll": highest(nll)},
            {**{name: lean(math, 2) for name, math in maths.items()},
             "nll": lean(nll, 3)})


def _trunk(sd, tokens, config, lean=False):
    """-> (the jitted functions, [h after each layer], the final h, [the
    picks (weights, experts) of each expert layer]; the picks only on the
    plain path)."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    kinds = kinds_of(config)
    fns = _jitted(tuple((k, config[k]) for k in _STATIC),
                  tuple(sorted(set(kinds), key=str)))[int(lean)]
    h = f32(sd["model.embed_tokens.weight"])[tokens]
    after, picks = [], []
    for i, kind in enumerate(kinds):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        h = fns[kind](h, w)
        if kind is not None and not lean:
            h, routed = h
            picks.append(routed)
        after.append(h)
    return fns, after, h, picks


def logits(sd, tokens, config):
    """Full logits (B, T, V) from HF-named weights. Call it eagerly."""
    fns, _, h, _ = _trunk(sd, tokens, config)
    return fns["head"](h, jnp.asarray(sd["model.norm.weight"], jnp.float32),
                       jnp.asarray(sd["lm_head.weight"], jnp.float32))


def _loss(sd, tokens, targets, config, lean=False):
    fns, after, h, picks = _trunk(sd, tokens, config, lean)
    nll = fns["nll"](h, jnp.asarray(sd["model.norm.weight"], jnp.float32),
                     jnp.asarray(sd["lm_head.weight"], jnp.float32), targets)
    routed = config.get("num_routed_experts", config["n_routed_experts"])
    terms = {"nll": nll, "hidden": after}
    if picks:
        experts = jnp.stack([e for _, e in picks])
        terms.update(
            experts=experts, weights=jnp.stack([w for w, _ in picks]),
            counts=jnp.sum(jax.nn.one_hot(experts, routed, dtype=jnp.int32),
                           (1, 2)))
    return jnp.mean(nll), terms


def loss_terms(sd, tokens, targets, config):
    """(loss, {nll (B, T) a token's next-token NLL, hidden [L x (B, T, D)]
    the residual stream after each layer, and over the expert layers:
    experts (Le, B*T, k) the picks, weights (Le, B*T, k) theirs, counts (Le,
    routed) the picks each expert took}) from HF-named weights. Call it
    eagerly: its layers and head are jitted inside."""
    return _loss(sd, tokens, targets, config)


def bias_after_step(bias, counts, rate):
    """The selection bias (Le, routed) after a step whose batch gave each
    expert `counts` picks: b_e + rate * sign(mean(c) - c_e)."""
    counts = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float64) + rate * np.sign(
        counts.mean(-1, keepdims=True) - counts)


def grads_of(names):
    """-> f(sd, tokens, targets, config) -> (loss, grads): the reference's
    own loss and its `jax.grad` with respect to the weights called `names`,
    as a dict. Call it eagerly too."""
    def grads(sd, tokens, targets, config):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _loss({**rest, **part}, tokens, targets, config,
                         lean=True)[0]

        return jax.value_and_grad(loss)({n: jnp.asarray(sd[n], jnp.float32)
                                         for n in names})
    return grads
