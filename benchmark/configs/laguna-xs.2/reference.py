"""Plain float32 reference of laguna-xs.2's forward pass, next-token loss and
gradients (poolside, `model_type` `laguna`; no `transformers` release carries
the model: the equations are ISSUE 49's, written from the config's keys and
`transformers`' `_compute_yarn_parameters`), on llama-family parameter names
(`hetu_tpu/models/hf_laguna.py`), for ONE CHIP'S SHARE of each expert layer.

With h = embed[tokens], for layer l (N1 `input_layernorm`, N2
`post_attention_layernorm`, RMSNorm eps `rms_norm_eps`, no bias anywhere):
  a = h + Attn_l(N1_l(h));  h = a + MLP_l(N2_l(a))
Attn_l, x the normed input, d = `head_dim`, G = `num_key_value_heads`, H_l =
`num_attention_heads_per_layer`[l]; head h reads k/v head h // (H_l / G):
  q = x Wq^T (H_l heads), k = x Wk^T, v = x Wv^T (G heads);
  `layer_types`[l] = `sliding_attention`: q and k through rotate-half RoPE on
    ALL d columns, inverse frequencies theta^(-2i/d) (`rope_parameters.
    sliding_attention`); o_t = sum over t - `sliding_window` < s <= t of
    softmax_s(q_t . k_s / sqrt(d)) v_s: the mask is (s <= t) AND (s > t -
    `sliding_window`), an explicit boolean array;
  `full_attention`: only the FIRST rot = `partial_rotary_factor` x d columns
    of a head turn, rotate-half inside them, the others pass; inverse
    frequencies by YaRN (`yarn_table`: float64, then cast), cos and sin
    times `attention_factor`; the mask is s <= t;
  g = sigmoid(x Wg^T) in R^{H_l} (`gating`: per head); out = concat_h(g_h
    o_h) Wo^T.
MLP_l, `mlp_layer_types`[l] = `dense`:  down(silu(gate u) . up u), width
  `intermediate_size`.
`sparse`:  s = sigmoid(u Wr^T), one score for each of the
  `num_routed_experts` experts; the token's picks are the
  `num_experts_per_tok` largest of s + b (`e_score_correction_bias`; it
  enters nowhere else); w_i = `moe_routed_scaling_factor` x s_i / (sum over
  ALL the picks of s + 1e-20), on an expert's OUTPUT; out = sum over the
  picks HELD HERE of w_i E_i(u) + S(u), E_i a SwiGLU of
  `moe_intermediate_size`, S ONE SwiGLU of `shared_expert_intermediate_size`
  on every token. This chip holds experts [`first_expert_held`, +
  `num_experts`); what the others would add is left out, the shared expert is
  computed whole, and the partial h goes on. With every expert held
  (`num_routed_experts` absent) that is the whole model's layer.
Logits = Nf(h) lm_head^T (`model.norm`; untied), loss the mean next-token
cross-entropy; no auxiliary loss. After a step the bias moves by
`bias_after_step`: b_e += u sign(mean(c) - c_e), and every other weight by
`adamw_after_step` (both `assumed`).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over
layers, explicit boolean masks, a plain softmax, partial rotary by slicing a
head's first columns, every held expert on EVERY token masked by the picks'
weights, the top k by k argmaxes, full logits over the vocabulary held: no
sort, no gather of rows, no grouped matmul, no kernel, no scan, no loop bound
at a window's edge, no fused cross-entropy. The picks come from the
reference's own router, or are handed in (`picks`) and taken as they are.
Departures from the equations as written, none to the arithmetic:
- the softmax runs on blocks of at most `QUERY_ROWS` query rows against every
  key (`lax.map`), each block under its rows of the (T, T) mask: 64 heads of
  16,384 x 16,384 scores are 69 GB on a 16 GB chip.
- the held experts are one batched matmul a projection over a stacked expert
  axis, not a Python loop.
- each kind of layer and the head are ONE jitted function, called eagerly,
  and `grads_of` keeps only each call's INPUTS for the backward pass and runs
  the layer, or the head, again there under `jax.vjp` in one jitted program.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 256
_STATIC = ("hidden_size", "num_key_value_heads", "head_dim", "rms_norm_eps",
           "sliding_window", "num_experts", "num_experts_per_tok",
           "moe_routed_scaling_factor", "shared_expert_intermediate_size",
           "gating")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_table(r, dim):
    """The `dim` / 2 inverse frequencies of `rope_parameters` entry `r` in
    numpy float64 and the factor on cos and sin -> (inv (dim / 2,), factor).
    `rope_type` default: theta^(-2i/dim), 1. yarn, the five formulas:
      c(n) = dim ln(original / (2 pi n)) / (2 ln theta)
      low = floor(c(beta_fast)), high = ceil(c(beta_slow))
      ramp_i = clip((i - low) / (high - low), 0, 1)
      f_i = theta^(-2i/dim)
      inv_i = (1 - ramp_i) f_i + ramp_i f_i / factor
    and cos and sin times `attention_factor`."""
    theta = float(r["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    if r.get("rope_type", "default") == "default":
        return f, 1.0
    original = r["original_max_position_embeddings"]
    c = lambda n: dim * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low = max(math.floor(c(r["beta_fast"])), 0)
    high = min(math.ceil(c(r["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * f + ramp * f / r["factor"], r["attention_factor"]


def _rotate(x, r, d):
    """x (B, T, heads, d): the first rot = `partial_rotary_factor` x d
    columns of each head through rotate-half RoPE at positions 0..T-1 by
    `yarn_table`'s frequencies, the others as they are."""
    T = x.shape[1]
    rot = int(round(r.get("partial_rotary_factor", 1.0) * d))
    inv, factor = yarn_table(r, rot)
    freqs = (jnp.arange(T, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], -1)[:, None, :]
    cos, sin = cos * jnp.float32(factor), sin * jnp.float32(factor)
    turn, stay = x[..., :rot], x[..., rot:]
    rotated = jnp.concatenate([-turn[..., rot // 2:], turn[..., :rot // 2]],
                              -1)
    return jnp.concatenate([turn * cos + rotated * sin, stay], -1)


def _attn_math(x, w, c, kind):
    """`kind` = (layer type, query heads, that type's `rope_parameters` as a
    tuple of items)."""
    layer_type, H, rope = kind
    r = dict(rope)
    B, T, _ = x.shape
    G, d = c["num_key_value_heads"], c["head_dim"]
    q = _rotate((x @ w["self_attn.q_proj.weight"].T).reshape(B, T, H, d), r, d)
    k = _rotate((x @ w["self_attn.k_proj.weight"].T).reshape(B, T, G, d), r, d)
    v = (x @ w["self_attn.v_proj.weight"].T).reshape(B, T, G, d)
    # head h reads k/v head h // (H / G)
    k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
    t_pos, s_pos = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = s_pos <= t_pos                                   # (T, T) bool
    if layer_type == "sliding_attention":
        mask = mask & (s_pos > t_pos - c["sliding_window"])
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(first):
        cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(t, first, rows,
                                                           axis)
        scores = jnp.einsum("bthd,bshd->bhts", cut(q, 1), k) / np.sqrt(d)
        a = jax.nn.softmax(jnp.where(cut(mask, 0), scores, -jnp.inf), -1)
        return jnp.einsum("bhts,bshd->bthd", a, v)          # (B, rows, H, d)

    o = jax.lax.map(block, jnp.arange(0, T, rows))
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, T, H, d)
    if c["gating"]:
        g = jax.nn.sigmoid(x @ w["self_attn.g_proj.weight"].T)  # (B, T, H)
        o = o * g[..., None]
    return o.reshape(B, T, H * d) @ w["self_attn.o_proj.weight"].T


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(indices, -1)


def _swiglu(m, w, scope):
    return (jax.nn.silu(m @ w[scope + "gate_proj.weight"].T)
            * (m @ w[scope + "up_proj.weight"].T)
            ) @ w[scope + "down_proj.weight"].T


def _experts_math(m, w, c, first, picks):
    """An expert layer's MLP half on rows m (S, D): the held experts' part of
    the routed sum and the shared expert -> (it, the picks' (weights,
    experts), (S, k) each). `picks` (S, k) int: the experts handed in."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ w["mlp.gate.weight"].T)
    top_e = (_top_k(s + w["mlp.gate.e_score_correction_bias"], k)
             if picks is None else picks)
    top_s = jnp.take_along_axis(s, top_e, -1)
    top_w = (top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
             * c["moe_routed_scaling_factor"])
    held = first + jnp.arange(n)
    gate, up, down = (jnp.stack([w[f"mlp.experts.{first + e}.{p}.weight"]
                                 for e in range(n)])
                      for p in ("gate_proj", "up_proj", "down_proj"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    u = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
         * jnp.einsum("sd,efd->esf", m, up))
    out = jnp.einsum("es,esf,edf->sd", weight, u, down)
    if c["shared_expert_intermediate_size"]:
        out = out + _swiglu(m, w, "mlp.shared_expert.")
    return out, (top_w, top_e)


def _layer_math(h, w, picks, c, kind, first):
    """One decoder layer; `first` = None for the dense MLP or the first
    expert held -> (h, the picks' (weights, experts) | None)."""
    B, T, D = h.shape
    a = h + _attn_math(_rms(h, w["input_layernorm.weight"],
                            c["rms_norm_eps"]), w, c, kind)
    m = _rms(a, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    if first is None:
        return a + _swiglu(m, w, "mlp."), None
    out, routed = _experts_math(m.reshape(B * T, D), w, c, first, picks)
    return a + out.reshape(B, T, D), routed


def _nll_math(h, norm, head, targets, c):
    logits = _rms(h, norm, c["rms_norm_eps"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def kinds_of(config):
    """[((layer type, query heads, its rope_parameters), None | first expert
    held)] a layer of the stack."""
    held = config.get("first_expert_held", 0)
    return [((t, H, tuple(sorted(config["rope_parameters"][t].items()))),
             None if mlp == "dense" else held)
            for t, H, mlp in zip(config["layer_types"],
                                 config["num_attention_heads_per_layer"],
                                 config["mlp_layer_types"])]


@functools.lru_cache(maxsize=None)
def _jitted(static, kind, first, given):
    """-> (plain, lean) of one kind of layer, or of the head (`kind` None):
    jitted functions compiled once for one architecture at "highest"
    precision. `given`: the picks are handed in."""
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

    if kind is None:
        nll = functools.partial(_nll_math, c=c)
        return highest(nll), lean(nll, 3)

    def layer(h, w, *handed):
        return _layer_math(h, w, handed[0] if given else None, c, kind,
                           first)

    return highest(layer), lean(lambda *args: layer(*args)[0], 2)


def _loss(sd, tokens, targets, config, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    static = tuple((k, config[k]) for k in _STATIC)
    h = f32(sd["model.embed_tokens.weight"])[tokens]
    after, routed = [], []
    for i, (kind, first) in enumerate(kinds_of(config)):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        given = picks is not None and first is not None
        fn = _jitted(static, kind, first, given)[int(lean)]
        out = fn(h, w, *([picks[len(routed)]] if given else []))
        h, r = (out, None) if lean else out
        after.append(h)
        if first is not None:
            routed.append(r)
    nll = _jitted(static, None, None, False)[int(lean)](
        h, f32(sd["model.norm.weight"]), f32(sd["lm_head.weight"]), targets)
    terms = {"nll": nll, "hidden": after}
    if routed and not lean:
        experts = jnp.stack([e for _, e in routed])
        width = config.get("num_routed_experts", config["num_experts"])
        terms.update(
            experts=experts, weights=jnp.stack([w for w, _ in routed]),
            counts=jnp.sum(jax.nn.one_hot(experts, width, dtype=jnp.int32),
                           (1, 2)))
    return jnp.mean(nll), terms


def loss_terms(sd, tokens, targets, config, picks=None):
    """(loss, {nll (B, T) a token's next-token NLL, hidden [L x (B, T, D)]
    the residual stream after each layer, and over the expert layers:
    experts (Le, B*T, k) the picks, weights (Le, B*T, k) theirs, counts (Le,
    routed) the picks each expert took}) from HF-named weights. `picks` [Le x
    (B*T, k) int]: the routing handed in, taken as it is (the reference's own
    router otherwise). Call it eagerly: its layers and head are jitted
    inside."""
    return _loss(sd, tokens, targets, config, picks)


def bias_after_step(bias, counts, rate):
    """The selection bias (Le, routed) after a step whose batch gave each
    expert `counts` picks: b_e + rate * sign(mean(c) - c_e)."""
    counts = np.asarray(counts, np.float64)
    return np.asarray(bias, np.float64) + rate * np.sign(
        counts.mean(-1, keepdims=True) - counts)


def adamw_after_step(p, m, v, g, t, lr, adamw):
    """A weight after AdamW's step number `t` (1 the first) on gradient `g`
    from the moments `m` and `v`, numpy float64, `adamw` = {b1, b2, eps,
    weight_decay} (config.json `assumed`): m' = b1 m + (1 - b1) g, v' = b2 v
    + (1 - b2) g^2, p' = p - lr (m' / (1 - b1^t) / (sqrt(v' / (1 - b2^t)) +
    eps) + weight_decay p)."""
    p, m, v, g = (np.asarray(a, np.float64) for a in (p, m, v, g))
    b1, b2 = adamw["b1"], adamw["b2"]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    return p - lr * (m / (1.0 - b1 ** t)
                     / (np.sqrt(v / (1.0 - b2 ** t)) + adamw["eps"])
                     + adamw["weight_decay"] * p)


def grads_of(names):
    """-> f(sd, tokens, targets, config, picks=None) -> (loss, grads): the
    reference's own loss and its `jax.grad` with respect to the weights
    called `names`, as a dict. Call it eagerly too."""
    def grads(sd, tokens, targets, config, picks=None):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _loss({**rest, **part}, tokens, targets, config, picks,
                         lean=True)[0]

        return jax.value_and_grad(loss)({n: jnp.asarray(sd[n], jnp.float32)
                                         for n in names})
    return grads
