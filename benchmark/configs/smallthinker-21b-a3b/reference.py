"""Plain float32 reference of SmallThinker-21BA3B-Instruct's forward pass,
next-token loss and gradients (PowerInfer, `model_name`
`smallthinker_21b_instruct`, arXiv:2507.20984; the equations are ISSUE 63's,
which the model's public `modeling_smallthinker.py` and llama.cpp's
`llm_build_smallthinker` agree on line by line), on the model's parameter
names (`hetu_tpu/models/hf_smallthinker.py`), for ONE CHIP'S SHARE of each
expert layer.

With x the residual stream entering layer l (x = embed[tokens] for l = 0; N1
`input_layernorm`, N2 `post_attention_layernorm`, RMSNorm eps `rms_norm_eps`,
no bias anywhere):
  r = x Wr^T                      the router's `num_routed_experts` logits,
                                  from the layer's INPUT: before N1, before
                                  attention (`block_sparse_moe.primary_router`)
  h = x + Attn_l(N1_l(x))
  u = N2_l(h)
  y = h + sum over the picks e HELD HERE of w_e Wdown_e (relu(Wgate_e u) *
      Wup_e u)
Attn_l, n the normed input, d = `head_dim`, H = `num_attention_heads`, G =
`num_key_value_heads`; query head j reads k/v head j // (H / G):
  q = n Wq^T (H heads), k = n Wk^T, v = n Wv^T (G heads);
  `rope_layout`[l] = 1: q and k through rotate-half RoPE on ALL d columns,
    inverse frequencies `rope_theta`^(-2i/d); 0: NO position signal at all;
  `sliding_window_layout`[l] = 1: o_t = sum over t - `sliding_window_size` <
    s <= t of softmax_s(q_t . k_s / sqrt(d)) v_s, the mask (s <= t) AND (s >
    t - `sliding_window_size`), an explicit boolean array; 0: the mask s <= t;
  out = concat_j(o_j) Wo^T.
The picks are the `moe_num_active_primary_experts` largest of r; their
weights w are softmax over THOSE logits (`moe_primary_router_apply_softmax`,
`norm_topk_prob`), which is softmax over all of r, then the picks'
probabilities over their sum. This chip holds experts [`first_expert_held`, +
`moe_num_primary_experts`); every pick is weighed, the sum runs over the
picks held, what the others would add is left out, and the partial y goes
on. With every expert held (`num_routed_experts` absent) that is the whole
model's layer.
Logits = Nf(y_last) lm_head^T (`model.norm`; untied). Loss = the mean
next-token cross-entropy + `assumed.router_aux_loss_coef` x sum over layers
of E sum_e f_e P_e (f_e the picks of expert e over tokens, P_e the mean over
tokens of softmax(r)_e, E = `num_routed_experts`) + `assumed.
router_z_loss_coef` x sum over layers of the mean over tokens of
logsumexp(r)^2: both `assumed` (the config names no loss; the trunk's as
olmoe-1b-7b runs them). After a step every weight moves by
`adamw_after_step` (`assumed`).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over
layers, explicit boolean masks, a plain softmax, every held expert on EVERY
token masked by the picks' weights, the top k by k argmaxes, full logits over
the vocabulary held: no sort, no gather of rows, no grouped matmul, no kernel,
no scan, no loop bound at a window's edge, no fused cross-entropy. The picks
come from the reference's own router, or are handed in (`picks`) and taken as
they are (their weights and the two losses are still the reference's own).
Departures from the equations as written, none to the arithmetic:
- the softmax runs on blocks of at most `QUERY_ROWS` query rows against every
  key (`lax.map`), each block under its rows of the (T, T) mask: 28 heads of
  16,384 x 16,384 scores are 30 GB on a 16 GB chip.
- the held experts are one batched matmul a projection over a stacked expert
  axis, not a Python loop.
- each kind of layer and the head are ONE jitted function, called eagerly,
  and `grads_of` keeps only each call's INPUTS for the backward pass and runs
  the layer, or the head, again there under `jax.vjp` in one jitted program.
- the model's SECONDARY experts (the report's hierarchical MLP) are not here:
  config.json has no key for them (`assumed`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 256
_STATIC = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "head_dim", "rms_norm_eps", "rope_theta", "sliding_window_size",
           "moe_num_primary_experts", "moe_num_active_primary_experts")
ROUTER = "block_sparse_moe.primary_router.weight"


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x (B, T, heads, d): every head through rotate-half RoPE on all d
    columns at positions 0..T-1."""
    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def _attn_math(n, w, c, kind):
    """`kind` = (a window layer?, a rotary layer?)."""
    window, rotary = kind
    B, T, _ = n.shape
    H, G, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    q = (n @ w["self_attn.q_proj.weight"].T).reshape(B, T, H, d)
    k = (n @ w["self_attn.k_proj.weight"].T).reshape(B, T, G, d)
    v = (n @ w["self_attn.v_proj.weight"].T).reshape(B, T, G, d)
    if rotary:
        q, k = _rotate(q, c["rope_theta"]), _rotate(k, c["rope_theta"])
    # query head j reads k/v head j // (H / G)
    k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
    t_pos, s_pos = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = s_pos <= t_pos                                   # (T, T) bool
    if window:
        mask = mask & (s_pos > t_pos - c["sliding_window_size"])
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(first):
        cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(t, first, rows,
                                                           axis)
        scores = jnp.einsum("bthd,bshd->bhts", cut(q, 1), k) / np.sqrt(d)
        a = jax.nn.softmax(jnp.where(cut(mask, 0), scores, -jnp.inf), -1)
        return jnp.einsum("bhts,bshd->bthd", a, v)          # (B, rows, H, d)

    o = jax.lax.map(block, jnp.arange(0, T, rows))
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, T, H * d)
    return o @ w["self_attn.o_proj.weight"].T


def _top_k(r, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(r, -1)
        indices.append(i)
        r = jnp.where(jax.nn.one_hot(i, r.shape[-1], dtype=bool), -jnp.inf, r)
    return jnp.stack(indices, -1)


def _route_math(router_in, w, c, picks):
    """The router on rows `router_in` (S, D) -> (the picks' weights (S, k),
    the picks (S, k), aux (2,) = [balance, z])."""
    k = c["moe_num_active_primary_experts"]
    r = router_in @ w[ROUTER].T                              # (S, E)
    top_e = _top_k(r, k) if picks is None else picks
    top_r = jnp.take_along_axis(r, top_e, -1)
    top_w = jax.nn.softmax(top_r, -1)
    E, S = r.shape[-1], r.shape[0]
    f = jnp.sum(jax.nn.one_hot(top_e, E), (0, 1)) / S
    balance = E * jnp.sum(f * jnp.mean(jax.nn.softmax(r, -1), 0))
    z = jnp.mean(jax.scipy.special.logsumexp(r, -1) ** 2)
    return top_w, top_e, jnp.stack([balance, z])


def _experts_math(u, w, c, first, top_w, top_e):
    """The held experts' part of the routed sum on rows u (S, D)."""
    n = c["moe_num_primary_experts"]
    held = first + jnp.arange(n)
    gate, up, down = (jnp.stack(
        [w[f"block_sparse_moe.experts.{first + e}.{p}.weight"]
         for e in range(n)]) for p in ("gate", "up", "down"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    a = (jax.nn.relu(jnp.einsum("sd,efd->esf", u, gate))
         * jnp.einsum("sd,efd->esf", u, up))
    return jnp.einsum("es,esf,edf->sd", weight, a, down)


def _layer_math(x, w, picks, c, kind, first):
    """One decoder layer -> (y, aux (2,), the picks' (weights, experts))."""
    B, T, D = x.shape
    eps = c["rms_norm_eps"]
    n1 = _rms(x, w["input_layernorm.weight"], eps)
    h = x + _attn_math(n1, w, c, kind)
    u = _rms(h, w["post_attention_layernorm.weight"], eps)
    router_in = x       # the layer's INPUT: before N1 and before attention
    top_w, top_e, aux = _route_math(router_in.reshape(B * T, D), w, c, picks)
    out = _experts_math(u.reshape(B * T, D), w, c, first, top_w, top_e)
    return h + out.reshape(B, T, D), aux, (top_w, top_e)


def _nll_math(h, norm, head, targets, c):
    logits = _rms(h, norm, c["rms_norm_eps"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def kinds_of(config):
    """[(a window layer?, a rotary layer?)] a layer of the stack: the first
    `num_hidden_layers` entries of the two published layouts."""
    kinds = []
    for s, r in list(zip(config["sliding_window_layout"],
                         config["rope_layout"]))[
                             :config["num_hidden_layers"]]:
        window = bool(s)
        rotary = bool(r)
        kinds.append((window, rotary))
    return kinds


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

    def layer(x, w, *handed):
        return _layer_math(x, w, handed[0] if given else None, c, kind,
                           first)

    return highest(layer), lean(lambda *args: layer(*args)[:2], 2)


def _loss(sd, tokens, targets, config, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    static = tuple((k, config[k]) for k in _STATIC)
    first = config.get("first_expert_held", 0)
    coef = config["assumed"]
    x = f32(sd["model.embed_tokens.weight"])[tokens]
    after, routed, aux = [], [], jnp.zeros((2,), jnp.float32)
    for i, kind in enumerate(kinds_of(config)):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        given = picks is not None
        fn = _jitted(static, kind, first, given)[int(lean)]
        out = fn(x, w, *([picks[i]] if given else []))
        x, aux = out[0], aux + out[1]
        after.append(x)
        if not lean:
            routed.append(out[2])
    nll = _jitted(static, None, None, False)[int(lean)](
        x, f32(sd["model.norm.weight"]), f32(sd["lm_head.weight"]), targets)
    ce = jnp.mean(nll)
    loss = (ce + coef["router_aux_loss_coef"] * aux[0]
            + coef["router_z_loss_coef"] * aux[1])
    terms = {"nll": nll, "ce": ce, "balance": aux[0], "z": aux[1],
             "hidden": after}
    if not lean:
        experts = jnp.stack([e for _, e in routed])
        width = config.get("num_routed_experts",
                           config["moe_num_primary_experts"])
        terms.update(
            experts=experts, weights=jnp.stack([w for w, _ in routed]),
            counts=jnp.sum(jax.nn.one_hot(experts, width, dtype=jnp.int32),
                           (1, 2)))
    return loss, terms


def loss_terms(sd, tokens, targets, config, picks=None):
    """(loss, {nll (B, T) a token's next-token NLL, ce its mean, balance and
    z the two router losses summed over layers, hidden [L x (B, T, D)] the
    residual stream after each layer, experts (L, B*T, k) the picks, weights
    (L, B*T, k) theirs, counts (L, routed) the picks each expert took}) from
    HF-named weights. `picks` [L x (B*T, k) int]: the routing handed in,
    taken as it is (the reference's own router otherwise). Call it eagerly:
    its layers and head are jitted inside."""
    return _loss(sd, tokens, targets, config, picks)


def layer_part(x, w, config, kind, first, held):
    """ONE layer's output on stream x (B, T, D) for the member that holds
    experts [first, first + held) -> (y, what the held experts ADDED to h,
    h), for the share test: the members' additions sum to the uncut layer's.
    `w` the layer's weights under their names below `model.layers.N.`."""
    c = {**{k: config[k] for k in _STATIC}, "moe_num_primary_experts": held}
    with jax.default_matmul_precision("highest"):
        y, _, _ = _layer_math(x, w, None, c, kind, first)
        h = x + _attn_math(_rms(x, w["input_layernorm.weight"],
                                c["rms_norm_eps"]), w, c, kind)
    return y, y - h, h


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
    """-> f(sd, tokens, targets, config, picks=None) -> (loss, hidden [L x
    (B, T, D)], grads): the reference's own loss, the residual stream after
    each layer of the SAME pass, and the loss's `jax.grad` with respect to
    the weights called `names`, as a dict. Call it eagerly too."""
    def grads(sd, tokens, targets, config, picks=None):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            value, terms = _loss({**rest, **part}, tokens, targets, config,
                                 picks, lean=True)
            return value, terms["hidden"]

        (value, hidden), g = jax.value_and_grad(loss, has_aux=True)(
            {n: jnp.asarray(sd[n], jnp.float32) for n in names})
        return value, hidden, g
    return grads
