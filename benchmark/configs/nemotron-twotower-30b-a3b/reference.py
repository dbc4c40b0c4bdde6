"""Plain float32 reference of the `nemotron_h` tower of Nemotron-Labs-
TwoTower-30B-A3B: forward pass, next-token loss and gradients (nvidia
Nemotron-H, arXiv:2504.03624; the mixer of Dao & Gu 2024, arXiv:2405.21060, as
`mamba_ssm`'s `Mamba2` computes it; no `transformers` release here carries
`nemotron_h`: the equations are ISSUE 55's, written from the config's keys),
on the checkpoint's parameter names (`hetu_tpu/models/hf_nemotron_h.py`), for
ONE CHIP'S SHARE of each expert layer. The model's second, DENOISER tower and
its block-diffusion objective are NOT here: config.json defines neither.

With h = embeddings[tokens], every layer l is ONE sublayer,
  h = h + f_l(RMSNorm_l(h))        eps `layer_norm_epsilon`, no bias,
f_l by letter l of `hybrid_override_pattern`:

`M`, Mamba-2 (H = `mamba_num_heads` heads of P = `mamba_head_dim` channels,
state N = `ssm_state_size`, G = `n_groups` groups, K = `conv_kernel` taps):
  [z | xBC | dt] = u W_in^T                 H P + (H P + 2 G N) + H columns
  xBC_t = silu(b + sum_k w[:, k] * xBC_{t-(K-1)+k}),  zeros before t = 0
  [x | B | C] = xBC;  head h reads B and C of group h // (H / G)
  dt = softplus(dt + dt_bias) (no clamp);  A = -exp(A_log)
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      a head, S_0 = 0
  y_t = S_t C_t + D x_t
  out = concat_g(RMSNorm(y_g * silu(z_g)) * w_norm_g) W_out^T
the norm over each GROUP's H P / G channels, gate first (`mamba_ssm`
`RMSNormGated`, `group_size` = d_inner / ngroups, `norm_before_gate` false).
`*`, attention: q = u Wq^T (`num_attention_heads` heads of `head_dim`), k, v
  (`num_key_value_heads`); NO rotary and no other position; head h reads k/v
  head h // (heads / kv heads); softmax(q k^T / sqrt(head_dim) + causal mask)
  v; Wo.
`E`, experts: s = sigmoid(u Wr^T), one score for each of the
  `num_routed_experts` experts; the token's picks are the
  `num_experts_per_tok` largest of s + b (`e_score_correction_bias`; it
  enters nowhere else); w_i = `routed_scaling_factor` x s_i / (sum over ALL
  the picks of s + 1e-20); out = sum over the picks HELD HERE of w_i W2_i
  relu(W1_i u)^2 + Ws2 relu(Ws1 u)^2: two matrices an expert, no gate. This
  chip holds experts [`first_expert_held`, + `n_routed_experts`); what the
  others would add is left out, the shared expert is computed whole, and the
  partial h goes on. With every expert held (`num_routed_experts` absent)
  that is the whole model's layer.
Logits = RMSNorm_f(h) lm_head^T (untied), loss the mean next-token
cross-entropy; no auxiliary loss. After a step the bias moves by
`bias_after_step`: b_e += u sign(mean(c) - c_e), and every other weight by
`adamw_after_step` (both `assumed`).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over the
pattern's letters, the recurrence a `lax.scan` over TIME (no chunks, no
cumulative decay), the norm by explicit groups, k/v heads indexed (no
repeat), every held expert on EVERY token masked by the picks' weights, the
top k by k argmaxes, relu^2 written out, full logits over the vocabulary
held: no sort, no gather of rows, no grouped matmul, no kernel, no fused
cross-entropy. The picks come from the reference's own router, or are handed
in (`picks`) and taken as they are. Departures from the equations as
written, none to the arithmetic:
- the softmax runs on blocks of at most `QUERY_ROWS` query rows against every
  key (`lax.map`): 32 heads of 8,192 x 8,192 scores are 8.6 GB on a 16 GB
  chip.
- the time scan runs in segments of `TIME_SEGMENT` positions, each under
  `jax.checkpoint`: its backward pass then keeps the state at the segments'
  starts (2 MB each) and makes a segment's again, where 8,192 kept states
  are 17 GB. The recurrence is position by position either way.
- the held experts are one batched matmul a projection over a stacked expert
  axis, not a Python loop.
- each letter's layer and the head are ONE jitted function, called eagerly,
  and `grads_of` keeps only each call's INPUTS for the backward pass and runs
  the layer, or the head, again there under `jax.vjp` in one jitted program.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_ROWS = 1024
TIME_SEGMENT = 128
_STATIC = ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
           "conv_kernel", "num_attention_heads", "num_key_value_heads",
           "head_dim", "layer_norm_epsilon", "n_routed_experts",
           "num_experts_per_tok", "routed_scaling_factor",
           "moe_shared_expert_intermediate_size")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rms_by_group(y, scale, groups, eps):
    """RMSNorm over each of `groups` runs of the last axis, one after
    another."""
    size = y.shape[-1] // groups
    return jnp.concatenate(
        [_rms(y[..., g * size:(g + 1) * size],
              scale[g * size:(g + 1) * size], eps) for g in range(groups)],
        -1)


def _relu2(a):
    return jnp.maximum(a, 0.0) * jnp.maximum(a, 0.0)


def _recurrence(x, Bm, Cm, dt, A):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t, position
    by position: x (B, T, H, P), Bm, Cm (B, T, H, N) a head's own, dt (B, T,
    H), A (H,) -> y (B, T, H, P)."""
    B_, T, H, P = x.shape
    N = Bm.shape[-1]

    def step(S, at_t):
        x_t, B_t, C_t, dt_t = at_t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    seg = min(TIME_SEGMENT, T)
    assert T % seg == 0, (T, seg)

    @jax.checkpoint
    def segment(S, at):
        return jax.lax.scan(step, S, at)

    # (T, ...) -> (T / seg, seg, ...): time first, cut into segments
    cut = lambda m: jnp.moveaxis(m, 1, 0).reshape((T // seg, seg)
                                                  + m.shape[:1] + m.shape[2:])
    _, y = jax.lax.scan(segment, jnp.zeros((B_, H, P, N), jnp.float32),
                        tuple(cut(m) for m in (x, Bm, Cm, dt)))
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)


def _mamba_math(u, w, c):
    """The Mamba-2 mixer on u (B, T, D); `w` maps the checkpoint's names
    under `mixer.` to arrays, `c` is the config."""
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    K, inner = c["conv_kernel"], c["mamba_num_heads"] * c["mamba_head_dim"]
    B_, T, _ = u.shape
    proj = u @ w["in_proj.weight"].T
    z, xBC, dt = jnp.split(proj, [inner, 2 * inner + 2 * G * N], -1)
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    taps = w["conv1d.weight"][:, 0, :]                      # (channels, K)
    xBC = jax.nn.silu(w["conv1d.bias"] + sum(
        padded[:, k:k + T] * taps[:, k] for k in range(K)))
    x, Bm, Cm = jnp.split(xBC, [inner, inner + G * N], -1)
    x = x.reshape(B_, T, H, P)
    # head h reads the B and C of group h // (H / G)
    group_of_head = jnp.arange(H) // (H // G)
    Bm, Cm = (m.reshape(B_, T, G, N)[:, :, group_of_head] for m in (Bm, Cm))
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # (B, T, H)
    y = _recurrence(x, Bm, Cm, dt, -jnp.exp(w["A_log"]))
    y = y + w["D"][:, None] * x
    y = y.reshape(B_, T, inner) * jax.nn.silu(z)
    return _rms_by_group(y, w["norm.weight"], G, c["layer_norm_epsilon"]) @ w[
        "out_proj.weight"].T


def _attention_math(u, w, c):
    """Grouped-query causal attention without positions on u (B, T, D)."""
    H, G, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    B_, T, _ = u.shape
    # query head g * (H / G) + r reads k/v head g: indexed, never repeated
    q = (u @ w["q_proj.weight"].T).reshape(B_, T, G, H // G, d)
    k = (u @ w["k_proj.weight"].T).reshape(B_, T, G, d)
    v = (u @ w["v_proj.weight"].T).reshape(B_, T, G, d)
    rows = min(T, QUERY_ROWS)

    @jax.checkpoint
    def block(first):
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_rows, k) / np.sqrt(d)
        visible = (jnp.arange(T)[None, :] <= first + jnp.arange(rows)[:, None])
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)

    ctx = jax.lax.map(block, jnp.arange(0, T, rows))    # (T/rows, B, rows, ..)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B_, T, H * d)
    return ctx @ w["o_proj.weight"].T


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(indices, -1)


def _experts_math(m, w, c, first, picks):
    """An expert layer on rows m (S, D): the held experts' part of the routed
    sum and the shared expert -> (it, the picks' (weights, experts), (S, k)
    each). `picks` (S, k) int: the experts handed in."""
    n, k = c["n_routed_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ w["gate.weight"].T)
    top_e = (_top_k(s + w["gate.e_score_correction_bias"], k)
             if picks is None else picks)
    top_s = jnp.take_along_axis(s, top_e, -1)
    top_w = (top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
             * c["routed_scaling_factor"])
    held = first + jnp.arange(n)
    up, down = (jnp.stack([w[f"experts.{first + e}.{p}.weight"]
                           for e in range(n)])
                for p in ("up_proj", "down_proj"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    a = _relu2(jnp.einsum("sd,efd->esf", m, up))
    out = jnp.einsum("es,esf,edf->sd", weight, a, down)
    if c["moe_shared_expert_intermediate_size"]:
        out = out + _relu2(m @ w["shared_experts.up_proj.weight"].T) @ w[
            "shared_experts.down_proj.weight"].T
    return out, (top_w, top_e)


def _layer_math(h, w, picks, c, letter, first):
    """One layer, one sublayer; `first` the first expert held (an `E` layer)
    -> (h, the picks' (weights, experts) | None). `w` maps the names under
    `backbone.layers.<i>.` to arrays."""
    B, T, D = h.shape
    u = _rms(h, w["norm.weight"], c["layer_norm_epsilon"])
    under = {n[len("mixer."):]: v for n, v in w.items()
             if n.startswith("mixer.")}
    if letter == "M":
        return h + _mamba_math(u, under, c), None
    if letter == "*":
        return h + _attention_math(u, under, c), None
    out, routed = _experts_math(u.reshape(B * T, D), under, c, first, picks)
    return h + out.reshape(B, T, D), routed


def _nll_math(h, norm, head, targets, c):
    logits = _rms(h, norm, c["layer_norm_epsilon"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def letters_of(config):
    """The stack, a letter a layer: the first `num_hidden_layers` of the
    pattern."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


@functools.lru_cache(maxsize=None)
def _jitted(static, letter, first, given):
    """-> (plain, lean) of one letter's layer, or of the head (`letter`
    None): jitted functions compiled once for one architecture at "highest"
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

    if letter is None:
        nll = functools.partial(_nll_math, c=c)
        return highest(nll), lean(nll, 3)

    def layer(h, w, *handed):
        return _layer_math(h, w, handed[0] if given else None, c, letter,
                           first)

    return highest(layer), lean(lambda *args: layer(*args)[0], 2)


def _loss(sd, tokens, targets, config, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    static = tuple((k, config.get(k, 0)) for k in _STATIC)
    first = config.get("first_expert_held", 0)
    h = f32(sd["backbone.embeddings.weight"])[tokens]
    after, routed = [], []
    for i, letter in enumerate(letters_of(config)):
        scope = f"backbone.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        given = picks is not None and letter == "E"
        fn = _jitted(static, letter, first if letter == "E" else None,
                     given)[int(lean)]
        out = fn(h, w, *([picks[len(routed)]] if given else []))
        h, r = (out, None) if lean else out
        after.append(h)
        if letter == "E":
            routed.append(r)
    nll = _jitted(static, None, None, False)[int(lean)](
        h, f32(sd["backbone.norm_f.weight"]), f32(sd["lm_head.weight"]),
        targets)
    terms = {"nll": nll, "hidden": after}
    if routed and not lean:
        experts = jnp.stack([e for _, e in routed])
        width = config.get("num_routed_experts", config["n_routed_experts"])
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
    """-> f(sd, tokens, targets, config, picks=None) -> (loss, hidden,
    grads): the reference's own loss, the residual stream after each layer
    of the same pass (`loss_terms`' `hidden`), and the loss's `jax.grad` with
    respect to the weights called `names`, as a dict. Call it eagerly too."""
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
