"""Plain float32 reference of Qwen3-Next-80B-A3B's forward pass, next-token
loss, gradients and AdamW's step (Qwen, `model_type` `qwen3_next`, 2025-09;
the equations are those of `transformers`' `modeling_qwen3_next.py`:
`Qwen3NextRMSNorm`, `Qwen3NextGatedDeltaNet` with `torch_recurrent_gated_
delta_rule`, `Qwen3NextAttention`, `Qwen3NextSparseMoeBlock`, written from
ISSUE 68's lines and held to `transformers` 4.57's `Qwen3NextForCausalLM`
on copied weights by `tests/test_references_against_transformers.py`), on the
checkpoint's parameter names (`hetu_tpu/models/hf_qwen3_next.py`), for ONE
CHIP'S SHARE of each expert layer.

With h = embed[tokens], layer l (from 0; N the ZERO-CENTRED RMSNorm, N(x) = x
/ sqrt(mean(x^2) + `rms_norm_eps`) (1 + w), w stored; no bias anywhere):
  a = h + Mixer_l(N1_l(h));  h = a + MoE_l(N2_l(a))
layer l is gated attention where (l + 1) % `full_attention_interval` == 0, a
Gated DeltaNet elsewhere; every layer ends in the expert block.

Gated DeltaNet (Hk = `linear_num_key_heads` key heads of K =
`linear_key_head_dim`, Hv = `linear_num_value_heads` value heads of V =
`linear_value_head_dim`, r = Hv / Hk, `linear_conv_kernel_dim` taps), input u
(T, D):
  u W_qkvz^T, its columns grouped by KEY head: [q K | k K | v r V | z r V]
    Hk times; u W_ba^T: [b r | a r] Hk times;
  [q | k | v] <- SiLU(conv([q | k | v])): ONE causal depthwise convolution
    without bias over the 2 Hk K + Hv V columns, zeros before t = 0; z is not
    convolved;
  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias): ONE log-decay a
    VALUE head and position;
  a key head's q and k L2-normalised over K columns, x / sqrt(sum x^2 +
    1e-6), q times K^-0.5; key head j serves value heads r j .. r j + r - 1;
  S'_t = exp(g_t) S_{t-1}                              decay first, all of S
  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T         the delta update
  o_t = S_t^T q_t                                      AFTER t's own update
    a value head, S (K x V), S_0 = 0;
  out = (RMSNorm_head(o) w_n SiLU(z)) W_o^T: the norm over a head's V columns
    with ONE scale w_n (NOT 1 + w), the gate AFTER the norm.
Gated attention (H = `num_attention_heads` on `num_key_value_heads` k/v heads
of `head_dim`): u W_q^T a head [q hd | gate hd]; k, v; q and k through N a
head (one hd-wide w each); rotate-half RoPE at `rope_theta` on a head's FIRST
`partial_rotary_factor` hd columns; causal softmax(q k^T / sqrt(hd)) v; out =
(o . sigmoid(gate)) W_o^T, the gate a COLUMN.
Expert block on m = N2(a): p = softmax(m W_r^T) over the `num_routed_experts`
  routed experts; the picks the `num_experts_per_tok` largest, their weights p
  over their sum (`norm_topk_prob`); out = sum over the picks HELD HERE of w_i
  E_i(m) + sigmoid(m w_sg^T) S(m), E_i a SwiGLU of `moe_intermediate_size`, S
  ONE shared SwiGLU of `shared_expert_intermediate_size` with one gate logit a
  token. This chip holds experts [`first_expert_held`, + `num_experts`); what
  the others would add is left out, the shared expert is computed whole, and
  the partial h goes on. With every expert held (`num_routed_experts` absent)
  that is the whole model's layer.
Logits = Nf(h) lm_head^T (untied). Loss = the mean next-token cross-entropy +
`router_aux_loss_coef` x sum over the layers of E sum_e f_e P_e (f_e the
share of tokens that pick expert e, P_e its mean probability: HF
`load_balancing_loss_func` over the router's E outputs, a layer at a time).
After a step every weight moves by `adamw_after_step` (`assumed`).

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over the
layers, the recurrence a `lax.scan` over POSITIONS (no chunks, no cumulated
decay, no triangular system), every held expert on EVERY token masked by the
picks' weights, the top k by k argmaxes, full logits over the vocabulary
held: no sort, no gather of rows, no grouped matmul, no kernel, no fused
cross-entropy. The picks come from the reference's own router, or are handed
in (`picks`) and taken as they are. Departures from the equations as written,
none to the arithmetic:
- the softmax runs on blocks of at most `QUERY_ROWS` query rows against every
  key (`lax.map`), each under `jax.checkpoint`: 16 heads of 16,384 x 16,384
  scores are 17 GB.
- the scan over positions runs in segments of `TIME_SEGMENT` positions, each
  under `jax.checkpoint`: its backward pass keeps the state at the segments'
  starts (2 MB each) and makes a segment's again, where 16,384 kept states
  are 34 GB. The recurrence is position by position either way.
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

QUERY_ROWS = 512
TIME_SEGMENT = 64
_STATIC = ("num_attention_heads", "num_key_value_heads", "head_dim",
           "partial_rotary_factor", "rope_theta", "rms_norm_eps",
           "linear_num_key_heads", "linear_num_value_heads",
           "linear_key_head_dim", "linear_value_head_dim", "num_experts",
           "num_experts_per_tok", "norm_topk_prob")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _norm(x, w, eps):
    """The zero-centred RMSNorm: the stored weight is w of 1 + w."""
    return _rms(x, 1.0 + w, eps)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _conv_silu(x, taps):
    """SiLU of the causal depthwise convolution of x (B, T, C) with `taps`
    (C, 1, K) (a `Conv1d`'s weight), zeros before the sequence, no bias."""
    taps = taps[:, 0, :]
    K, T = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + T] * taps[:, j] for j in range(K)))


def _recurrence(q, k, v, g, beta):
    """The gated delta rule position by position: q, k (B, T, H, K), v (B, T,
    H, V), g and beta (B, T, H) -> o (B, T, H, V)."""
    B_, T, H, K = k.shape

    def step(S, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        S = jnp.exp(g_t)[..., None, None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))[:, :, None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    seg = math.gcd(T, TIME_SEGMENT)

    @jax.checkpoint
    def segment(S, at):
        return jax.lax.scan(step, S, at)

    # (B, T, ...) -> (T / seg, seg, B, ...): time first, cut into segments
    cut = lambda m: jnp.moveaxis(m, 1, 0).reshape((T // seg, seg)
                                                  + m.shape[:1] + m.shape[2:])
    _, o = jax.lax.scan(segment,
                        jnp.zeros((B_, H, K, v.shape[-1]), jnp.float32),
                        tuple(cut(m) for m in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _gdn_math(u, w, c):
    """The Gated DeltaNet mixer on u (B, T, D); `w` maps the checkpoint's
    names under `linear_attn.` to arrays."""
    Hk, Hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    K, V, r = c["linear_key_head_dim"], c["linear_value_head_dim"], Hv // Hk
    B_, T, _ = u.shape
    # the checkpoint's columns, a key head at a time
    x = (u @ w["in_proj_qkvz.weight"].T).reshape(B_, T, Hk, 2 * K + 2 * r * V)
    q, k = x[..., :K], x[..., K:2 * K]
    v = x[..., 2 * K:2 * K + r * V].reshape(B_, T, Hv * V)
    z = x[..., 2 * K + r * V:].reshape(B_, T, Hv, V)
    ba = (u @ w["in_proj_ba.weight"].T).reshape(B_, T, Hk, 2 * r)
    b, a = ba[..., :r].reshape(B_, T, Hv), ba[..., r:].reshape(B_, T, Hv)
    mixed = _conv_silu(jnp.concatenate(
        [q.reshape(B_, T, Hk * K), k.reshape(B_, T, Hk * K), v], -1),
        w["conv1d.weight"])
    q = mixed[..., :Hk * K].reshape(B_, T, Hk, K)
    k = mixed[..., Hk * K:2 * Hk * K].reshape(B_, T, Hk, K)
    v = mixed[..., 2 * Hk * K:].reshape(B_, T, Hv, V)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
    q, k = _l2(q) * K ** -0.5, _l2(k)
    # key head j serves value heads r j .. r j + r - 1
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    o = _recurrence(q, k, v, g, beta)
    o = _rms(o, w["norm.weight"], c["rms_norm_eps"]) * jax.nn.silu(z)
    return o.reshape(B_, T, Hv * V) @ w["out_proj.weight"].T


def _rope(x, theta, rot):
    """Rotate-half RoPE on the FIRST `rot` columns of each head of x (B, T,
    H, hd), positions 0 .. T - 1; the other columns pass."""
    T = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turn, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turn[..., rot // 2:], turn[..., :rot // 2]], -1)
    return jnp.concatenate(
        [turn * jnp.cos(angle) + half * jnp.sin(angle), rest], -1)


def _attn_math(u, w, c):
    """Gated attention on u (B, T, D); `w` maps the names under `self_attn.`
    to arrays."""
    B_, T, _ = u.shape
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    rot = int(hd * c["partial_rotary_factor"])
    qg = (u @ w["q_proj.weight"].T).reshape(B_, T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (u @ w["k_proj.weight"].T).reshape(B_, T, Hkv, hd)
    v = (u @ w["v_proj.weight"].T).reshape(B_, T, Hkv, hd)
    q = _rope(_norm(q, w["q_norm.weight"], c["rms_norm_eps"]),
              c["rope_theta"], rot)
    k = _rope(_norm(k, w["k_norm.weight"], c["rms_norm_eps"]),
              c["rope_theta"], rot)
    k, v = (jnp.repeat(x, H // Hkv, axis=2) for x in (k, v))
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(first):
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        visible = jnp.arange(T)[None, :] <= first + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(block, jnp.arange(0, T, rows))    # (T/rows, B, rows, ..)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B_, T, H, hd)
    return (ctx * jax.nn.sigmoid(gate)).reshape(B_, T, H * hd) @ w[
        "o_proj.weight"].T


def _top_k(p, k):
    """The k largest of each row by k argmaxes -> indices (S, k)."""
    indices = []
    for _ in range(k):
        i = jnp.argmax(p, -1)
        indices.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool), -jnp.inf, p)
    return jnp.stack(indices, -1)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate.T) * (m @ up.T)) @ down.T


def _shared_math(m, w):
    """The shared expert with its ONE gate logit a token."""
    return jax.nn.sigmoid(m @ w["shared_expert_gate.weight"].T) * _swiglu(
        m, *(w[f"shared_expert.{p}_proj.weight"]
             for p in ("gate", "up", "down")))


def _routed_math(m, w, c, first, picks=None):
    """The held experts' part of the routed sum on rows m (S, D) -> (it, the
    picks' (weights, experts), (S, k) each, the layer's balance loss).
    `picks` (S, k) int: the experts handed in. `w` maps the names under
    `mlp.` to arrays."""
    n, k = c["num_experts"], c["num_experts_per_tok"]
    p = jax.nn.softmax(m @ w["gate.weight"].T, -1)
    top_e = _top_k(p, k) if picks is None else picks
    top_w = jnp.take_along_axis(p, top_e, -1)
    if c["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    held = first + jnp.arange(n)
    gate, up, down = (jnp.stack([w[f"experts.{first + e}.{x}_proj.weight"]
                                 for e in range(n)])
                      for x in ("gate", "up", "down"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    a = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
         * jnp.einsum("sd,efd->esf", m, up))
    E = p.shape[-1]
    share = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32),
                    (0, 1)) / m.shape[0]
    balance = E * jnp.sum(share * jnp.mean(p, 0))
    return (jnp.einsum("es,esf,edf->sd", weight, a, down), (top_w, top_e),
            balance)


def _experts_math(m, w, c, first, picks=None):
    """A layer's expert block on rows m (S, D): the held experts' part of
    the routed sum and the gated shared expert -> (it, the picks, the
    balance loss)."""
    out, routed, balance = _routed_math(m, w, c, first, picks)
    return out + _shared_math(m, w), routed, balance


def _under(w, scope):
    return {n[len(scope):]: v for n, v in w.items() if n.startswith(scope)}


def _layer_math(h, w, picks, c, mixer, first):
    """One decoder layer: `mixer` "gdn" or "attention"; `first` the first
    expert held -> (h, the picks' (weights, experts), the balance loss). `w`
    maps the names under `model.layers.<i>.` to arrays."""
    B, T, D = h.shape
    u = _norm(h, w["input_layernorm.weight"], c["rms_norm_eps"])
    a = h + (_gdn_math(u, _under(w, "linear_attn."), c) if mixer == "gdn"
             else _attn_math(u, _under(w, "self_attn."), c))
    m = _norm(a, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    out, routed, balance = _experts_math(m.reshape(B * T, D),
                                         _under(w, "mlp."), c, first, picks)
    return a + out.reshape(B, T, D), routed, balance


def _nll_math(h, norm, head, targets, c):
    logits = _norm(h, norm, c["rms_norm_eps"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def layers_of(config):
    """[(mixer, the first expert held)] a layer of the stack, from 0."""
    held = config.get("first_expert_held", 0)
    every = config["full_attention_interval"]
    return [("attention" if (i + 1) % every == 0 else "gdn", held)
            for i in range(config["num_hidden_layers"])]


def _static(config):
    return tuple((k, config[k]) for k in _STATIC)


@functools.lru_cache(maxsize=None)
def _jitted(static, mixer, first, given):
    """-> (plain, lean) of one kind of layer, or of the head (`mixer` None):
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

    if mixer is None:
        nll = functools.partial(_nll_math, c=c)
        return highest(nll), lean(nll, 3)

    def layer(h, w, *handed):
        return _layer_math(h, w, handed[0] if given else None, c, mixer,
                           first)

    # the lean form returns (h, the balance loss): both enter the loss
    return highest(layer), lean(lambda *args: layer(*args)[::2], 2)


def _loss(sd, tokens, targets, config, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    static = _static(config)
    h = f32(sd["model.embed_tokens.weight"])[tokens]
    after, routed, balance = [], [], 0.0
    for i, (mixer, first) in enumerate(layers_of(config)):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        given = picks is not None
        fn = _jitted(static, mixer, first, given)[int(lean)]
        out = fn(h, w, *([picks[i]] if given else []))
        h, r, b = (out[0], None, out[1]) if lean else out
        after.append(h)
        routed.append(r)
        balance = balance + b
    nll = _jitted(static, None, None, False)[int(lean)](
        h, f32(sd["model.norm.weight"]), f32(sd["lm_head.weight"]), targets)
    terms = {"nll": nll, "hidden": after, "balance": balance}
    if not lean:
        experts = jnp.stack([e for _, e in routed])
        width = config.get("num_routed_experts", config["num_experts"])
        terms.update(
            experts=experts, weights=jnp.stack([w for w, _ in routed]),
            counts=jnp.sum(jax.nn.one_hot(experts, width, dtype=jnp.int32),
                           (1, 2)))
    coef = config["assumed"]["router_aux_loss_coef"]
    return jnp.mean(nll) + coef * balance, terms


def loss_terms(sd, tokens, targets, config, picks=None):
    """(loss, {nll (B, T) a token's next-token NLL, hidden [L x (B, T, D)]
    the residual stream after each layer, balance the layers' balance losses
    summed, experts (L, B*T, k) the picks, weights (L, B*T, k) theirs,
    counts (L, routed) the picks each expert took}) from HF-named weights.
    `picks` [L x (B*T, k) int]: the routing handed in, taken as it is (the
    reference's own router otherwise). Call it eagerly: its layers and head
    are jitted inside."""
    return _loss(sd, tokens, targets, config, picks)


def adamw_after_step(p, m, v, g, t, lr, adamw):
    """A weight after AdamW's step number `t` (1 the first) on gradient `g`
    from the moments `m` and `v`, numpy float64, `adamw` = {b1, b2, eps,
    weight_decay} (config.json `assumed`): m' = b1 m + (1 - b1) g, v' = b2 v
    + (1 - b2) g^2, p' = p - lr (m' / (1 - b1^t) / (sqrt(v' / (1 - b2^t)) +
    eps) + weight_decay p). Every leaf decays, a zero-centred norm's w too
    (towards a scale of 1)."""
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
