"""Plain float32 reference of Kimi-Linear-48B-A3B's forward pass, next-token
loss, gradients and AdamW's step (Moonshot AI, `model_type` `kimi_linear`,
arXiv:2510.26692; the model's public `modeling_kimi.py`: `KimiDeltaAttention`,
`KimiMLAAttention`, `KimiMoEGate`, `KimiSparseMoeBlock`, `KimiMLP`;
flash-linear-attention's `fla/ops/kda/naive.py` for the recurrence; no
`transformers` release here carries `kimi_linear`: the equations are ISSUE
66's, written from the config's keys), on the checkpoint's parameter names
(`hetu_tpu/models/hf_kimi_linear.py`), for ONE CHIP'S SHARE of each expert
layer.

With h = embed[tokens], layer l (N1 `input_layernorm`, N2
`post_attention_layernorm`, RMSNorm eps `rms_norm_eps`, no bias anywhere):
  a = h + Mixer_l(N1_l(h));  h = a + FFN_l(N2_l(a))
`linear_attn_config` counts layers from ONE: `kda_layers` are KDA mixers,
`full_attn_layers` latent attention.

KDA (H = `linear_attn_config.num_heads` heads, K = its `head_dim` columns for
k and for v, `short_conv_kernel_size` taps), input u (T, D):
  q = SiLU(conv(u Wq^T)), k = SiLU(conv(u Wk^T)), v = SiLU(conv(u Wv^T)):
    each its OWN causal depthwise convolution without bias, zeros before t = 0;
  a head's q and k L2-normalised over its K columns, x / sqrt(sum x^2 +
    1e-6), and q times K^-0.5;
  g = -exp(A_log[head]) softplus((u Wfa^T) Wfb^T + dt_bias): the log-decay, a
    CHANNEL; alpha = exp(g);    beta = sigmoid(u Wb^T), a HEAD;
  S'_t = Diag(alpha_t) S_{t-1}                         decay first, a row of S
  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T         the delta update
  o_t = S_t^T q_t                                      AFTER t's own update
    a head, S (K key columns x K value columns), S_0 = 0;
  out = (RMSNorm_head(o) w_o_norm sigmoid((u Wga^T) Wgb^T)) Wo^T: the norm
    over each head's K columns (one scale shared by the heads), the gate
    AFTER the norm.
Latent attention (`q_lora_rank` null, `mla_use_nope` true: NOTHING rotates):
  q = u Wq^T, a head [`qk_nope_head_dim` | `qk_rope_head_dim`]; [c | k_r] = u
  Wkv_a^T, c of `kv_lora_rank` columns, k_r ONE key a token; [k_nope | v] a
  head = RMSNorm(c; `kv_a_layernorm`) Wkv_b^T; k = [k_nope | k_r], the one
  k_r every head's, unrotated; softmax(q k^T / sqrt(nope + rope) + causal
  mask) v; Wo. `rope_theta` is read by nothing.
FFN_l, l < `first_k_dense_replace`: down(silu(gate u) . up u), width
  `intermediate_size`. Otherwise: s = sigmoid(u Wg^T), one score for each of
  the `num_routed_experts` experts; the picks are the `num_experts_per_token`
  largest of s + b (`e_score_correction_bias`; it enters nowhere else;
  `num_expert_group` = `topk_group` = 1); w_i = s_i / (sum over ALL the picks
  of s + 1e-20) (`moe_renormalize`) x `routed_scaling_factor`; out = sum over
  the picks HELD HERE of w_i E_i(u) + S(u), E_i a SwiGLU of
  `moe_intermediate_size` (w1 gate, w3 up, w2 down), S ONE shared SwiGLU of
  `num_shared_experts` x `moe_intermediate_size` on every token. This chip
  holds experts [`first_expert_held`, + `num_experts`); what the others would
  add is left out, the shared expert is computed whole, and the partial h
  goes on. With every expert held (`num_routed_experts` absent) that is the
  whole model's layer.
Logits = Nf(h) lm_head^T (untied), loss the mean next-token cross-entropy; no
auxiliary loss. After a step the bias moves by `bias_after_step` and every
other weight by `adamw_after_step` (both `assumed`).

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
  key (`lax.map`), each under `jax.checkpoint`: 32 heads of 16,384 x 16,384
  scores are 34 GB.
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
_STATIC = ("num_attention_heads", "rms_norm_eps", "kv_lora_rank",
           "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
           "num_experts", "num_experts_per_token", "moe_renormalize",
           "routed_scaling_factor", "num_shared_experts")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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
    """The gated delta rule position by position: q, k, g (B, T, H, K), v
    (B, T, H, V), beta (B, T, H) -> o (B, T, H, V)."""
    B_, T, H, K = k.shape

    def step(S, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        S = jnp.exp(g_t)[..., None] * S
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


def _kda_math(u, w, c):
    """The KDA mixer on u (B, T, D); `w` maps the checkpoint's names under
    `self_attn.` to arrays."""
    la = dict(c["linear_attn_config"])
    H, K = la["num_heads"], la["head_dim"]
    B_, T, _ = u.shape
    heads = lambda x: x.reshape(B_, T, H, K)
    q, k, v = (heads(_conv_silu(u @ w[f"{n}_proj.weight"].T,
                                w[f"{n}_conv1d.weight"])) for n in "qkv")
    q, k = _l2(q) * K ** -0.5, _l2(k)
    g = -jnp.exp(w["A_log"].reshape(H, 1)) * heads(jax.nn.softplus(
        (u @ w["f_a_proj.weight"].T) @ w["f_b_proj.weight"].T + w["dt_bias"]))
    beta = jax.nn.sigmoid(u @ w["b_proj.weight"].T)
    o = _recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid(
        (u @ w["g_a_proj.weight"].T) @ w["g_b_proj.weight"].T)
    o = _rms(o, w["o_norm.weight"], c["rms_norm_eps"]) * heads(gate)
    return o.reshape(B_, T, H * K) @ w["o_proj.weight"].T


def _mla_math(u, w, c):
    """Latent attention on u (B, T, D), nothing rotated (`mla_use_nope`)."""
    B_, T, _ = u.shape
    H, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    q = (u @ w["q_proj.weight"].T).reshape(B_, T, H, nope + rope)
    compressed = u @ w["kv_a_proj_with_mqa.weight"].T
    latent = _rms(compressed[..., :rank], w["kv_a_layernorm.weight"],
                  c["rms_norm_eps"])
    kv = (latent @ w["kv_b_proj.weight"].T).reshape(B_, T, H, nope + vd)
    k_shared = compressed[:, :, None, rank:]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_shared, (B_, T, H, rope))], -1)
    v = kv[..., nope:]
    rows = min(QUERY_ROWS, T)

    @jax.checkpoint
    def block(first):
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(
            nope + rope)
        visible = jnp.arange(T)[None, :] <= first + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(block, jnp.arange(0, T, rows))    # (T/rows, B, rows, ..)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B_, T, H * vd)
    return ctx @ w["o_proj.weight"].T


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
    return _swiglu(m, *(w[f"shared_experts.{p}_proj.weight"]
                        for p in ("gate", "up", "down")))


def _routed_math(m, w, c, first, picks=None):
    """The held experts' part of the routed sum on rows m (S, D) -> (it, the
    picks' (weights, experts), (S, k) each). `picks` (S, k) int: the experts
    handed in. `w` maps the names under `block_sparse_moe.` to arrays."""
    n, k = c["num_experts"], c["num_experts_per_token"]
    s = jax.nn.sigmoid(m @ w["gate.weight"].T)
    top_e = (_top_k(s + w["gate.e_score_correction_bias"], k)
             if picks is None else picks)
    top_s = jnp.take_along_axis(s, top_e, -1)
    if c["moe_renormalize"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_w = top_s * c["routed_scaling_factor"]
    held = first + jnp.arange(n)
    gate, up, down = (jnp.stack([w[f"experts.{first + e}.{p}.weight"]
                                 for e in range(n)])
                      for p in ("w1", "w3", "w2"))
    weight = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                               top_w[None], 0.0), -1)            # (E, S)
    a = (jax.nn.silu(jnp.einsum("sd,efd->esf", m, gate))
         * jnp.einsum("sd,efd->esf", m, up))
    return jnp.einsum("es,esf,edf->sd", weight, a, down), (top_w, top_e)


def _experts_math(m, w, c, first, picks=None):
    """An expert layer's MLP half on rows m (S, D): the held experts' part
    of the routed sum and the shared expert -> (it, the picks)."""
    out, routed = _routed_math(m, w, c, first, picks)
    if c["num_shared_experts"]:
        out = out + _shared_math(m, w)
    return out, routed


def _under(w, scope):
    return {n[len(scope):]: v for n, v in w.items() if n.startswith(scope)}


def _layer_math(h, w, picks, c, mixer, first):
    """One decoder layer: `mixer` "kda" or "mla"; `first` None for the dense
    MLP, else the first expert held -> (h, the picks' (weights, experts) |
    None). `w` maps the names under `model.layers.<i>.` to arrays."""
    B, T, D = h.shape
    u = _rms(h, w["input_layernorm.weight"], c["rms_norm_eps"])
    mix = _kda_math if mixer == "kda" else _mla_math
    a = h + mix(u, _under(w, "self_attn."), c)
    m = _rms(a, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    if first is None:
        return a + _swiglu(m, *(w[f"mlp.{p}_proj.weight"]
                                for p in ("gate", "up", "down"))), None
    out, routed = _experts_math(m.reshape(B * T, D),
                                _under(w, "block_sparse_moe."), c, first,
                                picks)
    return a + out.reshape(B, T, D), routed


def _nll_math(h, norm, head, targets, c):
    logits = _rms(h, norm, c["rms_norm_eps"]) @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def layers_of(config):
    """[(mixer, None | the first expert held)] a layer of the stack: the
    model's layers 1 .. `num_hidden_layers`, counted from one."""
    la = config["linear_attn_config"]
    held = config.get("first_expert_held", 0)
    out = []
    for i in range(1, config["num_hidden_layers"] + 1):
        mixer = "kda" if i in la["kda_layers"] else "mla"
        assert mixer == "kda" or i in la["full_attn_layers"], i
        out.append((mixer,
                    None if i <= config["first_k_dense_replace"] else held))
    return out


def _static(config):
    la = config["linear_attn_config"]
    return tuple((k, config.get(k, 0)) for k in _STATIC) + ((
        "linear_attn_config",
        tuple((k, la[k]) for k in ("num_heads", "head_dim"))),)


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

    return highest(layer), lean(lambda *args: layer(*args)[0], 2)


def _loss(sd, tokens, targets, config, picks=None, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    static = _static(config)
    h = f32(sd["model.embed_tokens.weight"])[tokens]
    after, routed = [], []
    for i, (mixer, first) in enumerate(layers_of(config)):
        scope = f"model.layers.{i}."
        w = {n[len(scope):]: f32(v) for n, v in sd.items()
             if n.startswith(scope)}
        given = picks is not None and first is not None
        fn = _jitted(static, mixer, first, given)[int(lean)]
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
