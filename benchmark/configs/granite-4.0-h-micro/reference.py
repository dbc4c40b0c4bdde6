"""Plain float32 reference of Granite 4.0-H's forward pass, next-token loss
and gradients (HuggingFace `GraniteMoeHybridForCausalLM`, `transformers`
`models/granitemoehybrid/modeling_granitemoehybrid.py`; the Mamba-2 mixer of
Dao & Gu 2024, arXiv:2405.21060), on the checkpoint's parameter names.

With h = embed[tokens] * embedding_multiplier, for layer l = 1..L:
  a = h + r * Mixer_l(N1_l(h))          N1 input_layernorm
  h = a + r * MLP_l(N2_l(a))            N2 post_attention_layernorm
r = residual_multiplier, both norms RMSNorm, MLP = out(silu(gate) * up) with
[gate | up] = input_linear (`shared_mlp`). Mixer_l by `layer_types[l]`:

"mamba" (H heads of P channels, state N, G groups, width-K convolution):
  [z | xBC | dt] = u W_in^T
  xBC_t = silu(b + sum_k w[:, k] * xBC_{t-(K-1)+k}),  zeros before t = 0
  [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      a head, S_0 = 0
  y_t = S_t C_t + D x_t
  out = (RMSNorm(y * silu(z)) * w_norm) W_out^T     over all H*P channels
"attention": q, k, v projections without bias or position embedding, each
  of the `num_key_value_heads` k/v heads shared by a group of query heads,
  softmax(q k^T * attention_multiplier + causal mask) v, o_proj.
Logits = (Nf(h) embed^T) / logits_scaling (the head is the embedding), loss
the mean next-token cross-entropy.

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), a Python loop over
layers, the recurrence as a `lax.scan` over TIME (no chunks, no cumulative
decay), full logits: no kernel, no fused cross-entropy. Departures from the
published description, none to the arithmetic:
- HF's `torch_forward` computes the recurrence in chunked form (`segment_sum`);
  this is the recurrence it factorises, position by position. The tests
  hold the two to each other on copied weights.
- HF clamps dt to `time_step_limit` = (0, inf): no clamp.
- the softmax runs on blocks of at most 1,024 query rows against every key
  (`lax.map`): 32 heads of 8,192 x 8,192 scores are 8.6 GB on a 16 GB chip.
- each kind of layer and the head are ONE jitted function, called eagerly (a
  float32 "highest" matmul costs the TPU's compiler about a second), and
  `grads_of` keeps only each call's INPUTS for the backward pass and runs
  the layer, or the head, again there under `jax.vjp` in one jitted program
  (the time scan keeps 2 MB of state a position, a head call its logits).
  `loss_terms` is the plain forward, and the tests hold `grads_of` to
  `jax.grad` of it.
"""
import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 1024


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mamba_math(u, w, c):
    """The Mamba-2 mixer on u (B, T, D); `w` maps the checkpoint's names
    under `mamba.` to arrays, `c` is the config."""
    H, P, N, G = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    K, inner = c["mamba_d_conv"], c["mamba_n_heads"] * c["mamba_d_head"]
    B_, T, _ = u.shape
    proj = u @ w["in_proj.weight"].T
    z, xBC, dt = jnp.split(proj, [inner, 2 * inner + 2 * G * N], -1)
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    taps = w["conv1d.weight"][:, 0, :]                      # (channels, K)
    xBC = jax.nn.silu(w["conv1d.bias"] + sum(
        padded[:, k:k + T] * taps[:, k] for k in range(K)))
    x, Bm, Cm = jnp.split(xBC, [inner, inner + G * N], -1)
    x = x.reshape(B_, T, H, P)
    # a group's B and C serve its H / G heads
    Bm, Cm = (jnp.repeat(m.reshape(B_, T, G, N), H // G, 2) for m in (Bm, Cm))
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # (B, T, H)
    A = -jnp.exp(w["A_log"])

    def step(S, at_t):
        x_t, B_t, C_t, dt_t = at_t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((B_, H, P, N), jnp.float32),
                        tuple(jnp.moveaxis(m, 1, 0) for m in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    y = y.reshape(B_, T, inner) * jax.nn.silu(z)
    return _rms(y, w["norm.weight"], c["rms_norm_eps"]) @ w[
        "out_proj.weight"].T


def _attention_math(u, w, c):
    """Grouped-query causal attention without positions on u (B, T, D)."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    B_, T, D = u.shape
    hd = D // heads
    q = (u @ w["q_proj.weight"].T).reshape(B_, T, heads, hd)
    k, v = (jnp.repeat((u @ w[f"{p}_proj.weight"].T).reshape(B_, T, kv, hd),
                       heads // kv, 2) for p in "kv")
    rows = min(T, QUERY_ROWS)

    def block(first):
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) * c[
            "attention_multiplier"]
        visible = (jnp.arange(T)[None, :] <= first + jnp.arange(rows)[:, None])
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(block, jnp.arange(0, T, rows))    # (T/rows, B, rows, ..)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B_, T, D)
    return ctx @ w["o_proj.weight"].T


def _layer_math(h, w, c, kind):
    """One decoder layer on h (B, T, D); `w` maps the names under
    `model.layers.<i>.` to arrays."""
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    under = lambda scope: {n[len(scope):]: v for n, v in w.items()
                           if n.startswith(scope)}
    u = _rms(h, w["input_layernorm.weight"], eps)
    mixed = (_mamba_math(u, under("mamba."), c) if kind == "mamba"
             else _attention_math(u, under("self_attn."), c))
    h = h + r * mixed
    u = _rms(h, w["post_attention_layernorm.weight"], eps)
    gate, up = jnp.split(u @ w["shared_mlp.input_linear.weight"].T, 2, -1)
    return h + r * ((jax.nn.silu(gate) * up)
                    @ w["shared_mlp.output_linear.weight"].T)


def _head_math(h, norm, embed, c):
    """-> logits (B, T, V) of the tied head."""
    return (_rms(h, norm, c["rms_norm_eps"]) @ embed.T) / c["logits_scaling"]


def _nll_math(h, norm, embed, targets, c):
    logits = _head_math(h, norm, embed, c)
    return (jax.scipy.special.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0])


_STATIC = ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
           "mamba_d_conv", "num_attention_heads", "num_key_value_heads",
           "attention_multiplier", "residual_multiplier", "logits_scaling",
           "rms_norm_eps")


@functools.lru_cache(maxsize=None)
def _jitted(static):
    """({"mamba", "attention": a layer; "head": logits; "nll"}, the same
    keeping only their inputs for the backward pass), each jitted once for
    one architecture at "highest" precision."""
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
             for kind in ("mamba", "attention")}
    maths["head"] = functools.partial(_head_math, c=c)
    nll = functools.partial(_nll_math, c=c)
    plain = {name: highest(math) for name, math in maths.items()}
    return ({**plain, "nll": highest(nll)},
            {**{name: lean(math, 2) for name, math in maths.items()},
             "nll": lean(nll, 3)})


def _trunk(sd, tokens, config, lean=False):
    """-> (the jitted functions, [h after each layer], the final h)."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    fns = _jitted(tuple((k, config[k]) for k in _STATIC))[int(lean)]
    h = f32(sd["model.embed_tokens.weight"])[tokens] * config[
        "embedding_multiplier"]
    after = []
    for i in range(config["num_hidden_layers"]):
        scope = f"model.layers.{i}."
        h = fns[config["layer_types"][i]](
            h, {n[len(scope):]: f32(v) for n, v in sd.items()
                if n.startswith(scope)})
        after.append(h)
    return fns, after, h


def logits(sd, tokens, config):
    """Full logits (B, T, V) from HF-named weights. Call it eagerly."""
    fns, _, h = _trunk(sd, tokens, config)
    return fns["head"](h, jnp.asarray(sd["model.norm.weight"], jnp.float32),
                       jnp.asarray(sd["model.embed_tokens.weight"],
                                   jnp.float32))


def _loss(sd, tokens, targets, config, lean=False):
    fns, after, h = _trunk(sd, tokens, config, lean)
    nll = fns["nll"](h, jnp.asarray(sd["model.norm.weight"], jnp.float32),
                     jnp.asarray(sd["model.embed_tokens.weight"],
                                 jnp.float32), targets)
    return jnp.mean(nll), {"nll": nll, "hidden": after}


def loss_terms(sd, tokens, targets, config):
    """(loss, {nll (B, T) a token's next-token NLL, hidden [L x (B, T, D)]
    the residual stream after each layer}) from HF-named weights. Call it
    eagerly: its layers and head are jitted inside."""
    return _loss(sd, tokens, targets, config)


def grads_of(names):
    """-> f(sd, tokens, targets, config) -> (loss, grads): the reference's
    own loss and its `jax.grad` with respect to the weights called `names`,
    as a dict (the tied embedding's holds the lookup's and the head's
    parts). Call it eagerly too."""
    def grads(sd, tokens, targets, config):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _loss({**rest, **part}, tokens, targets, config,
                         lean=True)[0]

        return jax.value_and_grad(loss)({n: jnp.asarray(sd[n], jnp.float32)
                                         for n in names})
    return grads
