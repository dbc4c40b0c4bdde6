"""Plain float32 reference of Ouro's forward pass, stage-I training loss and
gradients (Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; the HuggingFace `OuroForCausalLM` checkpoint's
parameter names).

With h0 = embed[tokens], for pass t = 1..T (T = `total_ut_steps`) and layer
l = 1..L, the SAME weights every pass:
  a = x + N2_l(Attn_l(N1_l(x)))        N1 input_layernorm, N2 input_layernorm_2
  y = a + N4_l(MLP_l(N3_l(a)))         N3 post_attention_layernorm,
                                       N4 post_attention_layernorm_2
all RMSNorm; Attn causal multi-head attention with RoPE (rotate_half, theta)
on q and k, no biases; MLP = down(silu(gate(x)) * up(x)). After the L layers
of a pass h_t = Nf(y_L) (`model.norm`): exit t's state AND pass t+1's input.
Exit gate (`model.early_exit_gate`, Linear(D, 1) with bias):
  stop_t = sigmoid(w . h_t + b) a token;
  q(t) = stop_t * prod_{j<t} (1 - stop_j) for t < T,
  q(T) = prod_{j<T} (1 - stop_j), so sum_t q(t) = 1.
Logits of exit t: lm_head(h_t), NLL_t the next-token cross-entropy a token.
Loss = mean over tokens of [sum_t q(t) NLL_t - beta H(q)],
H(q) = -sum_t q(t) log q(t), beta = `assumed.exit_entropy_weight`.

Straightforward `jax.numpy`: float32, matmuls at "highest" precision (on a
TPU a float32 matmul is otherwise one bfloat16 pass), Python loops over
passes and layers, the whole (T, T) score matrix, full logits at every exit,
q as the products above (no log space): no scan, no kernel, no fused
cross-entropy. Two concessions to the machine, neither to the arithmetic:
the block is ONE jitted function called T*L times and the exit head one
called T times, eagerly (a float32 "highest" matmul costs the TPU's compiler
about a second, and an unrolled program of 24 blocks and their gradients
would take it minutes); and `grads_of` keeps only each call's INPUTS for
the backward pass and runs the block, or the exit head, again there under
`jax.vjp` (24 applications at 4,096 tokens would otherwise hold 24 GiB of
attention probabilities, and four exits 6 GiB of logits, on a 16 GB chip). `loss_terms` is the plain forward, and the
tests hold `grads_of` to `jax.grad` of it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_PARTS = ("input_layernorm", "input_layernorm_2",
               "post_attention_layernorm", "post_attention_layernorm_2",
               "self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
               "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj",
               "mlp.down_proj")
GATE_W, GATE_B = "model.early_exit_gate.weight", "model.early_exit_gate.bias"


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


def _block_math(x, w, heads, eps, theta):
    """One decoder layer on x (B, T, D); `w` maps `LAYER_PARTS` to arrays in
    the checkpoint's (out, in) orientation."""
    B, T, D = x.shape
    hd = D // heads
    a = _rms(x, w["input_layernorm"], eps)
    q, k, v = ((a @ w[f"self_attn.{p}_proj"].T).reshape(B, T, heads, hd)
               .transpose(0, 2, 1, 3) for p in "qkv")
    q, k = _rope(q, theta), _rope(k, theta)
    scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + _rms(ctx @ w["self_attn.o_proj"].T, w["input_layernorm_2"], eps)
    m = _rms(x, w["post_attention_layernorm"], eps)
    u = jax.nn.silu(m @ w["mlp.gate_proj"].T) * (m @ w["mlp.up_proj"].T)
    return x + _rms(u @ w["mlp.down_proj"].T,
                    w["post_attention_layernorm_2"], eps)


def _exit_math(y, norm, gate_w, gate_b, head, targets, eps):
    """The end of one pass: y (B, T, D) -> (h the normed state, nll (B, T),
    stop (B, T) the gate's probability of stopping here)."""
    h = _rms(y, norm, eps)
    stop = jax.nn.sigmoid(h @ gate_w.reshape(-1) + gate_b.reshape(()))
    logits = h @ head.T
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return h, nll, stop


@functools.lru_cache(maxsize=None)
def _jitted(heads, eps, theta):
    """((block, exit head), (the same two keeping only their inputs for the
    backward pass)), each jitted once for one architecture, at "highest"
    precision."""
    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    block_math = functools.partial(_block_math, heads=heads, eps=eps,
                                   theta=theta)
    block = highest(block_math)

    def lean(fn, math, n):
        """`fn` for `jax.grad`: keeps its inputs alone and runs `math` again
        under `jax.vjp` in the backward pass; the first `n` arguments are
        differentiated, the rest (integers) are not."""
        vjp = highest(lambda args, g: jax.vjp(
            lambda *diff: math(*diff, *args[n:]), *args[:n])[1](g))
        lean_fn = jax.custom_vjp(fn)
        lean_fn.defvjp(lambda *args: (fn(*args), args),
                       lambda args, g: vjp(args, g) + (None,) * (
                           len(args) - n))
        return lean_fn

    exit_math = functools.partial(_exit_math, eps=eps)
    exit_head = highest(exit_math)
    return (block, exit_head), (lean(block, block_math, 2),
                                lean(exit_head, exit_math, 5))


def _forward(sd, tokens, targets, config, lean=False):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    block, exit_head = _jitted(
        config["num_attention_heads"], config["rms_norm_eps"],
        float(config["rope_theta"]))[int(lean)]
    steps, beta = config["total_ut_steps"], config["assumed"][
        "exit_entropy_weight"]
    layers = [{part: f32(sd[f"model.layers.{i}.{part}.weight"])
               for part in LAYER_PARTS}
              for i in range(config["num_hidden_layers"])]
    x = f32(sd["model.embed_tokens.weight"])[tokens]
    exits, nlls, stops = [], [], []
    for _ in range(steps):
        for w in layers:
            x = block(x, w)
        x, nll, stop = exit_head(
            x, f32(sd["model.norm.weight"]), f32(sd[GATE_W]), f32(sd[GATE_B]),
            f32(sd["lm_head.weight"]), targets)
        exits.append(x), nlls.append(nll), stops.append(stop)
    q, left = [], 1.0
    for t in range(steps - 1):
        q.append(stops[t] * left)
        left = left * (1.0 - stops[t])
    q.append(left)
    q, nll = jnp.stack(q), jnp.stack(nlls)
    # 0 log 0 = 0: an exit nobody can reach adds nothing to the entropy
    entropy = -jnp.sum(jnp.where(q > 0, q * jnp.log(jnp.where(q > 0, q, 1.0)),
                                 0.0), 0)
    expected = jnp.sum(q * nll, 0)
    loss = jnp.mean(expected - beta * entropy)
    return loss, {"nll": nll, "q": q, "exits": jnp.stack(exits),
                  "expected_nll": jnp.mean(expected),
                  "entropy": jnp.mean(entropy)}


def loss_terms(sd, tokens, targets, config):
    """(loss, {nll (T, B, S) a token's NLL at every exit, q (T, B, S) the
    exit distribution, exits (T, B, S, D) every pass's normed state,
    expected_nll and entropy the two terms' means}) from HF-named weights.
    Call it eagerly: its block and exit head are jitted inside."""
    return _forward(sd, tokens, targets, config)


def grads_of(names):
    """-> f(sd, tokens, targets, config): `jax.grad` of the reference's own
    loss with respect to the weights called `names`, as a dict. Call it
    eagerly too."""
    def grads(sd, tokens, targets, config):
        rest = {n: v for n, v in sd.items() if n not in names}

        def loss(part):
            return _forward({**rest, **part}, tokens, targets, config,
                            lean=True)[0]

        return jax.grad(loss)({n: jnp.asarray(sd[n], jnp.float32)
                               for n in names})
    return grads
