"""Kimi Delta Attention's recurrence in its chunked form, in XLA (Kimi
Linear, arXiv:2510.26692, section 2; flash-linear-attention ``fla/ops/kda``).

A head carries a state S (K key columns x V value columns), S_0 = 0; with a
log-decay g_t a CHANNEL (alpha_t = exp(g_t) in (0, 1)) and a step beta_t a
head:

  S'_t = Diag(alpha_t) S_{t-1};  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;
  o_t = S_t^T q_t.

Inside a chunk of C positions, G the log-decay cumulated from the chunk's
start (inclusive) and S_0 the state entering it:

  (I + A) U = Diag(beta) (V - (K . exp G) S_0),
      A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c]),  i < r
  o_r = (q_r . exp G_r)^T S_0 + sum_{i <= r} P[r, i] u_i,
      P[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])
  S_C = Diag(exp G_C) S_0 + sum_i (k_i . exp(G_C - G_i)) u_i^T

and U = U~ - W S_0 with [U~ | W] = (I + A)^-1 Diag(beta) [V | K . exp G]:
everything but the last line of ``_chunk_step`` is the chunks' own and runs
for all of them at once; the state alone goes through the chunks in sequence.

TWO TRAPS, and what is done about each.
(i) exp(G_r - G_i) is never written as exp(G_r) / exp(G_i): 1 / exp(G)
overflows float32 once a channel decays by e^-88 inside a chunk (at the
initial weights -102 is reached; a trained softplus is unbounded). The
difference G_r - G_i <= 0 is taken FIRST, pairwise, on the diagonal
sub-blocks of ``SUB`` positions (a (SUB, SUB, K) tensor a sub-block); a
sub-block's pairs with the positions BEFORE it go through the sub-block's
first position R0 as two factors, exp(G_r - G_R0) and exp(G_R0 - G_i), both
<= 1, and one matmul.
(ii) the pairwise tensor of every chunk at once is 17 GB a layer at 16,384
tokens: the chunks' own work runs a SEGMENT of ``SEGMENT_CHUNKS`` chunks at
a time under ``jax.checkpoint`` (its backward pass keeps a segment's inputs
and the state entering it, and makes the segment again), which keeps
``REMAT_KDA_INV`` alone: the inverse of I + A, 64 x 64 a chunk and head.

A DECAY A HEAD (Gated DeltaNet: g_t ONE scalar a head and position, alpha_t
a multiple of the identity) is the same rule, and ``scan`` takes it as g (B,
T, Hv) with q and k a KEY head, (B, T, Hk, K), each serving Hv / Hk value
heads. Of the lines above only the products with exp(G_r[c] - G_i[c]) inside
the sum over c are a CHANNEL's (``pair_products`` and its sub-blocks, trap
(i)'s device). With G_r a scalar the exponential leaves the sum, A[r, i] =
beta_r (k_r . k_i) exp(G_r - G_i): plain products of positions times ONE (C,
C) matrix of decays, no pairwise tensor. That form runs as two Mosaic
kernels of its own (``kernels/gdn.py``, PR 69: the Gram matrix of [k; q]
once a key head in one bfloat16 pass, no levels, q and k bfloat16 in every
product) wherever their rule admits the call; where it refuses (every CPU
run, a mesh, another chunk) ``scan`` repeats the key heads, BROADCASTS g over
the head's K columns and runs the form below as any channel's decay. (PR 68
wrote the head form in XLA, timed it on the v5e and took it out again: 63.5
ms a scan forward and backward against 38.4 for the channel kernels with g
broadcast; the head kernels read 20.6 against 40.1, docs/KERNELS.md.)

float32: g, G, the pairwise products, the triangular system and its inverse,
U, the carried state and o; q, k and v arrive in the compute dtype and are
read as float32. Every matmul here is float32 at HIGHEST precision (on a TPU
a float32 matmul is otherwise one bfloat16 pass). The backward pass is JAX's
own through this form; the inverse alone has a rule of its own (d X = -X dA
X, as ``jnp.linalg.inv`` has), so that the forward substitution's rows are no
residuals.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..kernels import gdn as gdn_kernel, kda as kda_kernel
from ..telemetry import tracing
from ..telemetry.tracing import (REMAT_KDA_INV, SCOPE_KDA_SCAN,
                                 SCOPE_KDA_SOLVE)

_log = logging.getLogger(__name__)

SUB = 16                # positions a diagonal sub-block (trap (i))
SEGMENT_CHUNKS = 16     # chunks a segment (trap (ii))
_HI = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HI)
_T = lambda x: jnp.swapaxes(x, -1, -2)


def _forward_substitution(N):
    """(I + N)^-1 of strictly lower triangular N (..., n, n), n <= SUB, row
    by row: X_r = e_r - sum_{i < r} N[r, i] X_i. Exact sums on the VPU."""
    n = N.shape[-1]
    eye = jnp.eye(n, dtype=N.dtype)
    rows = [jnp.broadcast_to(eye[0], N.shape[:-2] + (n,))]
    for r in range(1, n):
        rows.append(eye[r] - jnp.sum(
            N[..., r, :r, None] * jnp.stack(rows, -2), -2))
    return jnp.stack(rows, -2)


def _inverse(A):
    """(I + A)^-1 of strictly lower triangular A (..., C, C), exactly: the
    two halves' diagonal blocks first (together, one batch), then the block
    below them, -X2 A21 X1."""
    C = A.shape[-1]
    if C <= SUB:
        return _forward_substitution(A)
    m = C // 2
    if C % 2:
        raise ValueError(f"a chunk of {C} positions: {SUB} x a power of two")
    X1, X2 = _inverse(jnp.stack([A[..., :m, :m], A[..., m:, m:]]))
    below = -_mm(_mm(X2, A[..., m:, :m]), X1)
    return jnp.concatenate(
        [jnp.concatenate([X1, jnp.zeros_like(X1)], -1),
         jnp.concatenate([below, X2], -1)], -2)


@jax.custom_jvp
def unit_lower_inverse(A):
    """(I + A)^-1, A (..., C, C) strictly lower triangular, float32."""
    return _inverse(A)


@unit_lower_inverse.defjvp
def _unit_lower_inverse_jvp(primals, tangents):
    # the name sits on the value the backward pass reads
    X = checkpoint_name(_inverse(primals[0]), REMAT_KDA_INV)
    return X, -_mm(_mm(X, tangents[0]), X)


def pair_products(q, k, G):
    """-> (KK, QK), (..., C, C) float32: sum_c a_r[c] k_i[c] exp(G_r[c] -
    G_i[c]) for i <= r and 0 above the diagonal, a = k and a = q; q, k, G
    (..., C, K) float32, G the cumulated log-decay (decreasing down a
    chunk). Trap (i): no exponent here is positive."""
    C, K = q.shape[-2:]
    sub = min(SUB, C)
    if C % sub:
        raise ValueError(f"a chunk of {C} positions: whole sub-blocks of "
                         f"{sub}")
    nb = C // sub
    cut = lambda x: x.reshape(x.shape[:-2] + (nb, sub, K))
    Gs, qs, ks = cut(G), cut(q), cut(k)
    # the diagonal sub-blocks, pairwise: (..., nb, sub, sub, K)
    at = jnp.arange(sub)
    E = jnp.exp(jnp.where((at[:, None] >= at[None, :])[..., None],
                          Gs[..., :, None, :] - Gs[..., None, :, :],
                          -jnp.inf))
    own = lambda a: jnp.sum(a[..., :, None, :] * ks[..., None, :, :] * E, -1)
    # a sub-block's pairs with the positions before it, through its first
    # position: both factors <= 1
    first = Gs[..., 0, :]                                   # (..., nb, K)
    to_first = jnp.exp(Gs - first[..., None, :])            # (..., nb, sub, K)
    before = (jnp.arange(C)[None, :] < (jnp.arange(nb) * sub)[:, None])
    from_first = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], first[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                          # (..., nb, C, K)
    eye = jnp.eye(nb, dtype=q.dtype)

    def whole(a):
        earlier = jnp.einsum("...brc,...bic->...bri", a * to_first,
                             from_first, precision=_HI)     # (..., nb, sub, C)
        diagonal = jnp.einsum("...bri,bd->...brdi", own(a), eye)
        return (earlier + diagonal.reshape(earlier.shape)).reshape(
            q.shape[:-2] + (C, C))

    return whole(ks), whole(qs)


def chunk_parts(q, k, v, g, beta):
    """The chunks' own work, every chunk alike: q, k, g (..., C, K), v (...,
    C, V), beta (..., C), float32 -> (Q exp G, P the masked q-k products, U~,
    W, K decayed to the chunk's end, exp G_C, G)."""
    G = jnp.cumsum(g, -2)
    KK, QK = pair_products(q, k, G)
    C = q.shape[-2]
    strictly = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strictly, beta[..., None] * KK, 0.0)
    with jax.named_scope(SCOPE_KDA_SOLVE):
        UW = _mm(unit_lower_inverse(A), beta[..., None] * jnp.concatenate(
            [v, k * jnp.exp(G)], -1))
    V = v.shape[-1]
    last = G[..., -1:, :]
    return (q * jnp.exp(G), QK, UW[..., :V], UW[..., V:],
            k * jnp.exp(last - G), jnp.exp(last[..., 0, :]), G)


def _chunk_step(S, parts):
    """One chunk, the state S (..., K, V) entering it -> (the state leaving
    it, (o (..., C, V), U, the state that entered))."""
    QG, QK, Ut, W, Kd, decay = parts
    U = Ut - _mm(W, S)
    o = _mm(QG, S) + _mm(QK, U)
    return decay[..., None] * S + _mm(_T(Kd), U), (o, U, S)


def _segment(S, xs, terms=False, scope=SCOPE_KDA_SCAN):
    """A segment of chunks: xs = (q, k, v, g, beta), each (n, ..., C, .) with
    the chunks first -> (S after it, o (n, ..., C, V)). ``scope``: the
    calling mixer's name for the scan."""
    # the scope again INSIDE the checkpointed body: the ops the segment's
    # backward pass makes again carry the names their first run carried
    with jax.named_scope(scope):
        q, k, v, g, beta = (x.astype(jnp.float32) for x in xs)
        *parts, G = chunk_parts(q, k, v, g, beta)
        S, (o, U, entering) = jax.lax.scan(_chunk_step, S, tuple(parts))
    return S, ((o, U, entering, G) if terms else o)


def _cut(x, T, chunk, n):
    """(B, T, H, .) -> (segments, n chunks, B, H, chunk, .), zeros after T."""
    B, _, H = x.shape[:3]
    seg = n * chunk
    x = jnp.pad(x, ((0, 0), (0, -T % seg)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((B, -1, n, chunk, H) + x.shape[3:])
    return jnp.moveaxis(x, (1, 2, 3), (0, 1, 4))


def chunk_log_decay_min(g, chunk):
    """The most negative log-decay cumulated inside a chunk, g (B, T, H, K) a
    channel's or (B, T, H) a head's: how far past float32's 1 / exp(G) (-88)
    the stable form is worked."""
    B, T = g.shape[:2]
    g = jnp.pad(g, ((0, 0), (0, -T % chunk)) + ((0, 0),) * (g.ndim - 2))
    return jnp.min(jnp.cumsum(g.reshape((B, -1, chunk) + g.shape[2:]), 2))


@functools.lru_cache(maxsize=None)
def _log_form(kernel, reason):
    """Once a distinct answer of the rule, at trace time."""
    _log.info("kda.scan runs in %s", f"the XLA form ({reason})" if reason else
              f"the Mosaic kernel {kernel}")


# by g's rank, a channel's decay or a head's: (the form's name for
# `tracing.note_form("kda.scan", ...)`, the kernels' module, their scan, the
# forward kernel's name in a trace)
_KERNELS = {4: ("kernel", kda_kernel, kda_kernel.kda, kda_kernel.KDA_FWD),
            3: ("head-kernel", gdn_kernel, gdn_kernel.gdn, gdn_kernel.GDN_FWD)}


def scan(q, k, v, g, beta, chunk, terms=False, mesh=None,
         scope=SCOPE_KDA_SCAN):
    """The gated delta rule over T positions in chunks of ``chunk``: q, k
    (B, T, H, K), v (B, T, H, V), g (B, T, H, K) float32 <= 0 a channel's
    log-decay, beta (B, T, H) float32 -> o (B, T, H, V) float32; or g (B, T,
    Hv) a HEAD's, with v and beta a value head and q, k (B, T, Hk, K) a KEY
    head, Hk dividing Hv, key head j serving value heads r j .. r j + r - 1
    (``terms``' G is then (B, T, Hv) too). ``scope``: the calling mixer's
    name for the scan. T need not be whole chunks: positions after T are k =
    v = 0, g = 0, beta = 0, which leave the state as it is.
    ``terms``: -> (o, {U (B, T, H, V), entering (B, c, H, K, V) the state
    entering each chunk, G (B, T, H, K)}), for checks. Where the kernels'
    rule admits the call (one program on a TPU, whole chunks of 64, heads of
    whole lane tiles) Mosaic kernels serve it, ``terms`` too:
    ``kernels/kda.py``'s a channel's decay, ``kernels/gdn.py``'s a head's;
    everywhere else the form below, a head's decay broadcast over the head's
    columns and the key heads repeated."""
    head = g.ndim == 3
    form, kernel, run, name = _KERNELS[g.ndim]
    reason = kernel.refusal(q, k, v, g, beta, chunk, mesh)
    tracing.note_form("kda.scan", "xla" if reason else form, reason)
    _log_form(name, reason)
    if reason is None:
        return (kernel.terms if terms else run)(q, k, v, g, beta, chunk)
    if head:
        if v.shape[2] % k.shape[2]:
            raise ValueError(f"{v.shape[2]} value heads are not whole groups "
                             f"on {k.shape[2]} key heads")
        if v.shape[2] != k.shape[2]:
            q, k = (jnp.repeat(x, v.shape[2] // k.shape[2], axis=2)
                    for x in (q, k))
        g = jnp.broadcast_to(g[..., None], k.shape)
    B, T, H, K = k.shape
    n = min(SEGMENT_CHUNKS, -(-T // chunk))
    xs = tuple(_cut(x, T, chunk, n) for x in (q, k, v, g, beta[..., None]))
    xs = xs[:4] + (xs[4][..., 0],)
    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    body = functools.partial(_segment, terms=terms, scope=scope)
    if not terms:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                REMAT_KDA_INV))
    _, out = jax.lax.scan(body, S0, xs)

    def positions(x):       # (segments, n, B, H, chunk, .) -> (B, T, H, .)
        x = jnp.moveaxis(x, (0, 1, 4), (1, 2, 3))
        return x.reshape((B, -1, H) + x.shape[5:])[:, :T]

    if not terms:
        return positions(out)
    o, U, entering, G = out
    entering = jnp.moveaxis(entering, 2, 0).reshape(
        (B, -1, H) + entering.shape[4:])
    G = positions(G)
    return positions(o), {"U": positions(U), "entering": entering,
                          "G": G[..., 0] if head else G}
