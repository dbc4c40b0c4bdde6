"""Learned sparse attention beside the flash kernels: the lightning indexer
of DeepSeek-V3.2-Exp (arXiv:2512.02556, section 2.1), which ranks the keys of
every query, the exact selection of the ``top_k`` best, and the loss that
trains the indexer alone.

For a query t and a key s <= t, ``J`` index heads of ``c`` columns, ONE index
key a token:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            float32
    S_t     = the top_k keys s <= t of largest I[t, s] (all of them while
              t < top_k; ties to the lower s)
    L_I     = mean_t KL(p_t || softmax_{s in S_t} I[t, s]),
              p[t, s] = (1 / H) sum_h a[t, h, s], detached,

a the main attention's probabilities over S_t. Nothing (T, T) is ever whole:
every function here works on a block of ``row_block(T)`` query rows against
all T keys, one after another (``lax.map`` / ``lax.scan``), so the float32
working set is a few (rows, T) arrays, gone when the block is done. Nothing
(heads, rows, T) exists on a TPU either: the two sums over heads (the index
scores' and the target's) and the index scores' backward pass are three
Mosaic kernels that keep the heads' tiles in VMEM (``dsa_index_scores``,
``dsa_head_probs``, ``dsa_index_scores_bwd``; elsewhere the jnp expressions
they are held to). What leaves ``select`` is the kept set as the flash
kernels take it, one bit a pair (``flash_attention.pack_row_mask``'s form):
64 MiB a sequence of 16,384 where a float32 (T, T) is 1 GiB.

The k-th largest of a row is found without a sort: a float's bit pattern,
turned so that unsigned order is the float's order, is narrowed two bits a
pass from the top, 16 counting passes over the block, exact; the ties at the
threshold go to the lower s by a running count (``select_rows``).

The loss's target needs a[t, h, s] summed over the heads, which no flash
kernel returns: it is rebuilt from q, k and the kernel's saved row statistic
``lse`` (exp(q . k * scale - lse)), a block of rows at a time, and its
gradient on I, (softmax(I) - p) / rows, goes back through the index scores to
qI, kI and w and on through the indexer's projections to its LEAVES, all in
the forward pass: ``indexer_loss`` is a ``custom_vjp`` around the indexer as a
whole (it reads the layer's input detached, so its gradient ends at its own
weights), whose forward rule returns the leaves' gradients as its residuals
under one name (``tracing.REMAT_DSA_GRADS``) and whose backward rule is ``g *``
each. A (T, T, H) array never exists, and a trunk under ``jax.checkpoint``
that keeps the name (a few MiB a layer) runs the chain once a step: its
recomputation holds nothing of the loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.tracing import (REMAT_DSA_GRADS, SCOPE_DSA_PROJ,
                                 SCOPE_DSA_SCORES)
from . import flash_attention as fa

# query rows a block: a (ROWS, T) float32 array is 32 MiB at T = 16,384
ROWS = 512
# keys a grid step of the three kernels
KEYS = 512
# the three Mosaic kernels' names in a device trace (`mosaic:<name>`)
DSA_INDEX, DSA_PROBS, DSA_INDEX_BWD = ("dsa_index_scores", "dsa_head_probs",
                                       "dsa_index_scores_bwd")
_VMEM_LIMIT = 64 << 20


def row_block(T):
    """Query rows a block of a sequence of T: the most of ``ROWS`` by halving
    that divides T."""
    rows = ROWS
    while T % rows:
        rows //= 2
    return rows


def _on_tpu():
    return jax.default_backend() == "tpu"


def _kernels_take(R, T, *widths):
    """Whether the Mosaic kernels serve a block of R rows against T keys: a
    TPU, whole key blocks of ``KEYS``, rows the sublanes tile, head widths
    the lanes do. Elsewhere the jnp expressions they are held to run."""
    return (_on_tpu() and T % KEYS == 0 and R % 8 == 0
            and all(w % 64 == 0 for w in widths))


def _index_scores_jnp(q_rows, w_rows, k):
    R, J = w_rows.shape
    s = jnp.einsum("rjc,sc->jrs", q_rows.reshape(R, J, -1), k,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_rows.T[:, :, None], axis=0)


def _seen(first_row, rows):
    """Whether the grid step's key block holds a key that the block's last
    query sees (the others are skipped: zeros out)."""
    return pl.program_id(0) * KEYS <= first_row[0] + rows - 1


def _index_kernel(first_row, q_ref, w_ref, k_ref, o_ref, *, heads):
    # grid: (key blocks,); one block of query rows against one of keys, the
    # sum over the index heads in VMEM
    R, c = q_ref.shape[0], k_ref.shape[1]

    @pl.when(_seen(first_row, R))
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            s = fa._dot_nt(q_ref[:, j * c:(j + 1) * c], k)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = acc

    @pl.when(jnp.logical_not(_seen(first_row, R)))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _rows_call(kernel, name, first_row, rows, keys, out_shapes, out_specs,
               interpret):
    """One of the three kernels on a block of rows: `rows` whole a grid step,
    `keys` a block of ``KEYS`` a step, `first_row` in SMEM before the body."""
    whole = lambda a: pl.BlockSpec(a.shape, lambda j, first: (0, 0))
    block = lambda a: pl.BlockSpec((KEYS, a.shape[1]),
                                   lambda j, first: (j, 0))
    T = keys[0].shape[0]
    return pl.pallas_call(
        kernel, name=name, interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // KEYS,),
            in_specs=[whole(a) for a in rows] + [block(a) for a in keys],
            out_specs=out_specs),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.reshape(first_row, (1,)).astype(jnp.int32), *rows, *keys)


def _index_scores_pallas(q_rows, w_rows, k, first_row, interpret=False):
    R, J = w_rows.shape
    T = k.shape[0]
    return _rows_call(
        functools.partial(_index_kernel, heads=J), DSA_INDEX, first_row,
        (q_rows, w_rows), (k,), jax.ShapeDtypeStruct((R, T), jnp.float32),
        pl.BlockSpec((R, KEYS), lambda j, first: (0, j)), interpret)


def _index_bwd_kernel(first_row, q_ref, w_ref, k_ref, d_ref, dq_ref, dw_ref,
                      dk_ref, *, heads):
    # grid: (key blocks,), in order: dq and dw are the running sums over the
    # key blocks (their block never moves), dk is a key block's own
    R, c = q_ref.shape[0], k_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(_seen(first_row, R))
    def _():
        k, w, d = k_ref[...], w_ref[...], d_ref[...]
        head = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dk = jnp.zeros(dk_ref.shape, jnp.float32)
        dw = jnp.zeros(w.shape, jnp.float32)
        for j in range(heads):
            lanes = slice(j * c, (j + 1) * c)
            q = q_ref[:, lanes]
            s = fa._dot_nt(q, k)                            # (R, keys)
            on = s > 0.0
            dw = dw + jnp.where(head == j, jnp.sum(
                jnp.where(on, s, 0.0) * d, axis=1, keepdims=True), 0.0)
            ds = jnp.where(on, d * w[:, j:j + 1], 0.0).astype(k.dtype)
            dq_ref[:, lanes] += fa._dot(ds, k)
            dk = dk + fa._dot_tn(ds, q)
        dw_ref[...] += dw
        dk_ref[...] = dk

    @pl.when(jnp.logical_not(_seen(first_row, R)))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)


def _index_bwd_pallas(q_rows, w_rows, k, first_row, d_scores,
                      interpret=False):
    """The cotangents of ``index_scores_rows`` at ``d_scores`` (R, T), which
    is zero off the causal triangle: (dq (R, J * c), dw (R, J), dk (T, c)),
    float32. The scores are rebuilt a tile at a time; nothing (J, R, T) is
    kept from the forward pass."""
    R, J = w_rows.shape
    f32 = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    whole = lambda a: pl.BlockSpec(a.shape, lambda j, first: (0, 0))
    d_spec = pl.BlockSpec((R, KEYS), lambda j, first: (0, j))
    T = k.shape[0]
    return pl.pallas_call(
        functools.partial(_index_bwd_kernel, heads=J), name=DSA_INDEX_BWD,
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // KEYS,),
            in_specs=[whole(q_rows), whole(w_rows),
                      pl.BlockSpec((KEYS, k.shape[1]),
                                   lambda j, first: (j, 0)), d_spec],
            out_specs=[whole(q_rows), whole(w_rows),
                       pl.BlockSpec((KEYS, k.shape[1]),
                                    lambda j, first: (j, 0))]),
        out_shape=[f32(q_rows), f32(w_rows), f32(k)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(jnp.reshape(first_row, (1,)).astype(jnp.int32), q_rows, w_rows, k,
      d_scores)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _index_scores_kernels(q_rows, w_rows, k, first_row, interpret):
    return _index_scores_pallas(q_rows, w_rows, k, first_row, interpret)


def _index_scores_kernels_fwd(q_rows, w_rows, k, first_row, interpret):
    return (_index_scores_pallas(q_rows, w_rows, k, first_row, interpret),
            (q_rows, w_rows, k, first_row))


def _index_scores_kernels_bwd(interpret, res, g):
    q_rows, w_rows, k, first_row = res
    dq, dw, dk = _index_bwd_pallas(q_rows, w_rows, k, first_row, g,
                                   interpret)
    return dq.astype(q_rows.dtype), dw, dk.astype(k.dtype), None


_index_scores_kernels.defvjp(_index_scores_kernels_fwd,
                             _index_scores_kernels_bwd)


def index_scores_rows(q_rows, w_rows, k, first_row=0):
    """``q_rows`` (R, J * c) the index queries of the block of rows
    ``first_row`` ..., ``w_rows`` (R, J) float32 their heads' weights, ``k``
    (T, c) the index keys -> I (R, T) float32, anything (the kernel: zeros)
    for the keys no row of the block sees. The products take the operands as
    they come (bfloat16 in a bfloat16 model) and accumulate in float32;
    ReLU, the weights and the sum over heads are float32. On a TPU one Mosaic
    kernel (``dsa_index_scores``: the sum over heads stays in VMEM; its
    cotangents are ``dsa_index_scores_bwd``'s, which takes a cotangent that
    is zero off the causal triangle), else the jnp expression."""
    if _kernels_take(q_rows.shape[0], k.shape[0], k.shape[1]):
        return _index_scores_kernels(q_rows, w_rows, k, first_row, False)
    return _index_scores_jnp(q_rows, w_rows, k)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order (-0.0 and
    0.0 as one value: a sort calls them equal)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))    # signed order
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_rows(scores, first_row, top_k):
    """``scores`` (R, T) float32 of the query rows ``first_row`` ... ->
    keep (R, T) bool: of the keys s <= t the ``top_k`` of largest score, ties
    to the lower s; every s <= t while t < ``top_k``. Exact."""
    R, T = scores.shape
    t = first_row + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    causal = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1) <= t
    # a key the query does not see ranks below every score
    u = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))
    k = jnp.minimum(top_k, t + 1)                         # (R, 1)
    count = lambda hit: jnp.sum(hit, axis=1, keepdims=True, dtype=jnp.int32)

    def narrow(i, theta):
        # the largest theta with `k` or more keys at or above it, two bits a
        # pass from the top: the row's k-th largest value when all are set
        shift = (30 - 2 * i).astype(jnp.uint32)
        best = theta
        for step in (1, 2, 3):
            tried = theta | (jnp.uint32(step) << shift)
            best = jnp.where(count(u >= tried) >= k, tried, best)
        return best

    theta = jax.lax.fori_loop(0, 16, narrow, jnp.zeros((R, 1), jnp.uint32))
    above, at = u > theta, u == theta
    # of the keys AT the threshold, as many as are still owed, the lowest s
    owed = k - count(above)
    return causal & (above | (at & (jnp.cumsum(at, axis=1, dtype=jnp.int32)
                                    <= owed)))


def by_key_of(by_query):
    """The second array of a ``pack_row_mask`` pair from the first, (B, T, W)
    int32 each: ``by_query[b, p W + c, c2]`` bit p2 and ``by_key[b, p2 W +
    c2, c]`` bit p are the one pair (query p W + c, key p2 W + c2). A plane
    of keys at a time: its bit of every word, shifted to the query's plane."""
    B, T, W = by_query.shape
    P = T // W
    words = by_query.reshape(B, P, W, W)
    plane = jnp.arange(P, dtype=jnp.int32)[:, None, None]

    def of_key_plane(p2):
        bits = ((words >> p2) & 1) << plane               # [b, p, c, c2]
        return jnp.sum(bits, axis=1).swapaxes(1, 2)       # [b, c2, c]

    out = jax.lax.map(of_key_plane, jnp.arange(P, dtype=jnp.int32))
    return out.swapaxes(0, 1).reshape(B, T, W)


def select(qI, kI, w, top_k):
    """The kept set of every query -> ((by_query, by_key) the
    ``pack_row_mask`` pair the flash kernels take, kept (B,) int32 the pairs
    kept a sequence). ``qI`` (B, T, J * c), ``kI`` (B, T, c), ``w`` (B, T, J)
    float32. No gradient: the selection is a constant of the step."""
    B, T, _ = qI.shape
    R, planes = row_block(T), fa.mask_planes(T)
    n = T // R
    blocks = lambda x: x.reshape((B * n, R) + x.shape[2:])

    def block(x):
        i, q_rows, w_rows = x
        k = jax.lax.dynamic_index_in_dim(kI, i // n, 0, keepdims=False)
        with jax.named_scope(SCOPE_DSA_SCORES):
            scores = index_scores_rows(q_rows, w_rows, k, (i % n) * R)
        return fa.pack_bits(select_rows(scores, (i % n) * R, top_k), planes)

    by_query = jax.lax.map(
        block, (jnp.arange(B * n, dtype=jnp.int32), blocks(qI), blocks(w))
    ).reshape(B, T, T // planes)
    kept = jnp.sum(jax.lax.population_count(by_query), axis=(1, 2))
    return (by_query, by_key_of(by_query)), kept


def _head_probs_jnp(q_rows, k, lse_rows, scale):
    (R, H), T = lse_rows.shape, k.shape[0]
    d = q_rows.shape[1] // H
    G = k.shape[1] // d
    s = jnp.einsum("rgqd,sgd->gqrs", q_rows.reshape(R, G, H // G, d),
                   k.reshape(T, G, d),
                   preferred_element_type=jnp.float32) * scale
    return jnp.sum(jnp.exp(s.reshape(H, R, T) - lse_rows.T[:, :, None]),
                   axis=0)


def _probs_kernel(first_row, q_ref, lse_ref, k_ref, o_ref, *, heads, scale):
    # grid: (key blocks,); the sum over the heads stays in VMEM. Query head
    # h reads k/v head h // (heads / kv heads)
    R = q_ref.shape[0]
    d = q_ref.shape[1] // heads
    group = heads // (k_ref.shape[1] // d)

    @pl.when(_seen(first_row, R))
    def _():
        lse = lse_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            g = h // group
            s = fa._dot_nt(q_ref[:, h * d:(h + 1) * d],
                           k_ref[:, g * d:(g + 1) * d]) * scale
            acc = acc + jnp.exp(s - lse[:, h:h + 1])
        o_ref[...] = acc

    @pl.when(jnp.logical_not(_seen(first_row, R)))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _head_probs_pallas(q_rows, k, lse_rows, scale, first_row,
                       interpret=False):
    R, H = lse_rows.shape
    return _rows_call(
        functools.partial(_probs_kernel, heads=H, scale=scale), DSA_PROBS,
        first_row, (q_rows, lse_rows), (k,),
        jax.ShapeDtypeStruct((R, k.shape[0]), jnp.float32),
        pl.BlockSpec((R, KEYS), lambda j, first: (0, j)), interpret)


def head_probs_rows(q_rows, k, lse_rows, scale, first_row=0):
    """sum_h exp(q_h . k_g * scale - lse_h) for the block of rows
    ``first_row`` ... against every key, (R, T) float32: ``q_rows`` (R, H *
    d), ``k`` (T, G * d) at the k/v heads (query head h reads k/v head h //
    (H / G)), ``lse_rows`` (R, H) float32 the rows' softmax statistic a head
    (over the keys KEPT: the caller masks the others, which may read
    anything here, inf too). On a TPU one Mosaic kernel (``dsa_head_probs``),
    else the jnp expression."""
    d = q_rows.shape[1] // lse_rows.shape[1]
    if _kernels_take(q_rows.shape[0], k.shape[0], 2 * d):
        return _head_probs_pallas(q_rows, k, lse_rows, scale, first_row)
    return _head_probs_jnp(q_rows, k, lse_rows, scale)


def _rows_loss(scores, keep, probs):
    """One block's rows -> (sum over the rows of KL(p || softmax over the
    kept of I), its gradient on I (R, T)), float32: ``probs`` the head-MEAN
    of the attention's probabilities, anything off the kept set."""
    p = jnp.where(keep, probs, 0.0)
    m = jnp.max(jnp.where(keep, scores, -jnp.inf), axis=1, keepdims=True)
    e = jnp.where(keep, jnp.exp(scores - m), 0.0)
    z = jnp.sum(e, axis=1, keepdims=True)
    log_q = scores - m - jnp.log(z)
    seen = p > 0.0                                   # 0 log 0 = 0
    kl = jnp.sum(jnp.where(seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                                      - log_q), 0.0))
    return kl, jnp.where(keep, e / z - p, 0.0)


def _loss_and_grads(qI, kI, w, q, k, lse, by_query, n_heads, scale, grads):
    """-> (L_I, (dqI, dkI, dw) or None). A sequence at a time, a block of
    rows at a time; the index keys' gradient is the running sum over a
    sequence's blocks."""
    B, T, _ = qI.shape
    R, planes = row_block(T), T // by_query.shape[-1]
    n = T // R
    blocks = lambda x: x.reshape((n, R) + x.shape[1:])

    def sequence(x):
        qI, kI, w, q, k, lse, by_query = x

        def block(carry, rows):
            loss, dk = carry
            first, qI_rows, w_rows, q_rows, lse_rows, packed = rows
            index = functools.partial(index_scores_rows, first_row=first)
            with jax.named_scope(SCOPE_DSA_SCORES):
                if grads:
                    scores, back = jax.vjp(index, qI_rows, w_rows, kI)
                else:
                    scores = index(qI_rows, w_rows, kI)
            kl, d_scores = _rows_loss(
                scores, fa.unpack_bits(packed, planes),
                head_probs_rows(q_rows, k, lse_rows, scale, first)
                / n_heads)
            if not grads:
                return (loss + kl, dk), None
            with jax.named_scope(SCOPE_DSA_SCORES):
                dq_rows, dw_rows, dk_rows = back(d_scores)
            return (loss + kl, dk + dk_rows.astype(jnp.float32)), (
                dq_rows, dw_rows)

        zero = (jnp.zeros((), jnp.float32),
                jnp.zeros(kI.shape, jnp.float32) if grads else None)
        (loss, dk), d = jax.lax.scan(
            block, zero, (jnp.arange(n, dtype=jnp.int32) * R,) + tuple(
                blocks(a) for a in (qI, w, q, lse, by_query)))
        if not grads:
            return loss
        return loss, d[0].reshape(qI.shape), dk, d[1].reshape(w.shape)

    # lse as the flash kernels write it, (B * H, 1, T) -> (B, T, H)
    lse = lse.reshape(B, n_heads, T).swapaxes(1, 2)
    out = jax.lax.map(sequence, (qI, kI, w, q, k, lse, by_query))
    if not grads:
        return jnp.sum(out) / (B * T), None
    loss, dq, dk, dw = out
    rows = 1.0 / (B * T)
    return jnp.sum(loss) * rows, ((dq * rows).astype(qI.dtype),
                                  (dk * rows).astype(kI.dtype), dw * rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 7, 8))
def indexer_loss(index, leaves, x, q, k, lse, by_query, n_heads, scale):
    """L_I, a float32 scalar: the mean over all B * T queries of KL(p ||
    softmax of the index scores over the kept keys), p the mean over the
    ``n_heads`` heads of softmax(q . k * ``scale``) over the same keys,
    rebuilt from ``q`` (B, T, H * d), ``k`` (B, T, G * d) at the k/v heads
    and the attention's row statistic ``lse`` (B * H, 1, T). The index scores
    are those of ``index(x, leaves)`` -> (qI, kI, w) as ``select`` takes them:
    the indexer's projections of the layer's input ``x``. Its gradient
    reaches ``leaves`` (any tree) alone: x is read DETACHED, q, k and lse are
    the TARGET, detached, and ``by_query`` (the first array of ``select``'s
    pair) a constant. The gradient is made where the loss is, in the forward
    rule, and kept under ``tracing.REMAT_DSA_GRADS``."""
    with jax.named_scope(SCOPE_DSA_PROJ):
        qI, kI, w = index(x, leaves)
    return _loss_and_grads(qI, kI, w, q, k, lse, by_query, n_heads, scale,
                           grads=False)[0]


def _indexer_loss_fwd(index, leaves, x, q, k, lse, by_query, n_heads, scale):
    # the projections' scope around both halves of their vjp: the
    # pull-back's ops are the projections' too, wherever they run
    with jax.named_scope(SCOPE_DSA_PROJ):
        (qI, kI, w), to_leaves = jax.vjp(functools.partial(index, x), leaves)
    loss, cotangents = _loss_and_grads(qI, kI, w, q, k, lse, by_query,
                                       n_heads, scale, grads=True)
    with jax.named_scope(SCOPE_DSA_PROJ):
        grads, = to_leaves(cotangents)
    return loss, jax.tree.map(
        lambda g: checkpoint_name(g, REMAT_DSA_GRADS), grads)


def _indexer_loss_bwd(index, n_heads, scale, grads, g):
    return (jax.tree.map(lambda d: (g * d).astype(d.dtype), grads),
            None, None, None, None, None)


indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)
