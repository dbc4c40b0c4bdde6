"""Fused causal attention for TPU.

The reference's attention is unfused BatchMatMul + Softmax + BatchMatMul
(examples/nlp/hetu_transformer.py:56+), materializing the (S, S) score matrix
in HBM. This module computes attention blockwise with an online softmax so
only (block_q, block_k) tiles ever exist:

- forward: a Pallas kernel — q/k/v tiles stream HBM->VMEM, scores hit the
  MXU, the running (max, sum) rescale keeps the softmax exact. Falls back to
  interpreter mode off-TPU so the same code runs in CPU-mesh tests.
- backward: Pallas kernels both directions on TPU (a dq kernel over q blocks
  and a fused dk+dv kernel over k blocks, each recomputing its probability
  tile from (q, k, lse) — no (S,S) materialization); off-TPU, a blockwise
  `lax.scan` recomputation in XLA serves as fallback and numerical oracle.

Public entry: ``flash_attention(q, k, v, causal=True)`` with shapes
(batch, heads, seq, head_dim), differentiable via custom_vjp. An optional
``k_bias`` (batch, seq) float is ADDED to every score column — the key-
padding mask form (0 valid / -1e9 padded) the BERT encoder uses — so masked
batches keep the fused kernel instead of falling back to the unfused path.
All-padded rows degenerate to a uniform softmax, exactly like the unfused
form (softmax is shift-invariant), so the semantics match the dot path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernels' names in the device trace (docs/KERNELS.md): one constant a
# pallas_call site, written as the call's `name=`
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"

_NEG_INF = -1e30
_LANES = 128

# What one grid step may hold in VMEM, by `_vmem_bytes`' count: three
# quarters of the 16 MiB Mosaic gives a kernel by default on a v5e. The
# count follows the compiler's own: choices it puts at 14.8 and 15.5 MiB
# compile for the v5e, at 17.8 and more they are refused (PR 24).
_VMEM_BUDGET = 12 * 1024 * 1024
# FLOPs of the forward a grid step should carry at least: 1.3 us of the
# MXU at the v5e's 197 TFLOP/s (twice that at head size 64, which fills
# half of the array), against the ~0.35 us a grid step costs whatever it
# does. On the chip (PR 24) 2, 4 and 6 heads of 512 x 512 x 64 a step ran
# the three kernels in 7.09, 6.92 and 6.74 ms a layer: flat beyond this.
_STEP_FLOPS = 256e6
_MAX_HEADS = 16     # the head loop of a grid step is unrolled


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _vmem_bytes(s, d, itemsize, block_q, block_k, heads):
    """VMEM one grid step of the hungriest of the three kernels holds:
    two operands whole (k, v; or q, dO in `flash_bwd_dkv`) and at most
    four blocks (k, v, dk, dv there), the lse and delta rows padded to
    eight sublanes, all double-buffered, and five f32 arrays of the score
    tile's size (s, p, dp, ds and one in flight) for the one head being
    worked on."""
    blocks = 2 * heads * d * itemsize * (2 * s + 4 * max(block_q, block_k))
    rows = 2 * 2 * heads * 8 * s * 4
    return blocks + rows + 5 * block_q * block_k * 4


def _choose_tiles(s, d, dtype, causal, heads, block_q=None, block_k=None):
    """-> (block_q, block_k, heads a grid step), from the call's shapes alone.

    `heads` is how many consecutive rows of the flattened (batch*heads)
    axis may share a grid step: the head count where a key bias ties a step
    to one batch row, batch*heads where there is none. A `block_q`/`block_k`
    the caller passed is kept.

    Blocks: the largest of 512/256/128 that divides `s` (the whole sequence
    below 128), for causal calls too: on the chip a 512 x 512 tile that
    computes its masked half beat 256 x 256 tiles that skip more (PR 24).
    Heads: the fewest that give a step `_STEP_FLOPS` of work, among the
    divisors of `heads`. Both give way, heads first, until `_vmem_bytes`
    fits `_VMEM_BUDGET`; the floor is one head and the smallest blocks,
    which is what every call had before PR 24."""
    itemsize = jnp.dtype(dtype).itemsize

    def sizes(given):
        if given is not None:
            return [min(given, s)]
        return [b for b in (512, 256, 128) if s % b == 0] or [s]

    picks = [(bq, bk) for bq in sizes(block_q) for bk in sizes(block_k)]
    picks.sort(key=lambda p: -p[0] * p[1])
    groups = [g for g in range(1, min(heads, _MAX_HEADS) + 1)
              if heads % g == 0]
    for bq, bk in picks:
        step = 4.0 * bq * s * d * (0.5 if causal else 1.0)
        want = next((g for g in groups if g * step >= _STEP_FLOPS),
                    groups[-1])
        for g in reversed([g for g in groups if g <= want]):
            if _vmem_bytes(s, d, itemsize, bq, bk, g) <= _VMEM_BUDGET:
                return bq, bk, g
    bq, bk = picks[-1]
    return bq, bk, 1


def _tiles_for(q, k_bias, causal, block_q, block_k):
    """`_choose_tiles` for a (batch, heads, seq, head_dim) call; blocks that
    do not divide the sequence (only ones the caller passed can) raise."""
    b, h, s, d = q.shape
    block_q, block_k, heads = _choose_tiles(
        s, d, q.dtype, causal, b * h if k_bias is None else h, block_q,
        block_k)
    if s % block_q or s % block_k:
        raise ValueError(f"seq_len {s} must divide blocks ({block_q},{block_k})")
    return block_q, block_k, heads


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T on the MXU: operands as they come, f32 out."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _col_to_row(col):
    """(n, 1) -> (1, n): per-row statistics onto the lane axis, through a
    128-wide transpose (the shape Mosaic's transpose takes)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _row_to_col(row):
    """(1, n) -> (n, 1): the inverse, once a grid step and head."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _block(i, size):
    """Slice of block `i` along an axis cut into blocks of `size`."""
    if isinstance(i, int):
        return pl.ds(i * size, size)
    return pl.ds(pl.multiple_of(i * size, size), size)


def _loop(lower, upper, body, init):
    """`fori_loop`; a single pass is written out instead, with static
    slices. (Writing more passes out keeps every pass's tiles alive: 32 of
    them ran Mosaic out of VMEM where the loop compiled.)"""
    if isinstance(lower, int) and isinstance(upper, int) \
            and upper == lower + 1:
        return body(lower, init)
    return jax.lax.fori_loop(lower, upper, body, init)


def _causal_mask(s, q_start, k_start, keys_first=False):
    """Mask scores above the diagonal for one tile: (q rows, k columns), or
    (k rows, q columns) with `keys_first`."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if keys_first:
        keep = q_start + cols >= k_start + rows
    else:
        keep = q_start + rows >= k_start + cols
    return jnp.where(keep, s, _NEG_INF)


def _causal_upper_kb(q_start, block_q, block_k):
    """First key block strictly above the diagonal, by CEIL division —
    flooring would drop the diagonal block whenever block_q < block_k
    (regression guard: test_flash_causal_uneven_blocks). Shared by the
    forward and dq kernels so the bound cannot drift between them."""
    return (q_start + block_q + block_k - 1) // block_k


def _optional_bias(kernel, n_before, use_bias):
    """pallas hands a kernel its refs in order, inputs then outputs; the
    bias is the input after the first `n_before`, or absent."""
    if use_bias:
        return kernel
    return lambda *refs: kernel(*refs[:n_before], None, *refs[n_before:])


# ---------------------------------------------------------------------------
# The tile program, common to the three kernels. A grid step owns `heads`
# consecutive rows of the flattened (batch*heads) axis and one block of the
# sequence, and loops over the blocks of the other side:
# - MXU operands go in as the caller gave them (bf16 from a bf16 model, f32
#   from an f32 caller), every dot accumulates in f32, and everything else
#   is f32: scores, bias add, m, l, exp, lse, delta, the accumulators.
# - per-row statistics (lse, delta) and the key bias live in HBM with the
#   sequence on the lane axis, (batch*heads, 1, s) and (batch, 1, s). Where
#   a kernel needs one as a column it transposes it once a step, never in
#   the loop; `flash_bwd_dkv` works on the transposed score tile (keys down
#   the sublanes) so that lse and delta broadcast as rows and P^T, dS^T
#   feed dV and dK without a transposed matmul.
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *, scale,
                causal, block_k):
    # grid: (batch*heads / heads, q blocks); one q block, the whole k and v
    heads, block_q, d = q_ref.shape
    q_start = pl.program_id(1) * block_q
    # causal: skip key blocks entirely above the diagonal
    upper = (_causal_upper_kb(q_start, block_q, block_k) if causal
             else k_ref.shape[1] // block_k)

    for g in range(heads):
        q = q_ref[g]                                  # (block_q, d)

        def body(kj, carry):
            acc, m_prev, l_prev = carry
            keys = _block(kj, block_k)
            v_blk = v_ref[g, keys]
            s = _dot_nt(q, k_ref[g, keys]) * scale    # (block_q, block_k)
            if bias_ref is not None:
                s = s + bias_ref[0, :, keys]
            if causal:
                s = _causal_mask(s, q_start, kj * block_k)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk)
            return acc, m_new, l_new

        acc, m, l = _loop(0, upper, body, (
            jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        o_ref[g] = (acc / l).astype(o_ref.dtype)
        lse_ref[g] = _col_to_row(m + jnp.log(l))


def _specs(heads, s, d, block, n_heads):
    """BlockSpecs over the flattened arrays for a grid (groups, blocks):
    a (block, d) tile and a whole (s, d) operand; a row of statistics for
    the block and the whole row; the same two of the key bias, which has a
    row a batch row, so a group reads the row of the batch it lies in."""
    def batch_row(i):
        return i * heads // n_heads

    return (pl.BlockSpec((heads, block, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((heads, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((heads, 1, block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((heads, 1, s), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda i, j: (batch_row(i), 0, j)),
            pl.BlockSpec((1, 1, s), lambda i, j: (batch_row(i), 0, 0)))


def _bias_rows(k_bias):
    """(b, s) key bias -> (b, 1, s) f32 rows: one a batch row, whatever the
    head (the index map sends a group to its batch row)."""
    return k_bias.astype(jnp.float32)[:, None, :]


def _fwd_pallas(q, k, v, k_bias, scale, causal, block_q, block_k, interpret):
    b, h, s, d = q.shape
    bh = b * h
    use_bias = k_bias is not None
    block_q, block_k, heads = _tiles_for(q, k_bias, causal, block_q, block_k)
    tile, whole, row, _, _, whole_bias = _specs(heads, s, d, block_q, h)
    in_specs = [tile, whole, whole]
    ops = [x.reshape(bh, s, d) for x in (q, k, v)]
    if use_bias:
        in_specs.append(whole_bias)
        ops.append(_bias_rows(k_bias))
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_k=block_k)
    out, lse = pl.pallas_call(
        _optional_bias(kern, 3, use_bias),
        grid=(bh // heads, s // block_q),
        in_specs=in_specs,
        out_specs=[tile, row],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(*ops)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# ---------------------------------------------------------------------------
# backward Pallas kernels (dq; dk+dv) — flash backward both directions:
# each tile recomputes its probability block from (q, k, lse), so nothing
# (S, S)-shaped ever exists. delta = rowsum(dO * O) is precomputed in XLA.
# `scale` multiplies the f32 scores on the way in and the f32 accumulators
# of dq and dk on the way out (d x block elements, not block x block).
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   bias_ref, dq_ref, *, scale, causal, block_k):
    # grid: (groups, q blocks); owns one q block, loops over k blocks
    heads, block_q, d = q_ref.shape
    q_start = pl.program_id(1) * block_q
    upper = (_causal_upper_kb(q_start, block_q, block_k) if causal
             else k_ref.shape[1] // block_k)

    for g in range(heads):
        q = q_ref[g]                                  # (block_q, d)
        do = do_ref[g]
        lse = _row_to_col(lse_ref[g])                 # (block_q, 1)
        delta = _row_to_col(delta_ref[g])

        def body(kj, dq):
            keys = _block(kj, block_k)
            k_blk = k_ref[g, keys]
            s = _dot_nt(q, k_blk) * scale
            if bias_ref is not None:
                s = s + bias_ref[0, :, keys]
            if causal:
                s = _causal_mask(s, q_start, kj * block_k)
            p = jnp.exp(s - lse)
            dp = _dot_nt(do, v_ref[g, keys])
            ds = p * (dp - delta)
            return dq + _dot(ds.astype(q.dtype), k_blk)

        dq = _loop(0, upper, body, jnp.zeros((block_q, d), jnp.float32))
        dq_ref[g] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, dk_ref, dv_ref, *, scale, causal, block_q):
    # grid: (groups, k blocks); owns one k/v block, loops over q blocks.
    # Tiles are (block_k, block_q): S^T, P^T, dP^T, dS^T.
    heads, block_k, d = k_ref.shape
    k_start = pl.program_id(1) * block_k
    # causal: q blocks strictly before this k block contribute nothing
    lower = (k_start // block_q) if causal else 0
    upper = q_ref.shape[1] // block_q
    # this kernel owns ONE k block: its bias column is constant
    bias = None if bias_ref is None else _row_to_col(bias_ref[0])

    for g in range(heads):
        k_blk = k_ref[g]                              # (block_k, d)
        v_blk = v_ref[g]

        def body(qi, carry):
            dk, dv = carry
            rows = _block(qi, block_q)
            q = q_ref[g, rows]
            do = do_ref[g, rows]
            st = _dot_nt(k_blk, q) * scale
            if bias is not None:
                st = st + bias
            if causal:
                st = _causal_mask(st, qi * block_q, k_start, keys_first=True)
            pt = jnp.exp(st - lse_ref[g, :, rows])    # (block_k, block_q)
            dv = dv + _dot(pt.astype(do.dtype), do)
            dpt = _dot_nt(v_blk, do)
            dst = pt * (dpt - delta_ref[g, :, rows])
            dk = dk + _dot(dst.astype(q.dtype), q)
            return dk, dv

        zeros = jnp.zeros((block_k, d), jnp.float32)
        dk, dv = _loop(lower, upper, body, (zeros, zeros))
        dk_ref[g] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)


def _bwd_pallas(res, do, *, scale, causal, block_q, block_k, interpret):
    q, k, v, o, lse, k_bias = res
    b, h, s, d = q.shape
    bh = b * h
    use_bias = k_bias is not None
    block_q, block_k, heads = _tiles_for(q, k_bias, causal, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                           # (b, h, s)
    ops = [x.reshape(bh, s, d) for x in (q, k, v, do)]
    ops += [lse.reshape(bh, 1, s), delta.reshape(bh, 1, s)]
    if use_bias:
        ops.append(_bias_rows(k_bias))
    grid = bh // heads

    tile, whole, row, _, _, whole_bias = _specs(heads, s, d, block_q, h)
    dq_specs = [tile, whole, whole, tile, row, row]
    if use_bias:
        dq_specs.append(whole_bias)
    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_k=block_k)
    dq = pl.pallas_call(
        _optional_bias(dq_kern, 6, use_bias),
        grid=(grid, s // block_q),
        in_specs=dq_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        name=FLASH_BWD_DQ,
    )(*ops)

    tile, whole, _, whole_row, bias_row, _ = _specs(heads, s, d, block_k, h)
    dkv_specs = [whole, tile, tile, whole, whole_row, whole_row]
    if use_bias:
        dkv_specs.append(bias_row)
    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale,
                                 causal=causal, block_q=block_q)
    dk, dv = pl.pallas_call(
        _optional_bias(dkv_kern, 6, use_bias),
        grid=(grid, s // block_k),
        in_specs=dkv_specs,
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV,
    )(*ops)

    return (dq.reshape(b, h, s, d), dk.reshape(b, h, s, d),
            dv.reshape(b, h, s, d))


# ---------------------------------------------------------------------------
# blockwise backward (XLA): flash-style recomputation, no (S, S) tensor
# (off-TPU fallback and the Pallas backward's numerical oracle)
# ---------------------------------------------------------------------------

def _bwd_blockwise(res, do, *, scale, causal, block_k):
    q, k, v, o, lse, k_bias = res
    b, h, s, d = q.shape
    nkb = s // block_k
    do_f = do.astype(jnp.float32)
    q_f = q.astype(jnp.float32)
    # delta_i = sum_j dO_ij O_ij  (rowwise), standard flash backward
    delta = jnp.sum(do_f * o.astype(jnp.float32), axis=-1)  # (b,h,s)

    q_pos = jnp.arange(s)

    def one_kblock(kj):
        ks = kj * block_k
        k_blk = jax.lax.dynamic_slice_in_dim(k, ks, block_k, 2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, ks, block_k, 2)
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", q_f,
                           k_blk.astype(jnp.float32)) * scale
        if k_bias is not None:
            kb = jax.lax.dynamic_slice_in_dim(
                k_bias.astype(jnp.float32), ks, block_k, 1)
            s_blk = s_blk + kb[:, None, None, :]
        if causal:
            mask = q_pos[:, None] >= (ks + jnp.arange(block_k))[None, :]
            s_blk = jnp.where(mask, s_blk, _NEG_INF)
        p = jnp.exp(s_blk - lse[..., None])                    # (b,h,s,bk)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, do_f)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_f, v_blk.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_part = jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk.astype(jnp.float32))
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, q_f)
        return dq_part, dk_blk, dv_blk

    def scan_body(dq_acc, kj):
        dq_part, dk_blk, dv_blk = one_kblock(kj)
        return dq_acc + dq_part, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        scan_body, jnp.zeros(q.shape, jnp.float32), jnp.arange(nkb))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, s, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, s, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, k_bias, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, k_bias, causal, scale, block_q, block_k)
    return out


def flash_attention(q, k, v, causal=True, scale=None, block_q=None,
                    block_k=None, k_bias=None):
    """Fused attention. q/k/v: (batch, heads, seq, head_dim).

    The matmuls run in the dtype of q/k/v with f32 accumulation; the softmax
    is f32 whatever that dtype. ``block_q``/``block_k`` left at None are
    chosen from the shapes (`_choose_tiles`).

    ``k_bias``: optional (batch, seq) float added to every score column —
    the key-padding mask form (0 valid / -1e9 padded). Non-trainable: its
    cotangent is zero."""
    return _flash(q, k, v, k_bias, causal, scale, block_q, block_k)


def _scale(q, scale):
    return scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])


def _flash_fwd(q, k, v, k_bias, causal, scale, block_q, block_k):
    out, lse = _fwd_pallas(q, k, v, k_bias, _scale(q, scale), causal,
                           block_q, block_k, interpret=not _on_tpu())
    return out, (q, k, v, out, lse, k_bias)


def _flash_bwd(causal, scale, block_q, block_k, res, do):
    q, k_bias = res[0], res[5]
    if _on_tpu():
        grads = _bwd_pallas(res, do, scale=_scale(q, scale), causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=False)
    else:
        block_k = _tiles_for(q, k_bias, causal, block_q, block_k)[1]
        grads = _bwd_blockwise(res, do, scale=_scale(q, scale),
                               causal=causal, block_k=block_k)
    dbias = None if k_bias is None else jnp.zeros_like(k_bias)
    return grads + (dbias,)


_flash.defvjp(_flash_fwd, _flash_bwd)


def mha_reference(q, k, v, causal=True, scale=None, k_bias=None):
    """Unfused reference (the reference framework's BatchMatMul+Softmax
    attention) — used as the numerical oracle in tests."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if k_bias is not None:
        s = s + k_bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        n = q.shape[2]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
