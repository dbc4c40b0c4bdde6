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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _causal_mask(s, q_start, k_start, block_q, block_k):
    """Mask scores above the diagonal for one (q block, k block) tile."""
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _causal_upper_kb(q_start, block_q, block_k):
    """First key block strictly above the diagonal, by CEIL division —
    flooring would drop the diagonal block whenever block_q < block_k
    (regression guard: test_flash_causal_uneven_blocks). Shared by the
    forward and dq kernels so the bound cannot drift between them."""
    return (q_start + block_q + block_k - 1) // block_k


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *, scale,
                causal, use_bias, block_k, seq_len):
    # grid: (batch*heads, q_blocks); refs carry one q block and the full k/v
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (block_q, d)
    block_q = q.shape[0]
    q_start = qi * block_q

    num_kb = seq_len // block_k

    def body(kj, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if use_bias:
            s = s + bias_ref[0, pl.ds(kj * block_k, block_k), 0][None, :]
        if causal:
            s = _causal_mask(s, q_start, kj * block_k, block_q, block_k)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    # causal: skip key blocks entirely above the diagonal
    upper = (num_kb if not causal
             else _causal_upper_kb(q_start, block_q, block_k))
    acc0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, :, 0] = m + jnp.log(l)


def _expand_bias(k_bias, b, h, s):
    """(b, s) per-key bias -> (b*h, s, 1) column blocks for the kernels."""
    kb = jnp.broadcast_to(k_bias.astype(jnp.float32)[:, None, :], (b, h, s))
    return kb.reshape(b * h, s, 1)


def _fwd_pallas(q, k, v, k_bias, scale, causal, block_q, block_k, interpret):
    b, h, s, d = q.shape
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(bh, s, d)
    vf = v.reshape(bh, s, d)
    grid = (bh, s // block_q)
    use_bias = k_bias is not None
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             use_bias=use_bias, block_k=block_k, seq_len=s)
    if not use_bias:
        def kern(q_ref, k_ref, v_ref, o_ref, lse_ref):  # noqa: F811
            return _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                               scale=scale, causal=causal, use_bias=False,
                               block_k=block_k, seq_len=s)
    in_specs = [
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
    ]
    ops = [qf, kf, vf]
    if use_bias:
        in_specs.append(pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, 0)))
        ops.append(_expand_bias(k_bias, b, h, s))
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # trailing singleton keeps the block's last-two dims TPU-tileable
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(*ops)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# ---------------------------------------------------------------------------
# backward Pallas kernels (dq; dk+dv) — flash backward both directions:
# each tile recomputes its probability block from (q, k, lse), so nothing
# (S, S)-shaped ever exists. delta = rowsum(dO * O) is precomputed in XLA.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   bias_ref, dq_ref, *, scale, causal, use_bias, block_k,
                   seq_len):
    # grid: (batch*heads, q_blocks); owns one q block, loops over k blocks
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (block_q, d)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]                            # (block_q,)
    delta = delta_ref[0, :, 0]
    block_q = q.shape[0]
    q_start = qi * block_q
    num_kb = seq_len // block_k

    def body(kj, dq):
        k_blk = k_ref[0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if use_bias:
            s = s + bias_ref[0, pl.ds(kj * block_k, block_k), 0][None, :]
        if causal:
            s = _causal_mask(s, q_start, kj * block_k, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot(ds, k_blk,
                                preferred_element_type=jnp.float32)

    upper = (num_kb if not causal
             else _causal_upper_kb(q_start, block_q, block_k))
    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros((block_q, q.shape[1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, dk_ref, dv_ref, *, scale, causal, use_bias,
                    block_q, seq_len):
    # grid: (batch*heads, k_blocks); owns one k/v block, loops over q blocks
    ki = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)              # (block_k, d)
    v_blk = v_ref[0].astype(jnp.float32)
    block_k = k_blk.shape[0]
    k_start = ki * block_k
    num_qb = seq_len // block_q

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if use_bias:
            # this kernel owns ONE k block: its bias column is constant
            s = s + bias_ref[0, :, 0][None, :]
        if causal:
            s = _causal_mask(s, qi * block_q, k_start, block_q, block_k)
        p = jnp.exp(s - lse[:, None])                 # (block_q, block_k)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    # causal: q blocks strictly before this k block contribute nothing
    lower = (k_start // block_q) if causal else 0
    d = k_blk.shape[1]
    dk, dv = jax.lax.fori_loop(
        lower, num_qb, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_pallas(res, do, *, scale, causal, block_q, block_k, interpret):
    q, k, v, o, lse, k_bias = res
    b, h, s, d = q.shape
    bh = b * h
    use_bias = k_bias is not None
    biasf = _expand_bias(k_bias, b, h, s) if use_bias else None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                           # (b, h, s)
    qf, kf, vf = (x.reshape(bh, s, d) for x in (q, k, v))
    dof = do.reshape(bh, s, d)
    lsef = lse.reshape(bh, s, 1)
    deltaf = delta.reshape(bh, s, 1)

    full = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
    col = pl.BlockSpec((1, s, 1), lambda i, j: (i, 0, 0))

    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                use_bias=use_bias, block_k=block_k,
                                seq_len=s)
    if not use_bias:
        def dq_kern(q_ref, k_ref, v_ref, do_ref, lse_ref,  # noqa: F811
                    delta_ref, dq_ref):
            return _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, None, dq_ref, scale=scale,
                                  causal=causal, use_bias=False,
                                  block_k=block_k, seq_len=s)
    dq_specs = [
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            full, full,
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
    ]
    dq_ops = [qf, kf, vf, dof, lsef, deltaf]
    if use_bias:
        dq_specs.append(col)
        dq_ops.append(biasf)
    dq = pl.pallas_call(
        dq_kern,
        grid=(bh, s // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        name=FLASH_BWD_DQ,
    )(*dq_ops)

    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale,
                                 causal=causal, use_bias=use_bias,
                                 block_q=block_q, seq_len=s)
    if not use_bias:
        def dkv_kern(q_ref, k_ref, v_ref, do_ref, lse_ref,  # noqa: F811
                     delta_ref, dk_ref, dv_ref):
            return _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, None, dk_ref, dv_ref,
                                   scale=scale, causal=causal,
                                   use_bias=False, block_q=block_q,
                                   seq_len=s)
    dkv_specs = [
            full,
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            full, col, col,
    ]
    dkv_ops = [qf, kf, vf, dof, lsef, deltaf]
    if use_bias:
        dkv_specs.append(
            pl.BlockSpec((1, block_k, 1), lambda i, j: (i, j, 0)))
        dkv_ops.append(biasf)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(bh, s // block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV,
    )(*dkv_ops)

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


def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    k_bias=None):
    """Fused attention. q/k/v: (batch, heads, seq, head_dim).

    ``k_bias``: optional (batch, seq) float added to every score column —
    the key-padding mask form (0 valid / -1e9 padded). Non-trainable: its
    cotangent is zero."""
    return _flash(q, k, v, k_bias, causal, scale, block_q, block_k)


def _resolve(q, scale, block_q, block_k):
    s = q.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq_len {s} must divide blocks ({block_q},{block_k})")
    return scale, block_q, block_k


def _flash_fwd(q, k, v, k_bias, causal, scale, block_q, block_k):
    scale, block_q, block_k = _resolve(q, scale, block_q, block_k)
    out, lse = _fwd_pallas(q, k, v, k_bias, scale, causal, block_q, block_k,
                           interpret=not _on_tpu())
    return out, (q, k, v, out, lse, k_bias)


def _flash_bwd(causal, scale, block_q, block_k, res, do):
    q = res[0]
    scale, block_q, block_k = _resolve(q, scale, block_q, block_k)
    if _on_tpu():
        grads = _bwd_pallas(res, do, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=False)
    else:
        grads = _bwd_blockwise(res, do, scale=scale, causal=causal,
                               block_k=block_k)
    k_bias = res[5]
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
