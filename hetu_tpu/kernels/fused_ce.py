"""Fused linear + softmax cross-entropy ("cut cross-entropy") for TPU.

The standard path materializes the full (N, V) logits tensor in HBM twice
(forward + backward) — for BERT-base's MLM head that is N=B·P rows against
V≈30k vocab, ~300 MB of f32 per direction per step, pure bandwidth. This
kernel never materializes logits: the VOCABULARY is a grid axis, so weight
TILES stream HBM->VMEM one (block_v, D) slab at a time while per-row online
(max, sum) logsumexp state lives in VMEM scratch — the flash-attention
recurrence with the vocabulary playing the key axis. The backward recomputes
each probability tile from the saved per-row lse (no residual bigger than
(N,)).

    nll = fused_linear_nll(h, W, b, targets)   # (N,) per-row -log p[target]

with ``logits = h @ W^T + b`` implied (``w_layout="vd"``, W is (V, D) — the
tied-embedding orientation) or ``logits = h @ W + b`` (``w_layout="dv"``,
W is (D, V) — the LM-head orientation). Both layouts are native: no caller
ever transposes a vocab-sized matrix. Differentiable wrt h, W, b via
custom_vjp (targets are integers; their cotangent is None).

Reference accounting: SURVEY §7 names softmax-CE a Pallas fusion candidate;
the technique is the public "cut your losses" formulation re-derived for
the Pallas TPU programming model. Interpret mode off-TPU (same code runs in
the CPU-mesh tests); ``linear_nll_reference`` is the numerical oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_V = 512
_NEG_INF = -1e30

# the kernels' names in the device trace (docs/KERNELS.md): one constant a
# pallas_call site, written as the call's `name=`
FUSED_CE_FWD = "fused_ce_fwd"
FUSED_CE_BWD_DH = "fused_ce_bwd_dh"
FUSED_CE_BWD_DW = "fused_ce_bwd_dw"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def should_fuse(flag, mesh=None) -> bool:
    """The ONE gating rule for config flags ('auto' | True | False): fused
    CE runs on the single-program TPU path. Under a mesh the einsum form
    stays (GSPMD cannot partition the custom kernel); off-TPU interpret
    mode would be slower than the einsum."""
    if mesh is not None:
        return False
    return flag is True or (flag == "auto" and _on_tpu())


def _dot_hw(h, w_blk, w_dv):
    """(Bn, D) x W tile -> (Bn, block_v) logits tile for either layout."""
    if w_dv:   # w_blk (D, block_v)
        return jax.lax.dot(h, w_blk, preferred_element_type=jnp.float32)
    # w_blk (block_v, D)
    return jax.lax.dot_general(h, w_blk, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward: grid (row_blocks, vocab_blocks) — vocab innermost; the online
# (m, l, target-logit) state lives in scratch across the vocab sweep
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, b_ref, tgt_ref, lse_ref, tl_ref,
                m_sc, l_sc, tl_sc, *, block_v, vocab, n_vb, w_dv):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        tl_sc[:] = jnp.zeros_like(tl_sc[:])

    h = h_ref[0].astype(jnp.float32)                  # (Bn, D)
    tgt = tgt_ref[0, :, 0]                            # (Bn,)
    w_blk = w_ref[0].astype(jnp.float32)
    b_blk = b_ref[0, :, 0].astype(jnp.float32)
    Bn = h.shape[0]
    s = _dot_hw(h, w_blk, w_dv) + b_blk
    vpos = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (Bn, block_v), 1)
    s = jnp.where(vpos < vocab, s, _NEG_INF)          # vocab tail mask
    hit = vpos == tgt[:, None]
    tl_sc[:] = tl_sc[:] + jnp.sum(jnp.where(hit, s, 0.0), axis=1)
    m_prev, l_prev = m_sc[:], l_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    l_new = (l_prev * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(s - m_new[:, None]), axis=1))
    m_sc[:] = m_new
    l_sc[:] = l_new

    @pl.when(vj == n_vb - 1)
    def _emit():
        lse_ref[0, :, 0] = m_sc[:] + jnp.log(jnp.maximum(l_sc[:], 1e-30))
        tl_ref[0, :, 0] = tl_sc[:]


# ---------------------------------------------------------------------------
# backward: dh over (row_blocks, vocab_blocks) accumulating in scratch;
# dW/db over (vocab_blocks, row_blocks) — each recomputes its probability
# tile from (h, W, lse), flash-style
# ---------------------------------------------------------------------------

def _prob_grad_tile(h, w_blk, b_blk, tgt, lse, ct, v0, block_v, vocab, w_dv):
    """(softmax - onehot) * ct for one (row_block, vocab_block) tile."""
    Bn = h.shape[0]
    s = _dot_hw(h, w_blk, w_dv) + b_blk
    vpos = v0 + jax.lax.broadcasted_iota(jnp.int32, (Bn, block_v), 1)
    p = jnp.where(vpos < vocab, jnp.exp(s - lse[:, None]), 0.0)
    return (p - (vpos == tgt[:, None]).astype(jnp.float32)) * ct[:, None]


def _bwd_dh_kernel(h_ref, w_ref, b_ref, tgt_ref, lse_ref, ct_ref, dh_ref,
                   acc_sc, *, block_v, vocab, n_vb, w_dv):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc[:])

    h = h_ref[0].astype(jnp.float32)
    w_blk = w_ref[0].astype(jnp.float32)
    g = _prob_grad_tile(h, w_blk, b_ref[0, :, 0].astype(jnp.float32),
                        tgt_ref[0, :, 0], lse_ref[0, :, 0], ct_ref[0, :, 0],
                        vj * block_v, block_v, vocab, w_dv)
    if w_dv:   # w_blk (D, block_v): dh += g @ w_blk^T
        acc_sc[:] = acc_sc[:] + jax.lax.dot_general(
            g, w_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:      # w_blk (block_v, D): dh += g @ w_blk
        acc_sc[:] = acc_sc[:] + jax.lax.dot(
            g, w_blk, preferred_element_type=jnp.float32)

    @pl.when(vj == n_vb - 1)
    def _emit():
        dh_ref[0] = acc_sc[:].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, b_ref, tgt_ref, lse_ref, ct_ref,
                   dw_ref, db_ref, dw_sc, db_sc, *, block_n, block_v,
                   vocab, n_nb, w_dv):
    vj, nj = pl.program_id(0), pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        dw_sc[:] = jnp.zeros_like(dw_sc[:])
        db_sc[:] = jnp.zeros_like(db_sc[:])

    h = h_ref[0].astype(jnp.float32)                  # (Bn, D)
    w_blk = w_ref[0].astype(jnp.float32)
    g = _prob_grad_tile(h, w_blk, b_ref[0, :, 0].astype(jnp.float32),
                        tgt_ref[0, :, 0], lse_ref[0, :, 0], ct_ref[0, :, 0],
                        vj * block_v, block_v, vocab, w_dv)
    if w_dv:   # dw tile (D, block_v) += h^T @ g
        dw_sc[:] = dw_sc[:] + jax.lax.dot_general(
            h, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:      # dw tile (block_v, D) += g^T @ h
        dw_sc[:] = dw_sc[:] + jax.lax.dot_general(
            g, h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    db_sc[:] = db_sc[:] + jnp.sum(g, axis=0)

    @pl.when(nj == n_nb - 1)
    def _emit():
        dw_ref[0] = dw_sc[:].astype(dw_ref.dtype)
        db_ref[0, :, 0] = db_sc[:].astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# host-side plumbing
# ---------------------------------------------------------------------------

def _pad_to(x, mult, axis):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(h, w, b, targets, block_n, block_v, w_dv):
    out, _ = _fused_fwd(h, w, b, targets, block_n, block_v, w_dv)
    return out


def fused_linear_nll(h, w, b, targets, block_n=DEFAULT_BLOCK_N,
                     block_v=DEFAULT_BLOCK_V, w_layout="vd"):
    """Per-row NLL of ``softmax(linear(h))`` without materializing the
    (N, V) logits. h: (N, D); b: (V,); targets: (N,) int32; w: (V, D) with
    ``w_layout="vd"`` (tied-embedding orientation, logits = h @ w^T + b) or
    (D, V) with ``w_layout="dv"`` (LM-head orientation, logits = h @ w + b).
    Returns (N,) f32. Differentiable wrt h, w, b."""
    assert w_layout in ("vd", "dv"), w_layout
    return _fused(h, w, b, targets, block_n, block_v, w_layout == "dv")


def _stage(h, w, b, targets, block_n, block_v, w_dv):
    N = h.shape[0]
    V = w.shape[1] if w_dv else w.shape[0]
    block_n = min(block_n, max(N, 1))
    block_v = min(block_v, max(V, 1))
    hp = _pad_to(h, block_n, 0)
    tp = _pad_to(targets.astype(jnp.int32), block_n, 0)
    wp = _pad_to(w, block_v, 1 if w_dv else 0)
    bp = _pad_to(b, block_v, 0)
    return hp, wp, bp, tp, N, V, block_n, block_v


def _w_spec(block_v, D, w_dv):
    if w_dv:
        return pl.BlockSpec((1, D, block_v), lambda i, j: (0, 0, j))
    return pl.BlockSpec((1, block_v, D), lambda i, j: (0, j, 0))


def _fused_fwd(h, w, b, targets, block_n, block_v, w_dv):
    hp, wp, bp, tp, N, V, block_n, block_v = _stage(
        h, w, b, targets, block_n, block_v, w_dv)
    Np, D = hp.shape
    Vp = wp.shape[1] if w_dv else wp.shape[0]
    n_vb = Vp // block_v
    row = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, i, 0))
    lse, tl = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, vocab=V, n_vb=n_vb,
                          w_dv=w_dv),
        grid=(Np // block_n, n_vb),   # vocab innermost: W tiles stream
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
            _w_spec(block_v, D, w_dv),
            pl.BlockSpec((1, block_v, 1), lambda i, j: (0, j, 0)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, Np, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n,), jnp.float32)] * 3,
        interpret=not _on_tpu(),
        name=FUSED_CE_FWD,
    )(hp[None], wp[None], bp[None, :, None], tp[None, :, None])
    nll = (lse[0, :N, 0] - tl[0, :N, 0])
    return nll, (h, w, b, targets, lse[0, :, 0])


def _fused_bwd(block_n, block_v, w_dv, res, ct):
    h, w, b, targets, lse_p = res
    hp, wp, bp, tp, N, V, block_n, block_v = _stage(
        h, w, b, targets, block_n, block_v, w_dv)
    Np, D = hp.shape
    Vp = wp.shape[1] if w_dv else wp.shape[0]
    n_vb, n_nb = Vp // block_v, Np // block_n
    ctp = _pad_to(ct.astype(jnp.float32), block_n, 0)  # padded rows: ct = 0
    lsep = lse_p[None, :, None]
    row_i = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, i, 0))

    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_v=block_v, vocab=V,
                          n_vb=n_vb, w_dv=w_dv),
        grid=(n_nb, n_vb),
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
            _w_spec(block_v, D, w_dv),
            pl.BlockSpec((1, block_v, 1), lambda i, j: (0, j, 0)),
            row_i, row_i, row_i,
        ],
        out_specs=pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Np, D), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, D), jnp.float32)],
        interpret=not _on_tpu(),
        name=FUSED_CE_BWD_DH,
    )(hp[None], wp[None], bp[None, :, None], tp[None, :, None], lsep,
      ctp[None, :, None])

    # dW/db: vocab blocks OUTER, row blocks inner (each W tile revisits its
    # accumulator across the row sweep)
    row_j = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, j, 0))
    wspec = (pl.BlockSpec((1, D, block_v), lambda i, j: (0, 0, i)) if w_dv
             else pl.BlockSpec((1, block_v, D), lambda i, j: (0, i, 0)))
    dw_shape = (1, D, Vp) if w_dv else (1, Vp, D)
    dw_out = (pl.BlockSpec((1, D, block_v), lambda i, j: (0, 0, i)) if w_dv
              else pl.BlockSpec((1, block_v, D), lambda i, j: (0, i, 0)))
    dw_sc = (pltpu.VMEM((D, block_v), jnp.float32) if w_dv
             else pltpu.VMEM((block_v, D), jnp.float32))
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_n=block_n, block_v=block_v,
                          vocab=V, n_nb=n_nb, w_dv=w_dv),
        grid=(n_vb, n_nb),
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, j, 0)),
            wspec,
            pl.BlockSpec((1, block_v, 1), lambda i, j: (0, i, 0)),
            row_j, row_j, row_j,
        ],
        out_specs=[
            dw_out,
            pl.BlockSpec((1, block_v, 1), lambda i, j: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(dw_shape, w.dtype),
            jax.ShapeDtypeStruct((1, Vp, 1), jnp.float32),
        ],
        scratch_shapes=[dw_sc, pltpu.VMEM((block_v,), jnp.float32)],
        interpret=not _on_tpu(),
        name=FUSED_CE_BWD_DW,
    )(hp[None], wp[None], bp[None, :, None], tp[None, :, None], lsep,
      ctp[None, :, None])

    dw_full = dw[0, :, :V] if w_dv else dw[0, :V]
    return (dh[0, :N].astype(h.dtype), dw_full.astype(w.dtype),
            db[0, :V, 0].astype(b.dtype), None)


_fused.defvjp(_fused_fwd, _fused_bwd)


def linear_nll_reference(h, w, b, targets, w_layout="vd"):
    """Unfused oracle: materializes the full logits."""
    wf = w.astype(jnp.float32)
    if w_layout == "vd":
        wf = wf.T
    logits = h.astype(jnp.float32) @ wf + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[:, None].astype(jnp.int32),
                                -1)[:, 0]
