"""Fused linear + softmax cross-entropy ("cut cross-entropy") for TPU.

The standard path materializes the full (N, V) logits tensor in HBM twice
(forward + backward) — for BERT-base's MLM head that is N=B·P rows against
V≈30k vocab, ~300 MB of f32 per direction per step, pure bandwidth. This
kernel never materializes logits: the VOCABULARY is a grid axis, so weight
TILES stream HBM->VMEM one (block_v, D) slab at a time while the online
(max, sum) logsumexp state lives in VMEM scratch — the flash-attention
recurrence with the vocabulary playing the key axis. The backward recomputes
each probability tile from the saved per-row lse (no residual bigger than
(N,)). The blocks are chosen from the call's shapes (`_choose_blocks`;
docs/KERNELS.md, "Fused CE's tile program").

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


# ---------------------------------------------------------------------------
# the tile program: blocks chosen from the call's shapes (docs/KERNELS.md,
# "Fused CE's tile program")
# ---------------------------------------------------------------------------

_LANES = 128
_ROW_BLOCKS = (1024, 512, 256, 128)
_VOCAB_BLOCKS = (512, 256, 128)
# What one grid step may hold in VMEM by `_vmem_bytes`' count: three
# quarters, as flash's, of the 16 MiB Mosaic gives a kernel on a v5e unasked,
# or of the 32 MiB (`vmem_limit_bytes`; the chip has 128) a call asks for
# whose blocks are over the first. Asking is not free: with a limit passed
# on its three calls a BERT step held 87 MB more of HBM (PERF.md, PR 30).
_VMEM_BUDGET_UNASKED = 12 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 24 * 1024 * 1024
# Rows a block needs for the forward to do twice the v5e's 240 FLOP a byte
# of the head it streams: a block of that many rows that fits unasked stays.
_RIDGE_ROWS = 512


def _vmem_bytes(bn, bv, D, itemsize, kernel):
    """VMEM one grid step of `kernel` (one of the three names) holds at row
    block `bn`, vocabulary block `bv`, width `D`: the h block and the W
    tile, two buffers each; three (1, bn, 1) row blocks (targets, and lse
    and the target logit, or lse and ct), which pad to 128 lanes (512 B a
    row) and have two buffers too; three f32 arrays of the score tile's
    size; and what the kernel carries: the forward its three (bn, 128) f32
    states, `fused_ce_bwd_dh` its (bn, D) f32 sum, two buffers of the dh
    block and g in the operands' dtype, `fused_ce_bwd_dw` the same of its
    W tile's size and h transposed. Fitted from above to what Mosaic takes
    for a described v5e (the least `vmem_limit_bytes` it compiles under, at
    D = 2048 bf16: forward 13 / 21 MiB at 512 / 1024 x 512, `dh` 21 / 36,
    `dw` 20 / 29; this count 13.2 / 22.5, 21 / 38, 23 / 34); at D = 768 it
    overstates twice."""
    operands = 2 * (bn + bv) * D * itemsize
    rows = 2 * 3 * bn * _LANES * 4
    tiles = 3 * bn * bv * 4
    if kernel == FUSED_CE_FWD:
        carried = 3 * bn * _LANES * 4
    elif kernel == FUSED_CE_BWD_DH:
        carried = bn * D * (4 + 2 * itemsize) + bn * bv * itemsize
    else:
        carried = (bv * D * (4 + 2 * itemsize) + bn * bv * itemsize
                   + bn * D * itemsize)
    return operands + rows + tiles + carried


def _choose_blocks(N, V, D, dtype, block_n=None, block_v=None):
    """-> ((block_n, block_v) of `fused_ce_fwd`, of `fused_ce_bwd_dh`, of
    `fused_ce_bwd_dw`), from the call's shapes alone. A `block_n`/`block_v`
    the caller passed is kept, in all three.

    The forward and `dh` have the vocabulary innermost: every row block
    streams the whole head, so they do `block_n` (twice that in `dh`) FLOP a
    byte read, against the v5e's 240. Each takes the largest row block of
    1024/512/256/128 that `_vmem_bytes` fits into a budget with some
    vocabulary block of 512/256/128, the widest that fits; among row blocks
    that fit, the one that pads N least. The budget is what Mosaic gives
    unasked while that admits `_RIDGE_ROWS` rows (or the row block the
    larger budget would take, if that is smaller), else `_VMEM_BUDGET`. N
    below 128 is one block, V below a block is one tile. `dw` has the
    vocabulary outermost and streams h once a 512-wide W tile: it keeps
    that width and takes its row block by the same rule (a row block there
    only sets the number of grid steps)."""
    itemsize = jnp.dtype(dtype).itemsize
    if block_n is not None:
        rows = [min(block_n, max(N, 1))]
    elif N < _ROW_BLOCKS[-1]:
        rows = [max(N, 1)]
    else:
        rows = sorted(_ROW_BLOCKS, key=lambda bn: (-N % bn, -bn))

    def pick(kernel, widths):
        if block_v is not None:
            widths = (block_v,)
        widths = sorted({min(bv, max(V, 1)) for bv in widths}, reverse=True)

        def fit(budget):
            return next(((bn, bv) for bn in rows for bv in widths
                         if _vmem_bytes(bn, bv, D, itemsize, kernel)
                         <= budget), (min(rows), widths[-1]))

        unasked, asked = fit(_VMEM_BUDGET_UNASKED), fit(_VMEM_BUDGET)
        enough = unasked[0] >= min(_RIDGE_ROWS, asked[0])
        return unasked if enough else asked

    return (pick(FUSED_CE_FWD, _VOCAB_BLOCKS),
            pick(FUSED_CE_BWD_DH, _VOCAB_BLOCKS),
            pick(FUSED_CE_BWD_DW, _VOCAB_BLOCKS[:1]))


def _operands(h, w_blk):
    """The MXU's operands as the caller gave them: bf16 h and W go in as
    bf16 (every dot has `preferred_element_type=float32`), float32 ones as
    float32; a mixed pair is promoted."""
    dt = jnp.promote_types(h.dtype, w_blk.dtype)
    return h.astype(dt), w_blk.astype(dt)


def _dot_hw(h, w_blk, w_dv):
    """(Bn, D) x W tile -> (Bn, block_v) logits tile for either layout."""
    if w_dv:   # w_blk (D, block_v)
        return jax.lax.dot(h, w_blk, preferred_element_type=jnp.float32)
    # w_blk (block_v, D)
    return jax.lax.dot_general(h, w_blk, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward: grid (row_blocks, vocab_blocks) — vocab innermost; the online
# (m, l, target-logit) state lives in scratch across the vocab sweep, one
# value a row and LANE: column c of a tile belongs to lane c mod 128, so a
# grid step folds its tile into the state with elementwise work alone, and
# the lanes of a row meet once, when its lse is written
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, b_ref, tgt_ref, lse_ref, tl_ref,
                m_sc, l_sc, tl_sc, *, block_v, vocab, n_vb, w_dv):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        tl_sc[:] = jnp.zeros_like(tl_sc[:])

    h, w_blk = _operands(h_ref[0], w_ref[0])          # (Bn, D), W tile
    Bn, lanes = m_sc.shape
    s = _dot_hw(h, w_blk, w_dv) + b_ref[0].astype(jnp.float32)
    vpos = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (Bn, block_v), 1)
    s = jnp.where(vpos < vocab, s, _NEG_INF)          # vocab tail mask
    hit = jnp.where(vpos == tgt_ref[0], s, 0.0)
    folds = [slice(c, c + lanes) for c in range(0, block_v, lanes)]
    m_prev = m_sc[:]
    m_new = functools.reduce(jnp.maximum, [s[:, c] for c in folds], m_prev)
    l_sc[:] = l_sc[:] * jnp.exp(m_prev - m_new) + sum(
        jnp.exp(s[:, c] - m_new) for c in folds)
    tl_sc[:] = tl_sc[:] + sum(hit[:, c] for c in folds)
    m_sc[:] = m_new

    @pl.when(vj == n_vb - 1)
    def _emit():
        m = jnp.max(m_sc[:], axis=1, keepdims=True)   # (Bn, 1)
        l = jnp.sum(l_sc[:] * jnp.exp(m_sc[:] - m), axis=1, keepdims=True)
        lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-30))
        tl_ref[0] = jnp.sum(tl_sc[:], axis=1, keepdims=True)


def _fold_lanes(block_v):
    """Lanes of the forward's state: 128, or the whole of a vocabulary
    block that is not whole 128-lane tiles (one a caller passed, or a
    vocabulary below a block)."""
    return block_v if block_v % _LANES else _LANES


# ---------------------------------------------------------------------------
# backward: dh over (row_blocks, vocab_blocks) accumulating in scratch;
# dW/db over (vocab_blocks, row_blocks) — each recomputes its probability
# tile from (h, W, lse), flash-style
# ---------------------------------------------------------------------------

def _prob_grad_tile(h, w_blk, b_ref, tgt_ref, lse_ref, ct_ref, v0, block_v,
                    vocab, w_dv):
    """(softmax - onehot) * ct for one (row_block, vocab_block) tile, f32."""
    Bn = h.shape[0]
    s = _dot_hw(h, w_blk, w_dv) + b_ref[0].astype(jnp.float32)
    vpos = v0 + jax.lax.broadcasted_iota(jnp.int32, (Bn, block_v), 1)
    p = jnp.where(vpos < vocab, jnp.exp(s - lse_ref[0]), 0.0)
    return (p - (vpos == tgt_ref[0]).astype(jnp.float32)) * ct_ref[0]


def _bwd_dh_kernel(h_ref, w_ref, b_ref, tgt_ref, lse_ref, ct_ref, dh_ref,
                   acc_sc, *, block_v, vocab, n_vb, w_dv):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc[:])

    h, w_blk = _operands(h_ref[0], w_ref[0])
    g = _prob_grad_tile(h, w_blk, b_ref, tgt_ref, lse_ref, ct_ref,
                        vj * block_v, block_v, vocab, w_dv).astype(h.dtype)
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
                   dw_ref, db_ref, dw_sc, db_sc, *, block_v, vocab, n_nb,
                   w_dv):
    vj, nj = pl.program_id(0), pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        dw_sc[:] = jnp.zeros_like(dw_sc[:])
        db_sc[:] = jnp.zeros_like(db_sc[:])

    h, w_blk = _operands(h_ref[0], w_ref[0])          # (Bn, D), W tile
    g = _prob_grad_tile(h, w_blk, b_ref, tgt_ref, lse_ref, ct_ref,
                        vj * block_v, block_v, vocab, w_dv)
    db_sc[:] = db_sc[:] + jnp.sum(g, axis=0, keepdims=True)
    g = g.astype(h.dtype)
    if w_dv:   # dw tile (D, block_v) += h^T @ g
        dw_sc[:] = dw_sc[:] + jax.lax.dot_general(
            h, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:      # dw tile (block_v, D) += g^T @ h
        dw_sc[:] = dw_sc[:] + jax.lax.dot_general(
            g, h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(nj == n_nb - 1)
    def _emit():
        dw_ref[0] = dw_sc[:].astype(dw_ref.dtype)
        db_ref[0] = db_sc[:].astype(db_ref.dtype)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused(h, w, b, targets, blocks, w_dv):
    out, _ = _fused_fwd(h, w, b, targets, blocks, w_dv)
    return out


def fused_linear_nll(h, w, b, targets, block_n=None, block_v=None,
                     w_layout="vd"):
    """Per-row NLL of ``softmax(linear(h))`` without materializing the
    (N, V) logits. h: (N, D); b: (V,); targets: (N,) int32; w: (V, D) with
    ``w_layout="vd"`` (tied-embedding orientation, logits = h @ w^T + b) or
    (D, V) with ``w_layout="dv"`` (LM-head orientation, logits = h @ w + b).
    Returns (N,) f32. Differentiable wrt h, w, b. ``block_n``/``block_v``
    left at None are chosen from the shapes (`_choose_blocks`)."""
    assert w_layout in ("vd", "dv"), w_layout
    w_dv = w_layout == "dv"
    blocks = _choose_blocks(h.shape[0], w.shape[1 if w_dv else 0],
                            h.shape[1], h.dtype, block_n, block_v)
    return _fused(h, w, b, targets, blocks, w_dv)


def _pad_rows(x, blocks):
    """Rows padded to whole row blocks of all three kernels: their row
    blocks divide the largest."""
    return _pad_to(x, max(bn for bn, _ in blocks), 0)


def _vocab(w, b, block_v, w_dv):
    """W and the bias padded to whole vocabulary blocks, with the leading
    axis the kernels' block specs expect: the bias a (1, 1, Vp) row, the
    vocabulary on the lanes."""
    return (_pad_to(w, block_v, 1 if w_dv else 0)[None],
            _pad_to(b, block_v, 0)[None, None, :])


def _w_spec(block_v, D, w_dv, vocab_axis):
    """The W tile of vocabulary block `program_id(vocab_axis)`."""
    def at(*ids):
        return (0, 0, ids[vocab_axis]) if w_dv else (0, ids[vocab_axis], 0)
    return pl.BlockSpec((1, D, block_v) if w_dv else (1, block_v, D), at)


def _call(kernel, name, block, D, dtype, **kw):
    """`pallas_call` under the kernel's name; Mosaic is asked for
    `_VMEM_LIMIT` only where the step's count is over what it gives
    unasked."""
    over = _vmem_bytes(*block, D, jnp.dtype(dtype).itemsize,
                       name) > _VMEM_BUDGET_UNASKED
    return pl.pallas_call(
        kernel, interpret=not _on_tpu(), name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT if over else None), **kw)


def _fused_fwd(h, w, b, targets, blocks, w_dv):
    N, D = h.shape
    V = w.shape[1 if w_dv else 0]
    block_n, block_v = blocks[0]
    hp = _pad_rows(h, blocks)[None]
    tp = _pad_rows(targets.astype(jnp.int32), blocks)[None, :, None]
    wp, bp = _vocab(w, b, block_v, w_dv)
    Np, n_vb = hp.shape[1], bp.shape[2] // block_v
    row = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, i, 0))
    lse, tl = _call(
        functools.partial(_fwd_kernel, block_v=block_v, vocab=V, n_vb=n_vb,
                          w_dv=w_dv),
        FUSED_CE_FWD, blocks[0], D, h.dtype,
        grid=(Np // block_n, n_vb),   # vocab innermost: W tiles stream
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
            _w_spec(block_v, D, w_dv, 1),
            pl.BlockSpec((1, 1, block_v), lambda i, j: (0, 0, j)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((1, Np, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_n, _fold_lanes(block_v)),
                                   jnp.float32)] * 3,
    )(hp, wp, bp, tp)
    nll = (lse[0, :N, 0] - tl[0, :N, 0])
    return nll, (h, w, b, targets, lse)


def _fused_bwd(blocks, w_dv, res, ct):
    h, w, b, targets, lse = res
    N, D = h.shape
    V = w.shape[1 if w_dv else 0]
    hp = _pad_rows(h, blocks)[None]
    tp = _pad_rows(targets.astype(jnp.int32), blocks)[None, :, None]
    # padded rows: ct = 0
    ctp = _pad_rows(ct.astype(jnp.float32), blocks)[None, :, None]
    Np = hp.shape[1]

    block_n, block_v = blocks[1]
    wp, bp = _vocab(w, b, block_v, w_dv)
    n_vb = bp.shape[2] // block_v
    row_i = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, i, 0))
    dh = _call(
        functools.partial(_bwd_dh_kernel, block_v=block_v, vocab=V,
                          n_vb=n_vb, w_dv=w_dv),
        FUSED_CE_BWD_DH, blocks[1], D, h.dtype,
        grid=(Np // block_n, n_vb),
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
            _w_spec(block_v, D, w_dv, 1),
            pl.BlockSpec((1, 1, block_v), lambda i, j: (0, 0, j)),
            row_i, row_i, row_i,
        ],
        out_specs=pl.BlockSpec((1, block_n, D), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Np, D), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, D), jnp.float32)],
    )(hp, wp, bp, tp, lse, ctp)

    # dW/db: vocab blocks OUTER, row blocks inner (each W tile revisits its
    # accumulator across the row sweep)
    block_n, block_v = blocks[2]
    wp, bp = _vocab(w, b, block_v, w_dv)
    n_vb = bp.shape[2] // block_v
    n_nb = Np // block_n
    row_j = pl.BlockSpec((1, block_n, 1), lambda i, j: (0, j, 0))
    b_tile = pl.BlockSpec((1, 1, block_v), lambda i, j: (0, 0, i))
    w_tile = _w_spec(block_v, D, w_dv, 0)
    dw, db = _call(
        functools.partial(_bwd_dw_kernel, block_v=block_v, vocab=V,
                          n_nb=n_nb, w_dv=w_dv),
        FUSED_CE_BWD_DW, blocks[2], D, h.dtype,
        grid=(n_vb, n_nb),
        in_specs=[
            pl.BlockSpec((1, block_n, D), lambda i, j: (0, j, 0)),
            w_tile, b_tile, row_j, row_j, row_j,
        ],
        out_specs=[w_tile, b_tile],
        out_shape=[
            jax.ShapeDtypeStruct(wp.shape, w.dtype),
            jax.ShapeDtypeStruct(bp.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM(w_tile.block_shape[1:], jnp.float32),
                        pltpu.VMEM((1, block_v), jnp.float32)],
    )(hp, wp, bp, tp, lse, ctp)

    dw_full = dw[0, :, :V] if w_dv else dw[0, :V]
    return (dh[0, :N].astype(h.dtype), dw_full.astype(w.dtype),
            db[0, 0, :V].astype(b.dtype), None)


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
