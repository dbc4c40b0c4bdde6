"""Fused causal attention for TPU.

The reference's attention is unfused BatchMatMul + Softmax + BatchMatMul
(examples/nlp/hetu_transformer.py:56+), materializing the (S, S) score matrix
in HBM. This module computes attention blockwise with an online softmax so
only (block_q, block_k) tiles ever exist:

- forward: a Pallas kernel — q/k/v tiles stream HBM->VMEM, scores hit the
  MXU, the running (max, sum) rescale keeps the softmax exact. Falls back to
  interpreter mode off-TPU so the same code runs in CPU-mesh tests.
- backward: ONE Pallas kernel on TPU, recomputing each probability tile
  from (q, k, lse) — no (S,S) materialization — and making dq, dk and dv
  from that one rebuilt tile (five matmuls, one exp pass). Which one, the
  call's shapes say (`_kernels_of`): a sequence that is ONE tile (up to 512
  positions: BERT) has nothing to sum over blocks and runs `flash_bwd`; a
  sequence of several tiles (the decoders at 4,096 and 8,192) runs
  `flash_bwd_dqkv`, whose grid walks the key blocks in order: a key block
  owns its dk and dv, and dq is summed over the key blocks in an f32
  scratch that stays in VMEM. Off-TPU, a blockwise `lax.scan` recomputation
  in XLA serves as fallback and numerical oracle.

Public entry: ``flash_attention_btd(qkv, n_heads, causal=True)`` on the
projections' own layout, (batch, seq, heads * head_dim) in and out (``qkv``
one fused [q | k | v] array or three arrays), differentiable via custom_vjp:
nothing is transposed around the kernels. Three arrays may bring TWO head
widths, q's and k's and another for v (so for o): latent attention's q . k
is 192 wide and its p . v 128, and each product runs at its own width. ``flash_attention(q, k, v)`` on
(batch, heads, seq, head_dim) wraps it with XLA transposes. An optional
``k_bias`` (batch, seq) float is ADDED to every score column — the key-
padding mask form (0 valid / -1e9 padded) the BERT encoder uses — so masked
batches keep the fused kernel instead of falling back to the unfused path.
All-padded rows degenerate to a uniform softmax, exactly like the unfused
form (softmax is shift-invariant), so the semantics match the dot path.

A selection that differs by QUERY ROW (learned sparse attention: a query
keeps the keys its indexer ranks highest) comes as ``row_mask``, a
``pack_row_mask`` pair: one bit a (query, key) pair, 32 keys (or queries) of
bit planes a 32-bit word, so that a tile's bits are ONE block of whole lanes
shifted by its plane. The kernels put -inf where the bit is 0, compute every
tile below the diagonal as they do without it (a dense kernel under a mask:
the same numbers as a gather over the kept keys), and such a call returns
its row statistic ``lse`` beside ``o``. A call without it traces the
program it always did.
"""
from __future__ import annotations

import functools
import itertools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.tracing import REMAT_ATTN_LSE, REMAT_ATTN_O

# the kernels' names in the device trace (docs/KERNELS.md): one constant a
# pallas_call site, written as the call's `name=`
FLASH_FWD = "flash_fwd"
FLASH_BWD = "flash_bwd"
FLASH_BWD_DQKV = "flash_bwd_dqkv"

_LANES = 128
_NEG_INF = -1e30

# What one grid step may hold in VMEM, by `_vmem_bytes`' count: three
# quarters of the 16 MiB Mosaic gives a kernel by default on a v5e. The
# count follows the compiler's own: choices it puts at 14.8 and 15.5 MiB
# compile for the v5e, at 17.8 and more they are refused (PR 24).
_VMEM_BUDGET = 12 * 1024 * 1024
# A call whose smallest choice is over that asks Mosaic for more
# (`vmem_limit_bytes`; the chip has 128 MiB) and chooses within three
# quarters of what it asks for, as above. k and v lie whole in VMEM, so it is
# long sequences of wide heads that ask: two heads of 192 / 128 columns (the
# narrowest group whose blocks are whole lane tiles on both arrays) at 8,192
# positions count 25 MiB forward. Asking is not free
# (`fused_ce._VMEM_LIMIT`: HBM held beside), so no call that fits asks.
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET_ASKED = 48 * 1024 * 1024
# `flash_bwd_dqkv` holds q, dO, o and dq whole and dq's f32 sum beside them:
# 61 MiB by the count for those two heads at 8,192 positions, so there is a
# second step to ask for, and a call takes the lower one that holds it.
# At 16,384 positions the same two heads count 114.5 MiB at 512 x 512 blocks
# (q, dO, o, dq and dq's f32 sum are twice as long; kimi-linear-48b-a3b's one
# latent layer): a third step, 120 of the chip's 128 MiB, which Mosaic takes
# for a described v5e (at 100 it refuses the call by 4.75 MiB even at 128 x
# 128 blocks). Its budget is by the count, which reads ~3 MiB over the
# compiler's own at this shape.
_VMEM_LIMITS = (_VMEM_LIMIT, 100 * 1024 * 1024, 120 * 1024 * 1024)
_VMEM_BUDGETS = (_VMEM_BUDGET_ASKED, 75 * 1024 * 1024, 116 * 1024 * 1024)
# FLOPs of the forward a grid step should carry at least: 1.3 us of the
# MXU at the v5e's 197 TFLOP/s (twice that at head size 64, which fills
# half of the array), against the ~0.35 us a grid step costs whatever it
# does. On the chip (PR 24) 2, 4 and 6 heads of 512 x 512 x 64 a step ran
# the three kernels in 7.09, 6.92 and 6.74 ms a layer: flat beyond this.
# Every kernel of a many-tile call, and the backward kernel of a one-tile
# call, takes the fewest heads that carry it: alone on the chip (PR 33;
# bf16, ms a call at 2 / 4 / 6 / 12 heads a step, the two passes that set
# dq and dv into a fused gradient included) `flash_bwd` ran 512 rows of
# 128 x 128 x 64 in 3.59 / 2.64 / 2.37 / 2.00, 256 rows of 256 x 256 x 64
# in 3.14 / 2.59 / 2.43 / 2.27, and 128 rows of 512 x 512 x 64, where four
# heads carry it, in 3.55 / 3.40 / 3.36 / 3.35.
_STEP_FLOPS = 256e6
_MAX_HEADS = 16     # the head loop of a grid step is unrolled
# Lanes a grid step of a ONE-TILE call's forward takes at most where heads
# are narrower than a lane tile (four heads of 64). Such heads share their
# tiles: every other one is shifted along the lanes on its way in and its
# o is stored under a mask, and unrolled past two tiles the step slows
# down however little it carries (PR 33, ms a call at 2 / 4 / 6 / 12 heads
# a step): 512 rows of 128 x 128 x 64 in 2.20 / 1.89 / 2.14 / 2.48 (three
# arrays or one, with a key bias or none; 16 heads at 2 / 4 / 8 / 16: 1.45
# / 1.26 / 1.48 / 1.80), 256 rows of 256 x 256 x 64 in 2.22 / 2.01 / 1.99
# / 2.16, 128 rows of 512 x 512 x 64 flat (1.54 / 1.60 / 1.70 / 1.60).
# Heads of whole lane tiles only gain by it (12 of 128 x 128 x 128 at 1 /
# 2 / 4 / 6 / 12: 1.80 / 1.13 / 0.87 / 0.79 / 0.72), and so does the
# backward kernel at either head size (above). Why, no profile here shows
# (PERF.md section 7); a many-tile forward was not measured and keeps the
# group of its backward kernels.
_ONE_TILE_FWD_LANES = 2 * _LANES


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _vmem_bytes(s, d, itemsize, block_q, block_k, heads, kernel, dv=None,
                mask_w=0):
    """VMEM one grid step of `kernel` holds, `heads` heads a step: its
    operand and result blocks (`heads * d` columns wide for q, k and their
    gradients, `heads * dv` for v, o and theirs; `dv` is `d` unless given)
    and its rows of lse and delta (padded to eight sublanes), all
    double-buffered, and the f32 arrays of the score tile's size of the ONE
    head being worked on.
    - `flash_fwd`: k and v whole, a block of q and of o, a block of lse;
      s, p and one tile in flight, and the head's accumulator.
    - `flash_bwd` (the whole sequence one tile): q, k, v, dO, o in and dq,
      dk, dv out, eight blocks of the whole sequence, and lse; s, p, dp, ds
      and one in flight; the f32 product dO * O of every head of the step,
      with the piece being split off it, for delta.
    - `flash_bwd_dqkv` (a key block against the whole sequence): q, dO, o
      in and dq out whole, k, v in and dk, dv out a block, lse whole; the
      f32 sum of dq and the rows of delta, once each (scratch); s, p, dp, ds
      and dS as the MXU takes it, turned for dq.
    `mask_w`: the words a row of a packed row mask has (0: no mask); a step
    holds one block of rows of it, double-buffered."""
    dv = d if dv is None else dv
    mask = 2 * mask_w * 4 * (block_k if kernel == FLASH_BWD_DQKV
                             else block_q)
    # a position of one operand block of each width, the two side by side
    cols = 2 * heads * (d + dv) * itemsize
    rows = 2 * heads * 8 * 4            # a position of one statistics row
    tile = block_q * block_k * 4
    if kernel == FLASH_FWD:
        return (cols * (s + block_q) + rows * block_q + 3 * tile
                + block_q * dv * 4 + mask)
    if kernel == FLASH_BWD:
        return (cols * 4 * s + rows * s + 5 * tile + 2 * s * heads * dv * 4
                + mask)
    return (2 * cols * (s + block_k) + s * heads * d * 4 + rows * s * 3 // 2
            + 5 * tile + mask)


def _head_groups(heads, d, dv=None):
    """How many heads a grid step may take. Its block is `g * d` columns of
    a (batch, seq, heads * d) array, and Mosaic takes a block whose last
    axis is whole 128-lane tiles or the whole axis: the divisors `g` of
    `heads`, up to `_MAX_HEADS`, with `g * d` a multiple of 128 (2, 4, 6,
    12 of BERT's twelve heads of 64; any at d = 128) or `g` every head (3
    heads of 64 go as one block of the full 192). A head count that leaves
    none of these (17 heads of 64) goes whole. With `dv` (v and o of another
    head width than q and k) the blocks of BOTH arrays are whole tiles: heads
    of 192 / 128 go in twos (384 and 256 lanes)."""
    dv = d if dv is None else dv
    groups = [g for g in range(1, min(heads, _MAX_HEADS) + 1)
              if heads % g == 0 and (
                  ((g * d) % _LANES == 0 and (g * dv) % _LANES == 0)
                  or g == heads)]
    return groups or [heads]


def _kernels_of(s, block_q, block_k):
    """The kernels a call runs: ONE backward kernel makes dq, dk and dv from
    one rebuilt score tile either way. Where the whole sequence is one tile
    nothing is summed over blocks (`flash_bwd`); else a key block owns its
    dk and dv and dq is summed over the key blocks in VMEM
    (`flash_bwd_dqkv`)."""
    if block_q == block_k == s:
        return FLASH_FWD, FLASH_BWD
    return FLASH_FWD, FLASH_BWD_DQKV


def _picks(s, block_q, block_k):
    """Blocks a call may take, the largest tile first: of 512/256/128 those
    that divide `s` (the whole sequence below 128) on either side; a
    `block_q`/`block_k` the caller passed is kept."""
    def sizes(given):
        if given is not None:
            return [min(given, s)]
        return [b for b in (512, 256, 128) if s % b == 0] or [s]

    return sorted(((bq, bk) for bq in sizes(block_q) for bk in sizes(block_k)),
                  key=lambda p: -p[0] * p[1])


def _choose_tiles(s, d, dtype, causal, heads, block_q=None, block_k=None,
                  dv=None, window=None):
    """-> (block_q, block_k, {kernel: heads a grid step}) for the kernels
    the call will run (`_kernels_of`), from the call's shapes alone.

    `heads` is the head count: a grid step of a kernel takes `g` consecutive
    heads of one batch row, `g` one of `_head_groups`. A `block_q`/`block_k`
    the caller passed is kept.

    Blocks: the largest of `_picks`, for causal calls too: on the chip a
    512 x 512 tile that computes its masked half beat 256 x 256 tiles that
    skip more (PR 24). Heads: the fewest that give a step `_STEP_FLOPS` of
    work, kernel by kernel in a one-tile call (its forward at most
    `_ONE_TILE_FWD_LANES` of heads narrower than a lane tile). Blocks and
    heads give way, heads first, until the count fits `_VMEM_BUDGET` (a
    many-tile call stops at half the largest pick's side there), or, where
    no choice does, `_VMEM_BUDGET_ASKED` (the call then asks Mosaic for
    more: `_asking`); the floor is the smallest group and the smallest
    blocks. The count is `_vmem_bytes` of every kernel in a one-tile call.
    In a many-tile call it is the forward's, and the blocks are the
    forward's (and the XLA oracle's): `flash_bwd_dqkv` takes the forward's
    heads, or the most below them whose smallest blocks the most a call may
    ask for holds, and blocks of its own (`_bwd_blocks`). Alone on the chip
    (PR 46, ms a call, causal bf16) 4 x 32 heads of 64 at 8,192 keys, two a
    step, ran in 20.65 at the 512 x 512 tiles that count admits (11.7 MiB)
    and in 42.87 at 256 x 256, 1 x 32 in 5.16 and 10.71; 1 x 16 heads of 128
    in 2.51 at 512 x 512 and 6.50 at 512 x 128 (docs/KERNELS.md).
    `dv`: the head width of v and o where it is not q's and k's `d`.
    `window`: a query block of a window call meets `window + block_q` keys
    at most, and its step's work is counted so; what a step HOLDS is the
    call's without a window, since k and v still lie whole in VMEM (one copy
    a head group, fetched once: the loop's bounds, not the blocks, are what
    the window cuts)."""
    itemsize = jnp.dtype(dtype).itemsize
    dv = d if dv is None else dv
    picks = _picks(s, block_q, block_k)
    groups = _head_groups(heads, d, dv)

    def many_tiles(bq, bk, group):
        below = [g for g in groups if g <= group]
        return bq, bk, {FLASH_FWD: group, FLASH_BWD_DQKV: next(
            (g for g in reversed(below) if _vmem_bytes(
                s, d, itemsize, *picks[-1], g, FLASH_BWD_DQKV, dv)
             <= _VMEM_BUDGETS[-1]), below[0])}

    for budget, (bq, bk) in itertools.product(
            (_VMEM_BUDGET, _VMEM_BUDGET_ASKED), picks):
        kernels = _kernels_of(s, bq, bk)
        # the keys a query block meets: half of them under the diagonal, or
        # the window's and the block's own where that is fewer
        keys = s * (0.5 if causal else 1.0)
        if window is not None:
            keys = min(keys, window + bq)
        step = 2.0 * bq * keys * (d + dv)
        want = next((g for g in groups if g * step >= _STEP_FLOPS),
                    groups[-1])
        if FLASH_BWD not in kernels:
            if budget == _VMEM_BUDGET and 2 * min(bq, bk) < min(picks[0]):
                # a side a quarter of the largest pick's is not taken for
                # fitting unasked: the larger tile that asks is faster.
                # Alone on the chip (PR 46, ms a call, causal float32, 4 x 8
                # heads of 192 / 128 at 2,048 keys, two a step) 128 x 512
                # unasked ran in 0.903, 512 x 512 under 64 MiB asked in 0.574
                continue
            group = next((g for g in reversed(groups) if g <= want
                          and _vmem_bytes(s, d, itemsize, bq, bk, g,
                                          FLASH_FWD, dv) <= budget), None)
            if group is not None:
                return many_tiles(bq, bk, group)
            continue
        most = dict.fromkeys(kernels, want)
        if d % _LANES or dv % _LANES:
            most[FLASH_FWD] = min(want, max(_ONE_TILE_FWD_LANES // max(d, dv),
                                            groups[0]))
        chosen = {kernel: next((g for g in reversed(groups) if g <= limit
                                and _vmem_bytes(s, d, itemsize, bq, bk, g,
                                                kernel, dv) <= budget), None)
                  for kernel, limit in most.items()}
        if None not in chosen.values():
            return bq, bk, chosen
    bq, bk = picks[-1]
    kernels = _kernels_of(s, bq, bk)
    if FLASH_BWD in kernels:
        return bq, bk, dict.fromkeys(kernels, groups[0])
    return many_tiles(bq, bk, groups[0])


def _bwd_blocks(s, d, dtype, heads, block_q=None, block_k=None, dv=None):
    """-> (block_q, block_k) of `flash_bwd_dqkv` at `heads` heads a step: the
    largest of `_picks` that the most a call may ask for holds (the
    smallest where none does), whatever the forward's are. The kernel asks
    anyway, and alone on the chip (PR 41, ms a call, causal bf16) 512 x 512
    ran 4 x 32 heads of 64 at 8,192 positions in 37.1 where 256 x 256 took
    67.7 (512 x 256 44.4, 256 x 512 49.0), and 32 heads of 192 / 128 in 58.2
    against 61.2 / 64.7 / 69.9 for 512 x 256 / 256 x 512 / 256 x 256."""
    picks = _picks(s, block_q, block_k)
    return next((p for p in picks if _vmem_bytes(
        s, d, jnp.dtype(dtype).itemsize, *p, heads, FLASH_BWD_DQKV, dv)
        <= _VMEM_BUDGETS[-1]), picks[-1])


def _is_fused(qkv):
    """One (batch, seq, 3 * heads * d) array [q | k | v], as a fused
    projection writes it, and not three (batch, seq, heads * d) arrays."""
    return not isinstance(qkv, (tuple, list))


def _width(qkv):
    """heads * d, the columns of q (and of k)."""
    return qkv.shape[-1] // 3 if _is_fused(qkv) else qkv[0].shape[-1]


def _v_width(qkv):
    """heads * dv, the columns of v (and of o): q's in a fused array, which
    is cut in three alike; three arrays may bring a v of its own width."""
    return qkv.shape[-1] // 3 if _is_fused(qkv) else qkv[2].shape[-1]


def _three(qkv):
    """The three arrays, whichever form the caller gave."""
    return jnp.split(qkv, 3, axis=-1) if _is_fused(qkv) else tuple(qkv)


def _tiles_for(qkv, n_heads, causal, block_q, block_k, window=None):
    """-> (qkv, block_q, block_k, {kernel: heads a step}, d, dv): `d` the
    head width of q and k, `dv` of v and o (the same unless three arrays
    say otherwise). Blocks that do not divide the sequence (only ones the
    caller passed can) raise. A fused array whose head group is not whole
    lane tiles (three heads of 64: then every kernel takes every head)
    cannot be indexed by column block: it is cut in three here."""
    q = qkv if _is_fused(qkv) else qkv[0]
    s, d, dv = q.shape[1], _width(qkv) // n_heads, _v_width(qkv) // n_heads
    block_q, block_k, heads = _choose_tiles(s, d, q.dtype, causal, n_heads,
                                            block_q, block_k, dv, window)
    if s % block_q or s % block_k:
        raise ValueError(f"seq_len {s} must divide blocks ({block_q},{block_k})")
    if _is_fused(qkv) and (heads[FLASH_FWD] * d) % _LANES:
        qkv = _three(qkv)
    return qkv, block_q, block_k, heads, d, dv


def _vmem_limit(kernel, s, d, dv, dtype, block_q, block_k, heads, mask_w=0):
    """What the call asks of Mosaic (`vmem_limit_bytes`): the least of
    `_VMEM_LIMITS` whose budget holds the step's count (the most there is
    where none does), None where the count is within what Mosaic gives
    unasked."""
    count = _vmem_bytes(s, d, jnp.dtype(dtype).itemsize, block_q, block_k,
                        heads, kernel, dv, mask_w)
    if count <= _VMEM_BUDGET:
        return None
    return next((limit for limit, budget in zip(_VMEM_LIMITS, _VMEM_BUDGETS)
                 if count <= budget), _VMEM_LIMITS[-1])


def _asking(*call):
    """`pallas_call` keywords of a call of `_vmem_limit`'s arguments:
    nothing where it asks for nothing (a call that fits lowers to what it
    always did)."""
    limit = _vmem_limit(*call)
    if limit is None:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T on the MXU: operands as they come, f32 out."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b on the MXU: the contraction runs down both operands' rows."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _head_sums(x, d, pieces):
    """(n, heads * d) f32 -> (n, 128) f32: column `h` is the sum of head
    `h`'s d columns, every head of the block at once, on the MXU: x goes
    in as `pieces` bf16 terms that add up to it (two hold the product of
    two bf16 values exactly, three an f32 value) against a 0/1 matrix, so
    each pass is exact and the sum is f32's. (Summed head by head along the
    lanes of a slice, delta cost the kernel that made it 0.44 ms of 2.14 a
    call at BERT's shapes, and one matmul at "highest" precision as much;
    PR 26.)"""
    shape = (x.shape[1], _LANES)
    pick = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // d
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            ).astype(jnp.bfloat16)
    total = jnp.zeros((x.shape[0], _LANES), jnp.float32)
    for _ in range(pieces):
        piece = x.astype(jnp.bfloat16)
        total = total + _dot(piece, pick)
        x = x - piece.astype(jnp.float32)
    return total


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


def _causal_mask(s, q_start, k_start, keys_first=False, window=None):
    """Mask scores above the diagonal for one tile: (q rows, k columns), or
    (k rows, q columns) with `keys_first`; with `window`, the scores of keys
    at or before the query's position less `window` too."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos, k_pos = ((q_start + cols, k_start + rows) if keys_first
                    else (q_start + rows, k_start + cols))
    keep = q_pos >= k_pos
    if window is not None:
        keep &= k_pos > q_pos - window
    return jnp.where(keep, s, _NEG_INF)


def _causal_upper_kb(q_start, block_q, block_k):
    """First key block strictly above the diagonal, by CEIL division —
    flooring would drop the diagonal block whenever block_q < block_k
    (regression guard: test_flash_causal_uneven_blocks)."""
    return (q_start + block_q + block_k - 1) // block_k


def _window_lower_kb(q_start, window, block_k):
    """First key block that holds a key inside ANY row's window of the query
    block that starts at `q_start`: its first row's earliest key, q_start -
    window + 1."""
    first = q_start - window + 1
    first = max(first, 0) if isinstance(first, int) else jnp.maximum(first, 0)
    return first // block_k


def _window_upper_qb(k_start, block_k, window, block_q, q_blocks):
    """One past the last query block with a row that keeps a key of the key
    block that starts at `k_start`: its last key, k_start + block_k - 1, is
    kept up to the query `window` - 1 positions on."""
    last = (k_start + block_k + window - 2) // block_q + 1
    return (min(last, q_blocks) if isinstance(last, int)
            else jnp.minimum(last, q_blocks))


def window_bounds(s, window, block_q, block_k):
    """The tiles a causal call of `window` visits, by the bounds its kernels'
    loops run to: -> (forward [(first, one past the last key block) a query
    block], backward [(first, one past the last query block) a key block])
    as `flash_fwd` at (block_q, block_k) and `flash_bwd_dqkv` at the same
    walk them; `window` None: the causal call's."""
    q_blocks, key_blocks = s // block_q, s // block_k
    fwd = [(0 if window is None else
            _window_lower_kb(i * block_q, window, block_k),
            min(_causal_upper_kb(i * block_q, block_q, block_k), key_blocks))
           for i in range(q_blocks)]
    bwd = [(j * block_k // block_q, q_blocks if window is None else
            _window_upper_qb(j * block_k, block_k, window, block_q, q_blocks))
           for j in range(key_blocks)]
    return fwd, bwd


def _optional(kernel, n_before, *present):
    """pallas hands a kernel its refs in order, inputs then outputs; the key
    bias and the row mask are the inputs after the first `n_before`, each
    there or absent (`present`): the kernel gets None for an absent one."""
    def call(*refs):
        rest = iter(refs[n_before:])
        return kernel(*refs[:n_before],
                      *(next(rest) if there else None for there in present),
                      *rest)
    return kernel if all(present) else call


# ---------------------------------------------------------------------------
# A selection by query row. `keep` (batch, seq, seq) bool says which keys a
# query attends to. The kernels get it packed, one bit a pair: the key axis
# (for the forward kernel, whose tiles are queries x keys) or the query axis
# (for the backward kernels, whose tiles are keys x queries) is cut into P
# planes of W = seq / P positions, and bit p of word [row, c] is the pair
# (row, p * W + c). W is a multiple of every block a kernel may take (512,
# or the whole sequence below that), so a tile lies in ONE plane: its bits
# are a block of whole lanes, shifted by the plane and masked to one bit.
# At 16,384 positions P is 32 and the pair of arrays is 64 MiB a sequence,
# where a float32 (seq, seq) array is 1 GiB.
# ---------------------------------------------------------------------------

def mask_planes(s):
    """Bit planes of a packed row mask over `s` positions: the most, up to
    the 32 of a word, whose planes are whole blocks of 512 (of `s` below
    that: one plane, a word a pair)."""
    planes = 32
    while planes > 1 and (s % planes or (s // planes) % min(512, s)):
        planes //= 2
    return planes


def pack_bits(keep, planes):
    """(..., n) bool -> (..., n / planes) int32: bit p of word c is position
    p * (n / planes) + c of the last axis."""
    *lead, n = keep.shape
    bit = jnp.arange(planes, dtype=jnp.int32)[:, None]
    bits = keep.reshape(*lead, planes, n // planes).astype(jnp.int32)
    return jnp.sum(bits << bit, axis=-2)        # distinct bits: their OR


def unpack_bits(packed, planes):
    """The inverse of ``pack_bits``."""
    bit = jnp.arange(planes, dtype=jnp.int32)[:, None]
    return ((packed[..., None, :] >> bit) & 1).reshape(
        *packed.shape[:-1], planes * packed.shape[-1]) != 0


def pack_row_mask(keep):
    """`keep` (batch, seq, seq) bool, [query, key] -> (by_query, by_key),
    each (batch, seq, seq / P) int32: `by_query[b, t, c]` holds the keys
    p * W + c of query t, `by_key[b, s, c]` the queries p * W + c of key s."""
    planes = mask_planes(keep.shape[-1])
    return pack_bits(keep, planes), pack_bits(keep.swapaxes(1, 2), planes)


def unpack_row_mask(packed):
    """One array of a `pack_row_mask` pair -> the (batch, rows, seq) bool it
    packs (the XLA oracle's form)."""
    return unpack_bits(packed, packed.shape[1] // packed.shape[2])


def _kept(mask_ref, start, n):
    """The tile of a packed row mask block (rows, W) whose `n` columns start
    at position `start` of the packed axis -> (rows, n) bool."""
    w = mask_ref.shape[-1]
    if isinstance(start, int):
        plane, cols = start // w, pl.ds(start % w, n)
    else:
        plane, cols = start // w, pl.ds(pl.multiple_of(start % w, n), n)
    return (mask_ref[:, cols] >> plane) & 1 != 0


# ---------------------------------------------------------------------------
# The tile program, common to the three kernels. q, k, v, o and their
# gradients lie in HBM as the projections write and read them: (batch, seq,
# heads * d), a head's d columns side by side on the lane axis (v, o and
# their gradients: heads * dv, `dv` = `d` but for latent attention, whose
# q . k is 192 wide and whose p . v is 128: no product runs on a padded
# column). A grid step owns one batch row, `heads` consecutive heads (a
# block of heads * d columns, and one of heads * dv, whole lane tiles both)
# and one block of the sequence, and loops over the blocks of the other
# side; a head is a static slice of the lanes of either block.
# - MXU operands go in as the caller gave them (bf16 from a bf16 model, f32
#   from an f32 caller), every dot accumulates in f32, and everything else
#   is f32: scores, bias add, m, l, exp, lse, delta, the accumulators.
# - per-row statistics (lse, delta) and the key bias live in HBM with the
#   sequence on the lane axis, (batch*heads, 1, s) and (batch, 1, s). Where
#   a kernel needs one as a column it transposes it once a step, never in
#   the loop; `flash_bwd_dqkv` and `flash_bwd` work on the transposed score
#   tile (keys down the sublanes) so that lse and delta broadcast as rows
#   and P^T, dS^T feed dV and dK without a transposed matmul.
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, o_ref, lse_ref, *,
                scale, causal, block_k, d, dv, window=None):
    # grid: (batch, head groups, q blocks); one q block, the whole k and v
    block_q = q_ref.shape[0]
    q_start = pl.program_id(2) * block_q
    # causal: skip key blocks entirely above the diagonal. A lone key block
    # never is: one pass, written out, and not a loop to a bound read at
    # run time (causal, four heads a step, ms a call on the chip, PR 33:
    # 512 rows of 12 x 128 x 128 x 64 3.71 -> 1.85, 32 rows of 16 x 512 x
    # 512 x 64 0.80 -> 0.54)
    key_blocks = k_ref.shape[0] // block_k
    upper = (_causal_upper_kb(q_start, block_q, block_k)
             if causal and key_blocks > 1 else key_blocks)
    # a window: nor the key blocks wholly before every row's window
    lower = (_window_lower_kb(q_start, window, block_k)
             if window is not None and key_blocks > 1 else 0)

    for g in range(q_ref.shape[1] // d):
        lanes, v_lanes = _block(g, d), _block(g, dv)
        q = q_ref[:, lanes]                           # (block_q, d)

        def body(kj, carry):
            acc, m_prev, l_prev = carry
            keys = _block(kj, block_k)
            v_blk = v_ref[keys, v_lanes]
            s = _dot_nt(q, k_ref[keys, lanes]) * scale  # (block_q, block_k)
            if bias_ref is not None:
                s = s + bias_ref[0, :, keys]
            if mask_ref is not None:    # a causal selection: the mask says
                s = jnp.where(_kept(mask_ref, kj * block_k, block_k), s,
                              _NEG_INF)
            elif causal:
                s = _causal_mask(s, q_start, kj * block_k, window=window)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk)
            return acc, m_new, l_new

        acc, m, l = _loop(lower, upper, body, (
            jnp.zeros((block_q, dv), jnp.float32),
            jnp.full((block_q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        o_ref[:, v_lanes] = (acc / l).astype(o_ref.dtype)
        lse_ref[g] = _col_to_row(m + jnp.log(l))


def _specs(qkv, heads, d, n_heads, block, dv):
    """BlockSpec makers for a grid (batch, head groups, blocks of the
    sequence), each for one block of the sequence or, with `whole`, all of
    it: `cols(name)` the columns of head group `j`, `heads * d` of them in
    q, k or "dq", `heads * dv` in v or "o" (column block `j`, `groups + j`,
    `2 * groups + j` of a fused array for q, k, v; "dq" and "o" are arrays
    of their own: dq; o, dO, dv); `stats()` the group's rows of lse or
    delta; `bias()` the batch row's key bias; `mask(w)` the block's rows of
    a packed row mask of `w` words a row."""
    groups = n_heads // heads
    s = (qkv if _is_fused(qkv) else qkv[0]).shape[1]
    first = dict(q=0, k=0, v=0, o=0, dq=0)
    if _is_fused(qkv):
        first.update(k=groups, v=2 * groups)
    width = dict(q=d, k=d, dq=d, v=dv, o=dv)

    def along(whole):
        return (s, lambda i: 0) if whole else (block, lambda i: i)

    def cols(name, whole=False):
        n, at = along(whole)
        return pl.BlockSpec((None, n, heads * width[name]),
                            lambda b, j, i: (b, at(i), first[name] + j))

    def stats(whole=False):
        n, at = along(whole)
        return pl.BlockSpec((heads, 1, n),
                            lambda b, j, i: (b * groups + j, 0, at(i)))

    def bias(whole=False):
        n, at = along(whole)
        return pl.BlockSpec((1, 1, n), lambda b, j, i: (b, 0, at(i)))

    def mask(w):
        return pl.BlockSpec((None, block, w), lambda b, j, i: (b, i, 0))

    return cols, stats, bias, mask


def _windowed(window):
    """A kernel's keyword of a call's window: none without one (a call
    without a window traces the kernel it always did)."""
    return {} if window is None else {"window": window}


def _operands(qkv):
    """q, k and v as the kernels take them: the same array three times
    where they are one, under three index maps (`_specs`)."""
    return [qkv] * 3 if _is_fused(qkv) else list(qkv)


def _mask_of(row_mask, side):
    """-> ([the array of a `pack_row_mask` pair a kernel reads: 0 by query,
    1 by key], its words a row), ([], 0) without a mask."""
    if row_mask is None:
        return [], 0
    return [row_mask[side]], row_mask[side].shape[-1]


def _bias_rows(k_bias):
    """(b, s) key bias -> (b, 1, s) f32 rows: one a batch row, whatever the
    head (the index map sends a step to its batch row)."""
    return k_bias.astype(jnp.float32)[:, None, :]


def _fwd_pallas(qkv, n_heads, k_bias, scale, causal, block_q, block_k,
                interpret, row_mask=None, window=None):
    """-> o (batch, seq, heads * d), lse (batch * heads, 1, seq)."""
    qkv, block_q, block_k, heads, d, dv = _tiles_for(
        qkv, n_heads, causal, block_q, block_k, window)
    heads = heads[FLASH_FWD]
    ops = _operands(qkv)
    b, s, _ = ops[0].shape
    use_bias = k_bias is not None
    bias = [_bias_rows(k_bias)] if use_bias else []
    masks, mask_w = _mask_of(row_mask, 0)
    cols, stats, bias_spec, mask_spec = _specs(qkv, heads, d, n_heads,
                                               block_q, dv)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_k=block_k, d=d, dv=dv,
                             **_windowed(window))
    return pl.pallas_call(
        _optional(kern, 3, use_bias, bool(masks)),
        grid=(b, n_heads // heads, s // block_q),
        in_specs=[cols("q"), cols("k", True), cols("v", True)]
        + [bias_spec(True)] * use_bias + [mask_spec(mask_w)] * len(masks),
        out_specs=[cols("o"), stats()],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, n_heads * dv), ops[0].dtype),
            jax.ShapeDtypeStruct((b * n_heads, 1, s), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
        **_asking(FLASH_FWD, s, d, dv, ops[0].dtype, block_q, block_k, heads,
                  *[mask_w] * len(masks)),
    )(*ops, *bias, *masks)


# ---------------------------------------------------------------------------
# backward Pallas kernels, ONE a call, which makes dq, dk and dv from one
# rebuilt tile (five matmuls, one exp pass): each tile recomputes its
# probability block from (q, k, lse), so nothing (S, S)-shaped ever exists.
# `flash_bwd_dqkv`, for a sequence of several tiles, gives a grid step a key
# block, which owns its dk and dv and walks the query blocks; dq is summed
# over the key blocks in an f32 scratch of the whole sequence that stays in
# VMEM along that (last, sequential) grid axis and is written out once, so no
# partial dq goes through HBM. `flash_bwd` is the same tile where the
# sequence is one: nothing to walk, nothing to sum. delta = rowsum(dO * O)
# over a head's columns is made in the kernel, for the whole sequence before
# the first tile (`flash_bwd_dqkv`: at the first key block, into a scratch
# of rows like lse; o lies whole in VMEM for it): left to XLA, the sum over a
# (batch, seq, heads, d) view cost a relayout of the whole f32 product.
# `scale` multiplies the f32 scores on the way in and the f32 accumulators
# of dq and dk on the way out (d x block elements, not block x block).
# ---------------------------------------------------------------------------

def _bwd_dqkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, bias_ref,
                     mask_ref, dq_ref, dv_ref, dk_ref, dq_acc, delta_ref, *,
                     scale, causal, block_q, d, dv, window=None):
    # grid: (batch, head groups, k blocks), the last in order; owns one k/v
    # block, loops over q blocks. Tiles are (block_k, block_q): S^T, P^T,
    # dP^T, dS^T, and dQ alone contracts over the rows of dS^T.
    block_k = k_ref.shape[0]
    kj = pl.program_id(2)
    k_start = kj * block_k
    q_blocks = q_ref.shape[0] // block_q
    # causal: q blocks strictly before this k block contribute nothing
    lower = (k_start // block_q) if causal else 0
    # a window: nor those whose every row is past this k block's last key
    upper = (q_blocks if window is None else _window_upper_qb(
        k_start, block_k, window, block_q, q_blocks))
    heads = k_ref.shape[1] // d
    # this kernel owns ONE k block: its bias column is constant
    bias = None if bias_ref is None else _row_to_col(bias_ref[0])

    @pl.when(kj == 0)
    def _first_key_block():
        def body(qi, carry):
            rows = _block(qi, block_q)
            dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]),
                                        jnp.float32)
            deltas = _head_sums(
                do_ref[rows, :].astype(jnp.float32)
                * o_ref[rows, :].astype(jnp.float32), dv,
                pieces=2 if do_ref.dtype == jnp.bfloat16 else 3)
            delta_ref[:, 0, rows] = deltas.T[:heads]
            return carry
        _loop(0, q_blocks, body, 0)

    for g in range(heads):
        lanes, v_lanes = _block(g, d), _block(g, dv)
        k_blk = k_ref[:, lanes]                       # (block_k, d)
        v_blk = v_ref[:, v_lanes]

        def body(qi, carry):
            dk, d_v = carry
            rows = _block(qi, block_q)
            q = q_ref[rows, lanes]
            do = do_ref[rows, v_lanes]
            st = _dot_nt(k_blk, q) * scale
            if bias is not None:
                st = st + bias
            if mask_ref is not None:
                st = jnp.where(_kept(mask_ref, qi * block_q, block_q), st,
                               _NEG_INF)
            elif causal:
                st = _causal_mask(st, qi * block_q, k_start, keys_first=True,
                                  window=window)
            pt = jnp.exp(st - lse_ref[g, :, rows])    # (block_k, block_q)
            d_v = d_v + _dot(pt.astype(do.dtype), do)
            dpt = _dot_nt(v_blk, do)
            dst = (pt * (dpt - delta_ref[g, :, rows])).astype(q.dtype)
            dk = dk + _dot(dst, q)
            dq_acc[rows, lanes] += _dot_tn(dst, k_blk)
            return dk, d_v

        zeros = jnp.zeros((block_k, d), jnp.float32)
        dk, d_v = _loop(lower, upper, body, (
            zeros, zeros if dv == d else jnp.zeros((block_k, dv),
                                                   jnp.float32)))
        dk_ref[:, lanes] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[:, v_lanes] = d_v.astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _last_key_block():
        def body(qi, carry):
            rows = _block(qi, block_q)
            dq_ref[rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)
            return carry
        _loop(0, q_blocks, body, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, bias_ref,
                mask_ref, dq_ref, dv_ref, dk_ref, *, scale, causal, d, dv,
                window=None):
    # grid: (batch, head groups, 1); the whole sequence is ONE tile, so
    # nothing is summed over blocks and one rebuilt tile serves dq, dk and
    # dv. The tile is (keys, queries) as in `flash_bwd_dqkv`: lse and delta
    # broadcast as rows, P^T and dS^T feed dV and dK as they stand, and dQ
    # alone contracts over the rows of dS^T.
    heads = q_ref.shape[1] // d
    deltas = _head_sums(
        do_ref[...].astype(jnp.float32) * o_ref[...].astype(jnp.float32), dv,
        pieces=2 if do_ref.dtype == jnp.bfloat16 else 3).T   # (128, seq)
    bias = None if bias_ref is None else _row_to_col(bias_ref[0])

    for g in range(heads):
        lanes, v_lanes = _block(g, d), _block(g, dv)
        q = q_ref[:, lanes]                           # (seq, d)
        k = k_ref[:, lanes]
        do = do_ref[:, v_lanes]
        st = _dot_nt(k, q) * scale                    # (keys, queries)
        if bias is not None:
            st = st + bias
        if mask_ref is not None:
            st = jnp.where(_kept(mask_ref, 0, st.shape[1]), st, _NEG_INF)
        elif causal:
            st = _causal_mask(st, 0, 0, keys_first=True, window=window)
        pt = jnp.exp(st - lse_ref[g])
        dv_ref[:, v_lanes] = _dot(pt.astype(do.dtype),
                                  do).astype(dv_ref.dtype)
        dpt = _dot_nt(v_ref[:, v_lanes], do)
        dst = (pt * (dpt - deltas[g:g + 1])).astype(q.dtype)
        dk_ref[:, lanes] = (_dot(dst, q) * scale).astype(dk_ref.dtype)
        dq_ref[:, lanes] = (_dot_tn(dst, k) * scale).astype(dq_ref.dtype)


def _bwd_pallas(res, do, *, n_heads, scale, causal, block_q, block_k,
                interpret, window=None):
    """-> dq, dk, dv in the form qkv came in: three arrays where it was
    three; where it was one, one (batch, seq, 3 * heads * d) array, which
    the kernel makes and writes the k columns of, and dq and dv are set
    into: two passes over a third of it each, that transpose nothing. (The
    first result of either kernel stays an array of q's shape, which is o's
    too unless v brought a width of its own: the benchmark's reader takes a
    call's FLOPs from it.) `flash_bwd` where the sequence is one tile,
    `flash_bwd_dqkv` where it is cut (`_kernels_of`), each with its own
    heads a step."""
    given, o, lse, k_bias, *row_mask = res
    masks, mask_w = _mask_of(row_mask[0] if row_mask else None, 1)
    given_blocks = block_q, block_k
    qkv, block_q, block_k, heads, d, dv = _tiles_for(
        given, n_heads, causal, block_q, block_k, window)
    b, s, width = o.shape
    use_bias = k_bias is not None
    bias = [_bias_rows(k_bias)] if use_bias else []
    grad = jax.ShapeDtypeStruct(o.shape, o.dtype)           # dv; o's shape
    grad_q = jax.ShapeDtypeStruct((b, s, n_heads * d), o.dtype)
    # dk goes where k came from: the same columns of an array of qkv's
    # shape, or an array of its own
    dk_shape = (jax.ShapeDtypeStruct(qkv.shape, o.dtype) if _is_fused(qkv)
                else grad_q)
    kernel = _kernels_of(s, block_q, block_k)[1]
    group = heads[kernel]
    if kernel == FLASH_BWD_DQKV:
        block_q, block_k = _bwd_blocks(s, d, o.dtype, group, *given_blocks,
                                       dv)
    call = (kernel, s, d, dv, o.dtype, block_q, block_k, group,
            *[mask_w] * len(masks))

    if kernel == FLASH_BWD:
        cols, stats, bias_spec, mask_spec = _specs(qkv, group, d, n_heads, s,
                                                   dv)
        kern = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                                 d=d, dv=dv, **_windowed(window))
        dq, d_v, dk = pl.pallas_call(
            _optional(kern, 6, use_bias, bool(masks)),
            grid=(b, n_heads // group, 1),
            in_specs=[cols("q"), cols("k"), cols("v"), cols("o"), cols("o"),
                      stats()] + [bias_spec()] * use_bias
            + [mask_spec(mask_w)] * len(masks),
            out_specs=[cols("dq"), cols("o"), cols("k")],
            out_shape=[grad_q, grad, dk_shape],
            interpret=interpret,
            name=FLASH_BWD,
            **_asking(*call),
        )(*_operands(qkv), do, o, lse, *bias, *masks)
    else:
        cols, stats, bias_spec, mask_spec = _specs(qkv, group, d, n_heads,
                                                   block_k, dv)
        kern = functools.partial(_bwd_dqkv_kernel, scale=scale, causal=causal,
                                 block_q=block_q, d=d, dv=dv,
                                 **_windowed(window))
        dq, d_v, dk = pl.pallas_call(
            _optional(kern, 6, use_bias, bool(masks)),
            grid=(b, n_heads // group, s // block_k),
            in_specs=[cols("q", True), cols("k"), cols("v"), cols("o", True),
                      cols("o", True), stats(True)] + [bias_spec()] * use_bias
            + [mask_spec(mask_w)] * len(masks),
            out_specs=[cols("dq", True), cols("o"), cols("k")],
            out_shape=[grad_q, grad, dk_shape],
            scratch_shapes=[pltpu.VMEM((s, group * d), jnp.float32),
                            pltpu.VMEM((group, 1, s), jnp.float32)],
            interpret=interpret,
            name=FLASH_BWD_DQKV,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(*call)),
        )(*_operands(qkv), do, o, lse, *bias, *masks)

    if _is_fused(qkv):
        out = jax.lax.dynamic_update_slice_in_dim(dk, dq, 0, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(out, d_v, 2 * width, axis=2)
    if _is_fused(given):    # cut in three by `_tiles_for`: three heads of 64
        return jnp.concatenate([dq, dk, d_v], axis=-1)
    return dq, dk, d_v


# ---------------------------------------------------------------------------
# blockwise backward (XLA): flash-style recomputation, no (S, S) tensor
# (off-TPU fallback and the Pallas backward's numerical oracle)
# ---------------------------------------------------------------------------

def _bwd_blockwise(res, do, *, n_heads, scale, causal, block_k, window=None):
    qkv, o, lse, k_bias, *row_mask = res
    keep = unpack_row_mask(row_mask[0][0]) if row_mask else None
    b, s, _ = o.shape
    nkb = s // block_k

    def heads(x):
        return x.astype(jnp.float32).reshape(b, s, n_heads, -1)

    q_f, k_f, v_f = (heads(x) for x in _three(qkv))
    do_f = heads(do)
    lse = lse.reshape(b, n_heads, s)
    # delta_i = sum_j dO_ij O_ij  (rowwise), standard flash backward
    delta = jnp.einsum("bqhd,bqhd->bhq", do_f, heads(o))

    q_pos = jnp.arange(s)

    def one_kblock(kj):
        ks = kj * block_k
        k_blk = jax.lax.dynamic_slice_in_dim(k_f, ks, block_k, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_f, ks, block_k, 1)
        s_blk = jnp.einsum("bqhd,bkhd->bhqk", q_f, k_blk) * scale
        if k_bias is not None:
            kb = jax.lax.dynamic_slice_in_dim(
                k_bias.astype(jnp.float32), ks, block_k, 1)
            s_blk = s_blk + kb[:, None, None, :]
        if keep is not None:
            s_blk = jnp.where(jax.lax.dynamic_slice_in_dim(
                keep, ks, block_k, 2)[:, None], s_blk, _NEG_INF)
        elif causal:
            k_pos = (ks + jnp.arange(block_k))[None, :]
            mask = q_pos[:, None] >= k_pos
            if window is not None:
                mask &= k_pos > q_pos[:, None] - window
            s_blk = jnp.where(mask, s_blk, _NEG_INF)
        p = jnp.exp(s_blk - lse[..., None])                    # (b,h,s,bk)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, do_f)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do_f, v_blk)
        ds = p * (dp - delta[..., None]) * scale
        dq_part = jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, q_f)
        return dq_part, dk_blk, dv_blk

    def scan_body(dq_acc, kj):
        dq_part, dk_blk, dv_blk = one_kblock(kj)
        return dq_acc + dq_part, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        scan_body, jnp.zeros(q_f.shape, jnp.float32), jnp.arange(nkb))
    grads = [dq] + [jnp.moveaxis(x, 0, 1) for x in (dk_blocks, dv_blocks)]
    grads = [x.reshape(b, s, -1).astype(o.dtype) for x in grads]
    if _is_fused(qkv):
        return jnp.concatenate(grads, axis=-1)
    return tuple(grads)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 3, 4, 5, 6, 7))
def _flash(qkv, n_heads, k_bias, causal, scale, block_q, block_k, window):
    out, _ = _flash_fwd(qkv, n_heads, k_bias, causal, scale, block_q,
                        block_k, window)
    return out


def flash_attention_btd(qkv, n_heads, causal=True, scale=None, block_q=None,
                        block_k=None, k_bias=None, row_mask=None, window=None):
    """Fused attention in the projections' own layout: the kernels' entry.

    ``qkv``: one (batch, seq, 3 * heads * head_dim) array, [q | k | v] along
    the columns as a fused projection writes it (the kernels read the three
    out of it in place), or a tuple of three (batch, seq, heads * head_dim)
    arrays where q and k are touched in between; v of the three may have a
    head width of its own, which is o's. -> (batch, seq, heads * v's
    head_dim), what the output projection reads; the gradient comes back in
    the form ``qkv`` had.

    The matmuls run in the dtype of q/k/v with f32 accumulation; the softmax
    is f32 whatever that dtype. ``block_q``/``block_k`` left at None are
    chosen from the shapes (`_choose_tiles`).

    ``k_bias``: optional (batch, seq) float added to every score column —
    the key-padding mask form (0 valid / -1e9 padded). Non-trainable: its
    cotangent is zero.

    ``row_mask``: optional ``pack_row_mask`` pair, the keys each query keeps
    (a selection inside the causal triangle: pass ``causal=True`` with it,
    which skips the tiles above the diagonal). Such a call returns (o, lse):
    ``lse`` (batch * heads, 1, seq) float32, the logarithm of each row's
    softmax denominator over its kept keys, for a caller that rebuilds the
    probabilities; it carries no gradient.

    ``window``: optional int W, a causal call's sliding window: query t keeps
    the keys t - W < s <= t (its own among them). The kernels' loops stop at
    the window's edge: no tile that lies wholly outside every row's window is
    computed (`window_bounds`)."""
    if window is not None and (not causal or row_mask is not None
                               or window < 1):
        raise ValueError(
            f"window={window}: a positive count of keys of a causal call "
            "without a row mask")
    if not _is_fused(qkv):
        qkv = tuple(qkv)
    if row_mask is not None:
        return _flash_rows(qkv, n_heads, tuple(row_mask), causal, scale,
                           block_q, block_k)
    return _flash(qkv, n_heads, k_bias, causal, scale, block_q, block_k,
                  window)


def flash_attention(q, k, v, causal=True, scale=None, block_q=None,
                    block_k=None, k_bias=None):
    """`flash_attention_btd` for q/k/v of (batch, heads, seq, head_dim):
    transposed in XLA on the way in and out, so a caller that holds that
    layout pays four passes a call that the trunk does not."""
    b, h, s, _ = q.shape
    out = flash_attention_btd(
        tuple(x.transpose(0, 2, 1, 3).reshape(b, s, -1) for x in (q, k, v)),
        h, causal, scale, block_q, block_k, k_bias)
    return out.reshape(b, s, h, -1).transpose(0, 2, 1, 3)


def _scale(qkv, n_heads, scale):
    if scale is not None:
        return scale
    return 1.0 / math.sqrt(_width(qkv) // n_heads)


def _flash_fwd(qkv, n_heads, k_bias, causal, scale, block_q, block_k,
               window=None):
    out, lse = _fwd_pallas(qkv, n_heads, k_bias, _scale(qkv, n_heads, scale),
                           causal, block_q, block_k, interpret=not _on_tpu(),
                           window=window)
    # named HERE so that the very values a `jax.checkpoint` policy saves are
    # the backward kernels' residuals: a name on the caller's side of the
    # custom_vjp would still re-run this kernel for them
    out = checkpoint_name(out, REMAT_ATTN_O)
    lse = checkpoint_name(lse, REMAT_ATTN_LSE)
    return out, (qkv, out, lse, k_bias)


def _flash_bwd(n_heads, causal, scale, block_q, block_k, window, res, do):
    """`res`: (qkv, o, lse, k_bias) and, under a row mask, the pair."""
    qkv, k_bias = res[0], res[3]
    kw = dict(n_heads=n_heads, scale=_scale(qkv, n_heads, scale),
              causal=causal, window=window)
    if _on_tpu():
        grads = _bwd_pallas(res, do, block_q=block_q, block_k=block_k,
                            interpret=False, **kw)
    else:
        block_k = _tiles_for(qkv, n_heads, causal, block_q, block_k,
                             window)[2]
        grads = _bwd_blockwise(res, do, block_k=block_k, **kw)
    dbias = None if k_bias is None else jnp.zeros_like(k_bias)
    return grads, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 3, 4, 5, 6))
def _flash_rows(qkv, n_heads, row_mask, causal, scale, block_q, block_k):
    """`_flash` under a row mask -> (o, lse). A `custom_vjp` of its own: the
    calls without a mask keep theirs, and with it their traced programs."""
    return _flash_rows_fwd(qkv, n_heads, row_mask, causal, scale, block_q,
                           block_k)[0]


def _flash_rows_fwd(qkv, n_heads, row_mask, causal, scale, block_q, block_k):
    out, lse = _fwd_pallas(qkv, n_heads, None, _scale(qkv, n_heads, scale),
                           causal, block_q, block_k, interpret=not _on_tpu(),
                           row_mask=row_mask)
    out = checkpoint_name(out, REMAT_ATTN_O)
    lse = checkpoint_name(lse, REMAT_ATTN_LSE)
    return (out, lse), (qkv, out, lse, None, row_mask)


def _flash_rows_bwd(n_heads, causal, scale, block_q, block_k, res, cts):
    grads, _ = _flash_bwd(n_heads, causal, scale, block_q, block_k, None,
                          res, cts[0])  # lse's cotangent: it has no gradient
    return grads, jax.tree.map(
        lambda m: np.zeros(m.shape, jax.dtypes.float0), res[4])


_flash_rows.defvjp(_flash_rows_fwd, _flash_rows_bwd)


def mha_reference(q, k, v, causal=True, scale=None, k_bias=None, keep=None,
                  window=None):
    """Unfused reference (the reference framework's BatchMatMul+Softmax
    attention) — used as the numerical oracle in tests. ``keep`` (batch,
    seq, seq) bool: the keys each query keeps, every head alike. ``window``:
    query t keeps the keys t - window < s <= t."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if k_bias is not None:
        s = s + k_bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        n = q.shape[2]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask, s, _NEG_INF)
    if window is not None:
        n = q.shape[2]
        s = jnp.where(jnp.triu(jnp.ones((n, n), bool), 1 - window), s,
                      _NEG_INF)
    if keep is not None:
        s = jnp.where(keep[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
