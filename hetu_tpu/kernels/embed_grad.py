"""Fused sparse embedding gradient: sort/unique + segment-sum into
IndexedSlices-style ``(rows, grads)`` pairs (docs/KERNELS.md).

The pre-hetukern ``embedding_lookup_gradient_op`` scatters the batch's
row gradients into a ``(vocab, dim)`` zeros table
(``jnp.zeros(shape).at[idx].add(vec)``) — for a CTR table that is a
table-sized HBM intermediate written per step to carry a few thousand
live rows (the reference pays the same shape with a hand-written
``EmbeddingLookup.cu`` scatter kernel). This module computes the compact
form instead:

    rows, grads, count = embed_grad_rows(vec, idx, vocab)

``rows`` is ``(n,)`` int32 — the sorted unique row ids, padded with the
``vocab`` sentinel past ``count``; ``grads`` is ``(n, dim)`` with the
per-unique-row gradient sums in the first ``count`` slots and zeros
after. The pair feeds the PS push path directly (rows leave the device
anyway) and reconstructs the dense table gradient with ONE
unique-index scatter when a consumer genuinely needs table shape.

Split of labor: the sort + segment-id prep is XLA either way (XLA's sort
is already good; a Pallas sort would be re-deriving it); the kernel tier
covers the segment-sum — a blocked mask-matmul (``out[k] = Σ_j
[seg_j = k]·g_j``) whose per-block compare-and-MAC rides the MXU with
row blocks streamed through VMEM, versus the fallback's
``jax.ops.segment_sum`` scatter-adds. Note the jax.grad path through
``embedding_lookup_op`` cannot use the compact form — a vjp cotangent
must match the primal's (table) shape — so this tier serves the explicit
gradient op and the PS push route, and the dense reconstruction keeps
the scatter unique-rows-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry

# the kernel's name in the device trace (the pallas_call's `name=`) and in
# the registry: one constant a call site (docs/KERNELS.md)
FUSED_EMBED_GRAD = "fused_embed_grad"

# MXU-friendly tile for the mask-matmul; eligibility asks the padded row
# count to divide it and the trailing dim to be lane-aligned. Tiling and
# VMEM-budget constants are the registry's shared ones: the kernel holds
# the full (n, d) sorted-grad array + (n,) seg ids + one (BLOCK_ROWS, d)
# output block in VMEM per grid step, and oversized CTR batches must
# fall back under auto instead of dying in a Mosaic VMEM-exhausted
# compile.
BLOCK_ROWS = 128
_LANE = registry.LANE
VMEM_BUDGET_BYTES = registry.VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# shared prep (XLA both paths): sort, segment ids, unique-row vector
# ---------------------------------------------------------------------------

def _prep(vec, idx, vocab: int):
    """Flatten + stable-sort the row gradients by row id.

    Returns ``(sorted_grads (n, d) f32, seg (n,) i32, rows (n,) i32,
    count () i32)`` — ``seg`` maps each sorted slot to its unique-row
    rank, ``rows[k]`` is unique row k's id (``vocab`` sentinel past
    ``count``)."""
    d = vec.shape[-1]
    flat_idx = idx.astype(jnp.int32).reshape(-1)
    flat_vec = vec.reshape(-1, d).astype(jnp.float32)
    order = jnp.argsort(flat_idx)   # jnp.argsort is stable by default
    sidx = flat_idx[order]
    sv = flat_vec[order]
    n = sidx.shape[0]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sidx[1:] != sidx[:-1]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1          # (n,) 0..count-1
    count = seg[-1] + 1
    rows = jnp.full((n,), vocab, jnp.int32).at[seg].set(sidx)
    return sv, seg, rows, count


# ---------------------------------------------------------------------------
# segment-sum implementations (the registered kernel)
# ---------------------------------------------------------------------------

def _segsum_xla(sv, seg):
    """The fallback: XLA's sorted-scatter segment sum."""
    return jax.ops.segment_sum(sv, seg, num_segments=sv.shape[0])


def _segsum_kernel(seg_ref, g_ref, o_ref, *, block_rows, n):
    """One output row-block: mask-matmul segment MAC. ``out[k] = Σ_j
    [seg_j = k] g_j`` — the (block, block) compare mask against a g block
    is one MXU dot; the fori_loop streams g blocks through VMEM."""
    i = pl.program_id(0)
    k0 = i * block_rows

    def body(jb, acc):
        seg = seg_ref[pl.ds(jb, 1), :]                  # (1, block_rows)
        g = g_ref[pl.ds(jb * block_rows, block_rows), :]
        krow = k0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, block_rows), 0)
        m = (krow == seg).astype(jnp.float32)
        # HIGHEST: at default precision the MXU rounds the f32 grads to
        # bf16 (3e-3 relative on the chip) — the sums must stay f32-exact
        # like the segment_sum fallback's adds
        return acc + jax.lax.dot(m, g, precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((block_rows, g_ref.shape[1]), jnp.float32)
    o_ref[:] = jax.lax.fori_loop(0, n // block_rows, body, acc0)


def _segsum_pallas(sv, seg):
    n, d = sv.shape
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block_rows=BLOCK_ROWS, n=n),
        grid=(n // BLOCK_ROWS,),
        in_specs=[
            # ids ride as (n / 128, 128): one row per g block. A 1-D VMEM
            # ref can only be sliced at multiples of its 1024-element
            # tile, which Mosaic cannot prove of jb * 128.
            pl.BlockSpec((n // BLOCK_ROWS, BLOCK_ROWS), lambda i: (0, 0)),
            pl.BlockSpec((n, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=not registry._on_tpu(),
        name=FUSED_EMBED_GRAD,
    )(seg.reshape(n // BLOCK_ROWS, BLOCK_ROWS), sv)
    return out


def _segsum_eligible(sv, seg):
    n = sv.shape[0]
    d = sv.shape[1] if sv.ndim == 2 else None
    if sv.ndim != 2:
        return False, f"grads must be (n, dim), got rank {sv.ndim}"
    if jnp.dtype(sv.dtype) not in (jnp.dtype(jnp.float32),):
        return False, f"grads must be f32 on the wire, got {sv.dtype}"
    if n == 0 or n % BLOCK_ROWS:
        return False, (f"row count {n} must be a positive multiple of the "
                       f"{BLOCK_ROWS}-row mask-matmul tile")
    if d % _LANE:
        return False, f"embedding dim {d} must be a multiple of {_LANE}"
    if (n * (d + 1) + BLOCK_ROWS * d) * 4 > VMEM_BUDGET_BYTES:
        return False, (f"{n} rows x dim {d} exceed the "
                       f"{VMEM_BUDGET_BYTES >> 20} MiB VMEM residency "
                       "budget for the mask-matmul sweep")
    return True, None


registry.register_kernel(
    FUSED_EMBED_GRAD,
    pallas_fn=_segsum_pallas,
    xla_fallback=_segsum_xla,
    eligibility=_segsum_eligible,
)


# ---------------------------------------------------------------------------
# public forms
# ---------------------------------------------------------------------------

def rows_path_eligible(vec, idx) -> bool:
    """Would the fused segment-sum kernel serve this call? The dense-grad
    op consults this BEFORE restructuring into the rows form, so an
    ineligible shape under ``auto`` keeps the pre-tier one-scatter
    expression instead of paying sort + segment-sum + scatter on the XLA
    fallback."""
    n = 1
    for s in idx.shape:
        n *= int(s)
    d = int(vec.shape[-1])
    ok, _why = registry.eligibility_of(
        "fused_embed_grad",
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    return ok


def embed_grad_rows(vec, idx, vocab: int):
    """Compact embedding gradient: ``(rows, grads, count)`` (see module
    docstring for the layout contract). Dispatches the segment-sum through
    the kernel registry."""
    d = int(vec.shape[-1])
    n = 1
    for s in idx.shape:
        n *= int(s)
    if n == 0:
        # empty batch: the sort/segment prep's first-occurrence flag is
        # minimum length 1 and would shape-error; the compact form of
        # nothing is just nothing (the off-mode dense scatter handles
        # n=0 natively, so this route must too)
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0, d), jnp.float32),
                jnp.zeros((), jnp.int32))
    sv, seg, rows, count = _prep(vec, idx, vocab)
    grads = registry.dispatch("fused_embed_grad", sv, seg)
    return rows, grads, count


def embed_grad_dense(vec, idx, shape):
    """Dense ``(vocab, dim)`` gradient via the compact form: one scatter
    over UNIQUE rows (duplicates were already summed), versus the
    fallback's scatter over every occurrence. The sentinel row (``vocab``)
    is dropped by XLA's out-of-bounds-scatter semantics and carries zero
    grads regardless."""
    shape = tuple(int(s) for s in shape)
    rows, grads, _count = embed_grad_rows(vec, idx, shape[0])
    return jnp.zeros(shape, vec.dtype).at[rows].add(
        grads.astype(vec.dtype), mode="drop")


def embed_grad_dense_xla(vec, idx, shape):
    """The pre-hetukern expression, verbatim — what ``kernels='off'``
    must reproduce bit-for-bit and what equality tests compare against."""
    shape = tuple(int(s) for s in shape)
    flat_idx = idx.astype(jnp.int32).reshape(-1)
    flat_vec = vec.reshape((-1, shape[-1]))
    return jnp.zeros(shape, vec.dtype).at[flat_idx].add(flat_vec)
