"""CSR/COO sparse-times-dense matmul kernel (docs/KERNELS.md).

The graph-side sparse products (``csrmm_op``/``csrmv_op``,
``graph/ops/matmul.py``) and DistGCN's 1.5D local block product
(``parallel/distgcn.py``) all reduce to one primitive:

    Z[r, :] = Σ_j [rows_j = r] · values_j · B[cols_j, :]

The XLA fallback expresses it as gather + ``jax.ops.segment_sum`` —
correct, but the segment sum lowers to a SORT of the contributions
before the scatter, and the gather materializes an ``(nnz, F)``
intermediate in HBM. The Pallas kernel instead streams nnz blocks
through SMEM (ids/values) and does a rows-into-VMEM segment MAC: for
each entry, one dynamic-row read of ``B`` and one dynamic-row
accumulate into the output block resident in VMEM — no ``(nnz, F)``
intermediate, no sort. The TPU grid is sequential, so cross-block
accumulation into the same output ref is exact and deterministic.

Zero-padded entries (DistGCN pads blocks to the max nnz) contribute
``0 · B[0]`` and are harmless, same as in the fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

# the kernel's name in the device trace (the pallas_call's `name=`) and in
# the registry: `csr_spmv` is served by the same call (docs/KERNELS.md)
CSR_SPMM = "csr_spmm"

# nnz entries per grid step: the tile XLA lays a 1-D array out in, which is
# the only 1-D block shape Mosaic accepts for an SMEM operand
BLOCK_NNZ = 1024
_LANE = registry.LANE
_SUBLANE = registry.SUBLANE
# the whole (nrow, F) output block plus the (K, F) dense operand live in
# VMEM for the kernel's lifetime — stay well under the ~16 MB/core
# budget (the registry's shared constant)
VMEM_BUDGET_BYTES = registry.VMEM_BUDGET_BYTES


def _spmm_xla(values, rows, cols, b, *, nrow: int):
    """The pre-hetukern expression (graph/ops/matmul.py ``_coo_matmat``),
    verbatim — the ``off``-mode path and the equality oracle."""
    contrib = values[:, None] * jnp.take(b, cols, axis=0)
    return jax.ops.segment_sum(contrib, rows, num_segments=nrow)


def _spmm_kernel(vals_ref, rows_ref, cols_ref, b_ref, o_ref, *, block_nnz):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    def body(j, _):
        r = rows_ref[j]
        c = cols_ref[j]
        v = vals_ref[j]
        o_ref[pl.ds(r, 1), :] = (o_ref[pl.ds(r, 1), :]
                                 + v * b_ref[pl.ds(c, 1), :])
        return 0

    jax.lax.fori_loop(0, block_nnz, body, 0)


def _pad_nnz(values, rows, cols):
    nnz = values.shape[0]
    pad = (-nnz) % BLOCK_NNZ
    if pad:
        # value-0 padding: contributes 0 * B[0] to row 0, a no-op
        values = jnp.pad(values, (0, pad))
        rows = jnp.pad(rows, (0, pad))
        cols = jnp.pad(cols, (0, pad))
    return values, rows, cols


def _spmm_pallas(values, rows, cols, b, *, nrow: int):
    k, f = b.shape
    values, rows, cols = _pad_nnz(
        values.astype(jnp.float32), rows.astype(jnp.int32),
        cols.astype(jnp.int32))
    nnz = values.shape[0]
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, block_nnz=BLOCK_NNZ),
        grid=(nnz // BLOCK_NNZ,),
        in_specs=[
            pl.BlockSpec((BLOCK_NNZ,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_NNZ,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_NNZ,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((k, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nrow, f), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nrow, f), jnp.float32),
        interpret=not registry._on_tpu(),
        name=CSR_SPMM,
    )(values, rows, cols, b)
    return out


def _spmm_eligible(values, rows, cols, b, *, nrow: int):
    if b.ndim != 2:
        return False, f"dense operand must be (K, F), got rank {b.ndim}"
    k, f = int(b.shape[0]), int(b.shape[1])
    if jnp.dtype(b.dtype) != jnp.dtype(jnp.float32):
        return False, f"dense operand must be f32, got {b.dtype}"
    if jnp.dtype(values.dtype) != jnp.dtype(jnp.float32):
        # the kernel casts to f32; the fallback computes in the input
        # dtype — declining keeps the force-vs-off dtype contract honest
        return False, f"values must be f32, got {values.dtype}"
    if f % _LANE:
        return False, f"feature dim {f} must be a multiple of {_LANE}"
    if int(nrow) % _SUBLANE or k % _SUBLANE:
        return False, (f"row counts (nrow={nrow}, K={k}) must be multiples "
                       f"of {_SUBLANE} (f32 sublane tile)")
    if (int(nrow) + k) * f * 4 > VMEM_BUDGET_BYTES:
        return False, (f"output ({nrow}x{f}) + dense operand ({k}x{f}) "
                       f"exceed the {VMEM_BUDGET_BYTES >> 20} MiB VMEM "
                       "residency budget")
    return True, None


registry.register_kernel(
    CSR_SPMM,
    pallas_fn=_spmm_pallas,
    xla_fallback=_spmm_xla,
    eligibility=_spmm_eligible,
)


# -- matvec: its own KernelSpec so the registry gate (mode semantics,
# counting, force errors) is defined in exactly one place ----------------

def _spmv_xla(values, rows, cols, x, *, nrow: int):
    """The pre-hetukern ``_coo_matvec`` expression, verbatim."""
    contrib = values * jnp.take(x, cols, axis=0)
    return jax.ops.segment_sum(contrib, rows, num_segments=nrow)


def _spmv_pallas(values, rows, cols, x, *, nrow: int):
    # ride the spmm kernel with the vector lane-padded to (K, 128)
    b = jnp.zeros((x.shape[0], _LANE), jnp.float32).at[:, 0].set(
        x.astype(jnp.float32))
    return _spmm_pallas(values, rows, cols, b, nrow=nrow)[:, 0]


def _spmv_eligible(values, rows, cols, x, *, nrow: int):
    if x.ndim != 1:
        return False, f"dense operand must be a vector, got rank {x.ndim}"
    if jnp.dtype(x.dtype) != jnp.dtype(jnp.float32):
        return False, f"vector must be f32, got {x.dtype}"
    return _spmm_eligible(
        values, rows, cols,
        jax.ShapeDtypeStruct((int(x.shape[0]), _LANE), jnp.float32),
        nrow=nrow)


registry.register_kernel(
    "csr_spmv",
    pallas_fn=_spmv_pallas,
    xla_fallback=_spmv_xla,
    eligibility=_spmv_eligible,
)


def coo_matmat(values, rows, cols, nrow: int, b):
    """``sparse(values, rows, cols) @ B`` through the kernel registry —
    the shared entry for ``csrmm_op`` and DistGCN."""
    return registry.dispatch("csr_spmm", values, rows, cols, b,
                             nrow=int(nrow))


def coo_matvec(values, rows, cols, nrow: int, x):
    """``sparse @ x`` through the registry (``csrmv_op``)."""
    return registry.dispatch("csr_spmv", values, rows, cols, x,
                             nrow=int(nrow))
