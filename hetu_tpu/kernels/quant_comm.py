"""Quant-fused collective legs for the hetuq AllReduce (docs/KERNELS.md,
docs/COMM_QUANT.md).

PR 8's quantized DP AllReduce lowers as reduce-scatter(f32) → blockwise
quantize → all-gather(int8/fp8 + scales) → dequantize. The quantize half
under XLA's default codegen is three passes over the shard (abs-max
reduce, scale divide, round/clip/cast) with the ``(nb, block)`` reshape
materialized between them; the dequantize half is another two. These
kernels fuse each half into ONE pass over the shard resident in VMEM —
the EQuARX move (PAPERS.md arXiv:2506.17615) of pushing the quantization
work below the collective boundary, expressed at the Pallas level since
GSPMD owns the collective itself.

Wire-format contract: the kernel output must be BIT-IDENTICAL to
``comm_quant.quantize_blocks`` — same abs-max, same ``/Q`` scale, same
round-half-even, same all-zero-block convention — because the payload
crosses the wire to peers that may dequantize with the unfused path
(and because the error-feedback residual algebra assumes one quantizer).
``tests/test_kernels.py`` asserts exact equality of ``(q, scales)`` for
both int8 and fp8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry

# the kernels' names in the device trace (the pallas_call's `name=`) and in
# the registry: one constant a call site (docs/KERNELS.md)
QUANT_BLOCKS = "quant_blocks"
DEQUANT_BLOCKS = "dequant_blocks"

_INT8_Q = 127.0
_FP8_Q = 448.0
_LANE = registry.LANE
# one-pass residency: the whole (nb, block) shard view sits in VMEM
# (the registry's shared budget constant)
VMEM_BUDGET_BYTES = registry.VMEM_BUDGET_BYTES


def _fp8():
    return getattr(jnp, "float8_e4m3fn", None)


# -- fallbacks: the comm_quant (jnp) implementations, re-used not copied ----

def _quant_xla(x, *, block: int, mode: str):
    from .. import comm_quant
    return comm_quant.quantize_blocks(x, block, mode)


def _dequant_xla(q, scales, *, n: int, block: int):
    from .. import comm_quant
    return comm_quant.dequantize_blocks(q, scales, n, block)


# -- pallas: one pass over the shard ----------------------------------------

def _quant_kernel(x_ref, q_ref, s_ref, *, mode):
    blocks = x_ref[:]                                   # (nb, block) f32
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    if mode == "fp8":
        scales = amax / _FP8_Q
        safe = jnp.where(scales > 0, scales, 1.0)
        q_ref[:] = (blocks / safe).astype(q_ref.dtype)
    else:
        scales = amax / _INT8_Q
        safe = jnp.where(scales > 0, scales, 1.0)
        q_ref[:] = jnp.clip(jnp.round(blocks / safe),
                            -127, 127).astype(jnp.int8)
    s_ref[:] = scales


def _quant_pallas(x, *, block: int, mode: str):
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(nb, block)
    wire_dtype = _fp8() if mode == "fp8" else jnp.int8
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, mode=mode),
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), wire_dtype),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=not registry._on_tpu(),
        name=QUANT_BLOCKS,
    )(blocks)
    return q.reshape(-1), scales.reshape(-1), n


def _quant_eligible(x, *, block: int, mode: str):
    if mode not in ("int8", "fp8"):
        return False, f"mode must be int8/fp8, got {mode!r}"
    if mode == "fp8" and _fp8() is None:
        return False, "this jax build has no float8_e4m3fn"
    if not jnp.issubdtype(jnp.dtype(x.dtype), jnp.floating):
        return False, f"payload must be float, got {x.dtype}"
    if block % _LANE:
        return False, f"block {block} must be a multiple of {_LANE}"
    n = 1
    for s in x.shape:
        n *= int(s)
    nb = -(-n // block)
    if nb * block * 5 > VMEM_BUDGET_BYTES:   # f32 in + 1-byte out
        return False, (f"shard of {nb * block} elements exceeds the "
                       f"{VMEM_BUDGET_BYTES >> 20} MiB one-pass VMEM budget")
    return True, None


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def _dequant_pallas(q, scales, *, n: int, block: int):
    nb = scales.size
    out = pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=not registry._on_tpu(),
        name=DEQUANT_BLOCKS,
    )(q.reshape(nb, block), scales.reshape(nb, 1))
    return out.reshape(-1)[:n]


def _dequant_eligible(q, scales, *, n: int, block: int):
    if block % _LANE:
        return False, f"block {block} must be a multiple of {_LANE}"
    nb = 1
    for s in scales.shape:
        nb *= int(s)
    if nb * block * 5 > VMEM_BUDGET_BYTES:
        return False, (f"shard of {nb * block} elements exceeds the "
                       f"{VMEM_BUDGET_BYTES >> 20} MiB one-pass VMEM budget")
    return True, None


registry.register_kernel(
    QUANT_BLOCKS,
    pallas_fn=_quant_pallas,
    xla_fallback=_quant_xla,
    eligibility=_quant_eligible,
)

registry.register_kernel(
    DEQUANT_BLOCKS,
    pallas_fn=_dequant_pallas,
    xla_fallback=_dequant_xla,
    eligibility=_dequant_eligible,
)


def quantize_blocks(x, block: int, mode: str = "int8"):
    """Registry-dispatched blockwise quantize — same signature and
    bit-identical output contract as ``comm_quant.quantize_blocks``."""
    return registry.dispatch("quant_blocks", x, block=block, mode=mode)


def dequantize_blocks(q, scales, n: int, block: int):
    return registry.dispatch("dequant_blocks", q, scales, n=n, block=block)
