"""hetuq: quantized communication for DP gradient sync and PS traffic.

Two independent wire paths share the one policy knob
(``HetuConfig(comm_quant="off"|"int8"|"fp8")`` / ``HETU_COMM_QUANT``):

- **DP AllReduce** (in-trace, pure XLA): the gradient all-reduce is
  decomposed as reduce-scatter (f32, exact accumulation) + all-gather of a
  blockwise-quantized payload (int8 or fp8 with one f32 scale per ~256-
  element block), expressed entirely through sharding constraints so GSPMD
  materializes the int8 collective — the JAX-level analogue of EQuARX's
  in-XLA blockwise AllReduce (PAPERS.md arXiv:2506.17615; GSPMD offers no
  trace-level handle on per-replica partial sums, so the reduction half
  stays exact f32 and only the broadcast half rides the wire compressed).
  An optional error-feedback residual (executor-managed state) carries the
  quantization error into the next step so compression error does not
  accumulate in the parameters.

- **PS sparse/dense traffic** (host/C++): row-wise int8 with one f32 scale
  per row for sparse push/pull payloads and block-wise int8 for dense
  push/push-pull, carried by the ``ArgType::kQI8`` wire container
  (``csrc/ps/net.h``). The server dequantizes on receipt and applies in
  f32, so dedup-sums, the snapshot format, the resend-dedup ledger, and
  exact lost-update accounting are all untouched. :func:`np_quantize_blocks`
  is the bit-exact Python mirror of the C++ quantizer (same f32 ops, same
  round-half-even), which is what the dedup-exactness tests assert against.

Scheme (both paths): symmetric linear quantization per block —
``scale = max(|block|) / Q`` (Q = 127 for int8, 448 for fp8-e4m3),
``q = round_half_even(v / scale)``, ``dq = q * scale``; an all-zero block
stores scale 0 and dequantizes to exact zeros. Max error per element is
``scale / 2`` for int8. See docs/COMM_QUANT.md for the error-feedback math
and the exemption policy.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

MODES = ("off", "int8", "fp8")

# wire block for dense (non-row-structured) payloads, both the XLA and the
# PS paths; sparse rows use the row width as the block so one scale serves
# one row
DEFAULT_BLOCK = 256
# params below this element count are exempt (biases, norm scales — tiny
# payloads where quantization risk buys no measurable wire saving)
DEFAULT_MIN_SIZE = 2048

_INT8_Q = 127.0
_FP8_Q = 448.0  # float8_e4m3fn max finite


def _env(name, dflt):
    v = os.environ.get(name)
    return v if v not in (None, "") else dflt


def _env_bool(name, dflt):
    v = os.environ.get(name)
    if v is None or v == "":
        return dflt
    return v.strip().lower() in ("1", "true", "yes", "on")


def fp8_dtype():
    """The fp8 wire dtype (``float8_e4m3fn``) or None when this jax build
    has no float8 support."""
    import jax.numpy as jnp
    return getattr(jnp, "float8_e4m3fn", None)


class QuantPolicy:
    """Per-parameter quantization decisions for one executor.

    ``mode``: "off" | "int8" | "fp8" (fp8 applies to the AllReduce path
    only; the PS wire container is int8). ``block``: scale granularity for
    dense payloads. ``min_size``: params with fewer elements are exempt.
    ``error_feedback``: carry the AllReduce quantization error as residual
    state. ``force``: param names quantized regardless of the size
    threshold (an override hetulint warns about when it defeats the
    exemption — see ``comm-quant-forced-small``).
    """

    def __init__(self, mode="off", block=DEFAULT_BLOCK,
                 min_size=DEFAULT_MIN_SIZE, error_feedback=True, force=()):
        if mode not in MODES:
            raise ValueError(
                f"comm_quant must be one of {MODES}, got {mode!r}")
        if int(block) <= 0:
            raise ValueError(f"comm_quant block must be positive, got {block}")
        self.mode = mode
        self.block = int(block)
        self.min_size = int(min_size)
        self.error_feedback = bool(error_feedback)
        self.force = tuple(force or ())
        if mode == "fp8" and fp8_dtype() is None:
            raise ValueError(
                "comm_quant='fp8' needs a jax build with float8_e4m3fn; "
                "use 'int8' on this environment")

    @property
    def active(self) -> bool:
        return self.mode != "off"

    def applies(self, param_node, size: int) -> bool:
        """Does this policy quantize a param of ``size`` elements?"""
        if not self.active:
            return False
        name = getattr(param_node, "name", None)
        if name is not None and name in self.force:
            return True
        return int(size) >= self.min_size

    def __repr__(self):
        return (f"QuantPolicy({self.mode!r}, block={self.block}, "
                f"min_size={self.min_size}, ef={self.error_feedback})")


def resolve_policy(mode=None, block=None, min_size=None, error_feedback=None,
                   force=()) -> QuantPolicy:
    """Config-or-env resolution (the telemetry/introspect convention):
    explicit arguments win, then ``HETU_COMM_QUANT`` /
    ``HETU_COMM_QUANT_BLOCK`` / ``HETU_COMM_QUANT_MIN`` /
    ``HETU_COMM_QUANT_EF``, then the defaults (off)."""
    if mode is None:
        mode = _env("HETU_COMM_QUANT", "off")
    if block is None:
        block = int(_env("HETU_COMM_QUANT_BLOCK", DEFAULT_BLOCK))
    if min_size is None:
        min_size = int(_env("HETU_COMM_QUANT_MIN", DEFAULT_MIN_SIZE))
    if error_feedback is None:
        error_feedback = _env_bool("HETU_COMM_QUANT_EF", True)
    return QuantPolicy(mode, block=block, min_size=min_size,
                       error_feedback=error_feedback, force=force)


# ---------------------------------------------------------------------------
# traced (jnp) blockwise quantize/dequantize — the AllReduce path
# ---------------------------------------------------------------------------

def quantize_blocks(x, block: int, mode: str = "int8"):
    """Blockwise symmetric quantization of a flat f32 array inside a trace.

    Returns ``(q, scales, n)``: ``q`` is the padded quantized payload
    (int8 or fp8, length ``ceil(n/block)*block``), ``scales`` one f32 per
    block, ``n`` the original element count. Deterministic (round half to
    even), so every replica of a replicated input quantizes identically.
    """
    import jax.numpy as jnp
    if mode not in ("int8", "fp8"):
        raise ValueError(f"quantize_blocks: mode must be int8/fp8, "
                         f"got {mode!r}")
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(nb, block)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    if mode == "fp8":
        f8 = fp8_dtype()
        scales = amax / _FP8_Q
        safe = jnp.where(scales > 0, scales, 1.0)
        q = (blocks / safe).astype(f8)
    else:
        scales = amax / _INT8_Q
        safe = jnp.where(scales > 0, scales, 1.0)
        q = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scales.reshape(-1), n


def dequantize_blocks(q, scales, n: int, block: int):
    """Inverse of :func:`quantize_blocks` (drops the padding tail)."""
    import jax.numpy as jnp
    nb = scales.size
    vals = (q.reshape(nb, block).astype(jnp.float32)
            * scales.reshape(nb, 1)).reshape(-1)
    return vals[:n]


def quantized_allreduce(x, residual, mesh, dp_axis: str, out_sharding,
                        policy: QuantPolicy):
    """One quantized DP gradient all-reduce inside the jitted step.

    ``x`` is the logical (full-batch) gradient; under GSPMD its physical
    realization before the first replication constraint is per-replica
    partial sums. The lowering is reduce-scatter (f32 — the accumulation
    stays exact) via a dp-sharded constraint, blockwise quantize of the
    shards, all-gather of the compressed payload via a replicated
    constraint, then dequantize. ``residual`` (or None) is the error-
    feedback state: it is added before quantization and the new residual
    ``(input - dequantized)`` is returned for the executor to thread into
    the next step.

    Returns ``(value, new_residual_or_None)`` with ``value`` constrained to
    ``out_sharding`` (the target parameter's own spec).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    orig_dtype = x.dtype
    g = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    if residual is not None:
        g = g + residual.astype(jnp.float32)
    flat = g.reshape(-1)
    # reduce-scatter point: the f32 partial-sum reduction lands here, into
    # dp shards (exact accumulation — quantization error never enters the
    # sum itself, which is also why error feedback only needs to model the
    # quantizer)
    flat = jax.lax.with_sharding_constraint(
        flat, NamedSharding(mesh, P(dp_axis)))
    # hetukern quant-fused legs (docs/KERNELS.md): the quantize fused into
    # the reduce-scatter output and the dequantize into the all-gather
    # output each become ONE Pallas pass over the shard when the kernel
    # tier is active — bit-identical wire payloads to this module's jnp
    # path (asserted in tests/test_kernels.py), so mixed fleets agree
    from .kernels import quant_comm as _qk
    q, scales, n = _qk.quantize_blocks(flat, policy.block, policy.mode)
    # all-gather point: the wire payload here is the 1-byte-per-element
    # compressed tensor plus one f32 scale per block
    q = jax.lax.with_sharding_constraint(q, NamedSharding(mesh, P()))
    scales = jax.lax.with_sharding_constraint(
        scales, NamedSharding(mesh, P()))
    dq = _qk.dequantize_blocks(q, scales, n, policy.block)
    new_residual = None
    if residual is not None:
        new_residual = (g.reshape(-1) - dq).reshape(x.shape)
    out = dq.reshape(x.shape).astype(orig_dtype)
    out = jax.lax.with_sharding_constraint(out, out_sharding)
    return out, new_residual


def allreduce_wire_report(sizes: dict, policy: QuantPolicy,
                          dp: int) -> dict:
    """Analytic per-step wire accounting for the quantized AllReduce path
    (``sizes``: quantized-param name -> element count). ``raw_bytes`` is
    the baseline f32 all-reduce payload (reduce-scatter + all-gather =
    2·N·4 per step), ``wire_bytes`` the quantized decomposition's
    (f32 reduce-scatter + 1-byte all-gather + scales). Exported as the
    ``hetu_comm_quant_raw_bytes`` / ``_wire_bytes`` gauges; the PS path
    reports *measured* counters instead (worker.h)."""
    raw = wire = 0
    for n in sizes.values():
        nb = -(-n // policy.block)
        raw += 2 * n * 4
        wire += n * 4 + n + nb * 4
    return {"params": len(sizes), "elements": sum(sizes.values()),
            "raw_bytes": raw, "wire_bytes": wire, "dp": dp,
            "ratio": round(raw / wire, 3) if wire else None}


# ---------------------------------------------------------------------------
# numpy mirror of the C++ wire quantizer (csrc/ps/net.h make_qi8_arg)
# ---------------------------------------------------------------------------

def np_quantize_blocks(vals, block: int):
    """Bit-exact host mirror of the C++ int8 quantizer: same f32 ops, same
    round-half-even (``lrintf`` under the default rounding mode). Tests
    assert the PS server's applied values equal this mirror EXACTLY, which
    proves dedup-sums happened in f32 before quantization."""
    flat = np.ascontiguousarray(vals, np.float32).ravel()
    n = flat.size
    nb = -(-n // block)
    padded = np.zeros(nb * block, np.float32)
    padded[:n] = flat
    blocks = padded.reshape(nb, block)
    amax = np.max(np.abs(blocks), axis=1).astype(np.float32)
    scales = (amax / np.float32(_INT8_Q)).astype(np.float32)
    safe = np.where(scales > 0, scales, np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(blocks / safe[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1)[: nb * block], scales, n


def np_dequantize_blocks(q, scales, n: int, block: int):
    nb = scales.size
    vals = (q.reshape(nb, block).astype(np.float32)
            * scales[:, None].astype(np.float32)).reshape(-1)
    return vals[:n]


def np_roundtrip(vals, block: int):
    """Quantize→dequantize through the wire mirror; shape-preserving."""
    a = np.ascontiguousarray(vals, np.float32)
    q, s, n = np_quantize_blocks(a, block)
    return np_dequantize_blocks(q, s, n, block).reshape(a.shape)
