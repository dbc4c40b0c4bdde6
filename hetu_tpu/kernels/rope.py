"""Partial interleaved rotary embeddings in one pass through VMEM.

`transformer._rope_interleaved` turns the rotary columns of a (batch, seq,
heads * hd) array with two rolls of the WHOLE array in float32, a select and
two tables tiled to the array's width: at latent attention's q, (4, 8192,
6144), that is 805 MB of float32 several times over and 2 x 201 MB of tables
where 403 MB is read and 403 MB written. This kernel reads a block once and
writes it once: a grid step holds (rows, cols) of the array as the projection
wrote it, casts a lane tile to float32 in VMEM, forms each column's partner
by two lane rotations of that tile, applies `x * cos + partner * sin` in the
reference's order and writes the input's dtype (docs/KERNELS.md, "Rotary
columns in one pass").

Public entry: ``rope_interleaved(x, pos0, theta, hd, first)``, the
reference's signature and result, differentiable via custom_vjp whose
backward pass is the same kernel with the sine negated and which keeps no
residual. ``takes(x, hd, first, mesh)`` is the one rule a caller asks: on a
TPU, in a single program, a shape the blocks divide (a width that whole
periods of lcm(hd, 128) columns divide, even `hd` and `first`, a sequence
that row blocks divide: DeepSeek-V3's 192 = 128 + 64 a head at any even
number of heads, and so a head of 128 or 64 columns; 96 + 64 needs a
multiple of four heads); every other call takes the reference. Called
directly off a TPU the kernel is interpreted, which is how tests drive it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the device trace (docs/KERNELS.md). The attention
# kernels are found by the substring "flash_": this name must not hold it
ROPE_PAIRS = "rope_pairs"

_LANES = 128
# Rows of a block: the first that divides the sequence. And what a block of x
# may weigh: with its result, both buffered twice, the two float32 tables of
# (rows, period) buffered twice and a few float32 lane tiles of temporaries,
# a grid step stays under half of the 16 MiB Mosaic gives a kernel on a v5e
# unasked. On the chip at (4, 8192, 6144) bf16, blocks of (512, 768) to
# (512, 3072) all read 1.31-1.36 ms a call and (512, 384) 1.45 (PERF.md,
# PR 40): the smallest of the fast ones.
_ROW_BLOCKS = (512, 256, 128, 64, 32, 16)
_BLOCK_BYTES = 768 * 1024


def _period(hd):
    """Columns after which both the heads and the lane tiles repeat."""
    return math.lcm(hd, _LANES)


def _blocks(shape, hd, first, itemsize):
    """-> (rows, cols) of a grid step's block of a (B, T, W) array, or None
    where the kernel does not serve the shape. `cols` is a whole number of
    periods, so no head and no lane tile straddles a block's edge; pairs
    start on even columns, so none straddles a lane tile."""
    _, T, W = shape
    period = _period(hd)
    if hd % 2 or first % 2 or not 0 <= first < hd or W % period:
        return None
    rows = next((r for r in _ROW_BLOCKS if T % r == 0), None)
    if rows is None:
        return None
    fit = max(1, _BLOCK_BYTES // (rows * period * itemsize))
    n = W // period
    cols = period * max(k for k in range(1, n + 1) if n % k == 0 and k <= fit)
    return rows, cols


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def takes(x, hd, first, mesh=None) -> bool:
    """The ONE gating rule: the kernel turns x (B, T, heads * hd) on the
    single-program TPU path where its blocks divide the shape. Under a mesh
    the reference stays (GSPMD cannot partition the custom kernel); off-TPU
    interpret mode would be slower than the reference."""
    if (mesh is not None and mesh.size > 1) or not _on_tpu():
        return False
    return _blocks(x.shape, hd, first, x.dtype.itemsize) is not None


def tables(T, pos0, theta, hd, first, heads):
    """cos and signed sin under `heads` heads' columns at positions
    pos0..pos0+T-1, (T, heads * hd) float32: ones and zeros under a head's
    columns before `first`, the pairs' angles from there on, the sign of
    out[2i] = x[2i] cos - x[2i+1] sin, out[2i+1] = x[2i+1] cos + x[2i] sin
    on the sine. The reference's tables (`heads` the array's) and the
    kernel's (one period's) are this one expression."""
    rot = hd - first
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    t = pos0 + jnp.arange(T, dtype=jnp.float32)
    freqs = jnp.repeat(jnp.outer(t, inv), 2, axis=-1)         # (T, rot)
    sign = jnp.tile(jnp.array([-1.0, 1.0], jnp.float32), rot // 2)
    cos = jnp.concatenate([jnp.ones((T, first), jnp.float32),
                           jnp.cos(freqs)], -1)
    sin = jnp.concatenate([jnp.zeros((T, first), jnp.float32),
                           jnp.sin(freqs) * sign], -1)
    return jnp.tile(cos, heads), jnp.tile(sin, heads)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, passes):
    """One (rows, cols) block, a lane tile at a time. `passes[j]`: every
    column of the period's lane tile j lies before `first` in its head, and
    the tile is copied."""
    rows, cols = x_ref.shape
    even = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1) % 2 == 0
    for j in range(cols // _LANES):
        at = slice(j * _LANES, (j + 1) * _LANES)
        of_period = j % len(passes)
        if passes[of_period]:
            o_ref[:, at] = x_ref[:, at]
            continue
        tab = slice(of_period * _LANES, (of_period + 1) * _LANES)
        x = x_ref[:, at].astype(jnp.float32)
        # a column's partner is one lane to its right (even) or left (odd);
        # the lanes a rotation wraps around the tile are never chosen
        partner = jnp.where(even, pltpu.roll(x, _LANES - 1, 1),
                            pltpu.roll(x, 1, 1))
        o_ref[:, at] = (x * cos_ref[:, tab]
                        + partner * sin_ref[:, tab]).astype(o_ref.dtype)


def _rotate(x, cos, sin, hd, first):
    B, T, W = x.shape
    rows, cols = _blocks(x.shape, hd, first, x.dtype.itemsize)
    period = cos.shape[-1]
    col = np.arange(period).reshape(-1, _LANES) % hd
    passes = tuple(bool(p) for p in (col < first).all(-1))
    # the row block outermost: the tables' block index moves with it alone,
    # so they stay in VMEM across the batch and the column blocks
    block = pl.BlockSpec((None, rows, cols), lambda r, b, c: (b, r, c))
    table = pl.BlockSpec((rows, period), lambda r, b, c: (r, 0))
    return pl.pallas_call(
        functools.partial(_kernel, passes=passes),
        grid=(T // rows, B, W // cols),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        # not `_on_tpu()`: a test that patches the rule to take the kernel
        # off the chip still needs it interpreted there
        interpret=jax.default_backend() != "tpu",
        name=ROPE_PAIRS,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def rope_interleaved(x, pos0, theta, hd, first):
    """`transformer._rope_interleaved` of a shape `_blocks` divides: x (B, T,
    heads * hd) at positions pos0..pos0+T-1 (`pos0` a Python number), each
    head's columns from `first` on turned in adjacent pairs, float32 inside,
    x's dtype out."""
    cos, sin = tables(x.shape[1], pos0, theta, hd, first, _period(hd) // hd)
    return _rotate(x, cos, sin, hd, first)


def _fwd(x, pos0, theta, hd, first):
    return rope_interleaved(x, pos0, theta, hd, first), None


def _bwd(pos0, theta, hd, first, _, g):
    # the rotation's transpose is the rotation by the opposite angle:
    # dx[2i] = g[2i] cos + g[2i+1] sin, dx[2i+1] = g[2i+1] cos - g[2i] sin
    cos, sin = tables(g.shape[1], pos0, theta, hd, first, _period(hd) // hd)
    return (_rotate(g, cos, -sin, hd, first),)


rope_interleaved.defvjp(_fwd, _bwd)
