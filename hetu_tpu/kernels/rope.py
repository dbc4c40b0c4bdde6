"""Rotary columns in one pass through VMEM, both conventions.

`transformer._rope_interleaved` and `transformer._rope` turn the rotary
columns of a (batch, seq, heads * hd) array with two rolls of the WHOLE array
in float32, a select and two tables tiled to the array's width: at latent
attention's q, (4, 8192, 6144), that is 805 MB of float32 several times over
and 2 x 201 MB of tables where 403 MB is read and 403 MB written. This kernel
reads a block once and writes it once: a grid step holds (rows, cols) of the
array as the projection wrote it, casts a lane tile to float32 in VMEM, forms
each column's partner by lane rotations of that tile, applies `x * cos +
partner * sin` in the reference's order and writes the input's dtype
(docs/KERNELS.md, "Rotary columns in one pass").

ONE kernel, parametrised by where a column's partner lies (`_form`): one lane
away by parity for adjacent pairs, `rot / 2` lanes away by `col % hd < rot /
2` for halves (a single rotation by 64 and no select where `rot` is 128).

Public entries, each its reference's signature and result, differentiable via
a custom_vjp whose backward pass is the same kernel with the sine negated and
which keeps no residual (the partner map is an involution under which the
signed sine changes sign): ``rope_interleaved(x, pos0, theta, hd, first)``
and ``rope_halves(x, pos0, theta, hd, rot, yarn)``, the latter also on a
column range ``at`` of x, which is how q and k are read out of the fused [q |
k | v] projection where it stands. ``takes(x, hd, first, mesh, rot, pos0,
at)`` is the one rule a caller asks: on a TPU, in a single program, `pos0` a
Python number, a shape the blocks divide (a width that whole periods of
lcm(hd, 128) columns divide, a column's partner in its own lane tile, a
sequence that row blocks divide: DeepSeek-V3's 192 = 128 + 64 a head at any
even number of heads in pairs; heads of 128, 64 or 32 in halves, or of 256
whose first 128 columns turn); every other call takes the reference. Called
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

# the kernel's names in the device trace (docs/KERNELS.md), one a convention so
# that a trace tells whose rotation a call is. The attention kernels are found
# by the substring "flash_": these names must not hold it
ROPE_PAIRS = "rope_pairs"
ROPE_HALVES = "rope_halves"

_LANES = 128
# Rows of a block: the first that divides the sequence. And what a block of x
# may weigh: with its result, both buffered twice, the two float32 tables of
# (rows, period) buffered twice and a few float32 lane tiles of temporaries,
# a grid step stays under half of the 16 MiB Mosaic gives a kernel on a v5e
# unasked. On the chip at (4, 8192, 6144) bf16, blocks of (512, 768) to
# (512, 3072) all read 1.31-1.36 ms a call and (512, 384) 1.45 (PERF.md,
# PR 40): the smallest of the fast ones. The halves of a (1, 16384, 8192) q
# and its k: (512, 256) 1.25 ms, (512, 512) 1.04, (512, 1024) 1.005 (PR 50).
_ROW_BLOCKS = (512, 256, 128, 64, 32, 16)
_BLOCK_BYTES = 768 * 1024


def _period(hd):
    """Columns after which both the heads and the lane tiles repeat."""
    return math.lcm(hd, _LANES)


def _form(hd, first, rot):
    """Where a column's partner lies -> (lo, hi, dist, span): of a head's hd
    columns those in [lo, hi) turn, a column whose lane % span < dist with the
    column `dist` lanes to its right and every other with the one `dist` to
    its left; or None where a partner would lie in another lane tile.
    `rot` None: adjacent pairs from `first` on (`_rope_interleaved`: pairs
    start on even columns, so none straddles a tile). Else the halves of a
    head's first `rot` columns (`_rope`, 0 = hd): heads inside a lane tile,
    or lane tiles inside a head of which only the first holds rotary
    columns."""
    if rot is None:
        fits = hd % 2 == 0 and first % 2 == 0 and 0 <= first < hd
        return (first, hd, 1, 2) if fits else None
    rot = rot or hd
    fits = first == 0 and rot % 2 == 0 and 0 < rot <= hd and (
        _LANES % hd == 0 or (hd % _LANES == 0 and rot <= _LANES))
    return (0, rot, rot // 2, min(hd, _LANES)) if fits else None


def _blocks(shape, hd, first, itemsize, rot=None, at=None):
    """-> (rows, cols) of a grid step's block of the columns `at` = (offset,
    width) of a (B, T, W) array (None: all of them), or None where the
    kernel does not serve the shape. `cols` is a whole number of periods, so
    no head and no lane tile straddles a block's edge, and divides `offset`,
    so a block index names the columns."""
    _, T, W = shape
    offset, width = at or (0, W)
    period = _period(hd)
    if (_form(hd, first, rot) is None or width % period or offset % period
            or not 0 < offset + width <= W):
        return None
    rows = next((r for r in _ROW_BLOCKS if T % r == 0), None)
    if rows is None:
        return None
    fit = max(1, _BLOCK_BYTES // (rows * period * itemsize))
    n = math.gcd(width // period, offset // period)
    cols = period * max(k for k in range(1, n + 1) if n % k == 0 and k <= fit)
    return rows, cols


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def takes(x, hd, first=0, mesh=None, rot=None, pos0=0, at=None) -> bool:
    """The ONE gating rule: the kernel turns x (B, T, heads * hd), or its
    columns `at`, on the single-program TPU path where its blocks divide the
    shape and the positions are known as the program is made (decode's are
    traced). Under a mesh the reference stays (GSPMD cannot partition the
    custom kernel); off-TPU interpret mode would be slower than the
    reference. `rot` None asks for pairs from `first` on, else for the
    halves of a head's first `rot` columns (`_form`)."""
    if (mesh is not None and mesh.size > 1) or not _on_tpu():
        return False
    if not isinstance(pos0, (int, float)) or x.ndim != 3:
        return False
    return _blocks(x.shape, hd, first, x.dtype.itemsize, rot, at) is not None


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


def yarn_inv_freq(theta, dim, yarn):
    """The ``dim`` / 2 inverse frequencies of a rotary table scaled by YaRN
    (``yarn`` a ``transformer.YarnConfig``), float64 (``transformers``
    ``_compute_yarn_parameters``): with f_i = theta^(-2i / dim) and c(n) =
    dim ln(original_max_len / (2 pi n)) / (2 ln theta), the index of the
    frequency that turns n times in the original length, low =
    floor(c(beta_fast)) and high = ceil(c(beta_slow)), clipped to the table
    (refused where they meet), ramp_i = clip((i - low) / (high - low), 0,
    1): inv_i = (1 - ramp_i) f_i + ramp_i f_i / factor."""
    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    c = lambda n: (dim * math.log(yarn.original_max_len / (2 * math.pi * n))
                   / (2 * math.log(theta)))
    low = max(math.floor(c(yarn.beta_fast)), 0)
    high = min(math.ceil(c(yarn.beta_slow)), dim - 1)
    if low >= high:
        raise ValueError(f"{yarn} on {dim} columns at theta {theta}: the "
                         f"ramp's ends are {low} and {high}, no ramp")
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * f + ramp * f / yarn.factor


def tables_halves(T, pos0, theta, hd, rot, yarn, heads):
    """cos and signed sin of the rotate-half convention under `heads` heads'
    columns at positions pos0..pos0+T-1, (T, heads * hd) float32: the angles
    of a head's first `rot` columns (both halves the same rot / 2), ones and
    zeros under the others; rotate_half is [-x2, x1], so the sign rides on
    the first half's sine. `yarn`: the frequencies are ``yarn_inv_freq``'s
    (made in float64, cast) and cos and sin carry its ``attention_factor``.
    The reference's tables (`heads` the array's) and the kernel's (one
    period's) are this one expression."""
    if yarn is None or yarn.factor == 1.0:      # nothing to scale
        inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                               / rot))
    else:
        inv = jnp.asarray(yarn_inv_freq(theta, rot, yarn), jnp.float32)
    t = pos0 + jnp.arange(T, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # (T, rot/2)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if yarn is not None and yarn.attention_factor != 1.0:
        cos, sin = (c * jnp.float32(yarn.attention_factor)
                    for c in (cos, sin))
    still = ([jnp.ones((T, hd - rot), jnp.float32)],
             [jnp.zeros((T, hd - rot), jnp.float32)]) if rot < hd else ([], [])
    return (jnp.tile(jnp.concatenate([cos, cos] + still[0], -1), heads),
            jnp.tile(jnp.concatenate([-sin, sin] + still[1], -1), heads))


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, passes, dist, span):
    """One (rows, cols) block, a lane tile at a time. `passes[j]`: no column
    of the period's lane tile j turns, and the tile is copied. `dist`,
    `span`: `_form`'s."""
    rows, cols = x_ref.shape
    # half a tile away, right and left are the same lane: no select
    right = None if 2 * dist == _LANES else jax.lax.broadcasted_iota(
        jnp.int32, (rows, _LANES), 1) % span < dist
    for j in range(cols // _LANES):
        at = slice(j * _LANES, (j + 1) * _LANES)
        of_period = j % len(passes)
        if passes[of_period]:
            o_ref[:, at] = x_ref[:, at]
            continue
        tab = slice(of_period * _LANES, (of_period + 1) * _LANES)
        x = x_ref[:, at].astype(jnp.float32)
        # the lanes a rotation wraps around the tile are never chosen
        partner = pltpu.roll(x, dist, 1)
        if right is not None:
            partner = jnp.where(right, pltpu.roll(x, _LANES - dist, 1),
                                partner)
        o_ref[:, at] = (x * cos_ref[:, tab]
                        + partner * sin_ref[:, tab]).astype(o_ref.dtype)


def _rotate(x, cos, sin, hd, first, rot=None, at=None):
    B, T, _ = x.shape
    offset, width = at or (0, x.shape[-1])
    rows, cols = _blocks(x.shape, hd, first, x.dtype.itemsize, rot, at)
    lo, hi, dist, span = _form(hd, first, rot)
    period = cos.shape[-1]
    col = np.arange(period).reshape(-1, _LANES) % hd
    passes = tuple(bool(p) for p in ((col < lo) | (col >= hi)).all(-1))
    # the row block outermost: the tables' block index moves with it alone,
    # so they stay in VMEM across the batch and the column blocks
    block = lambda skip: pl.BlockSpec(
        (None, rows, cols), lambda r, b, c: (b, r, c + skip))
    table = pl.BlockSpec((rows, period), lambda r, b, c: (r, 0))
    return pl.pallas_call(
        functools.partial(_kernel, passes=passes, dist=dist, span=span),
        grid=(T // rows, B, width // cols),
        in_specs=[block(offset // cols), table, table],
        out_specs=block(0),
        out_shape=jax.ShapeDtypeStruct((B, T, width), x.dtype),
        # not `_on_tpu()`: a test that patches the rule to take the kernel
        # off the chip still needs it interpreted there
        interpret=jax.default_backend() != "tpu",
        name=ROPE_PAIRS if rot is None else ROPE_HALVES,
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


def rope_halves(x, pos0, theta, hd, rot=0, yarn=None, at=None):
    """`transformer._rope` of a shape `_blocks` divides: x (B, T, heads *
    hd) at positions pos0..pos0+T-1 (`pos0` a Python number), the halves of
    each head's first `rot` columns (0 = hd) turned, float32 inside, x's
    dtype out. `at` = (offset, width): the rotation of x[..., offset:offset +
    width] alone, (B, T, width), read where it stands in x (q or k in the
    fused [q | k | v] projection: no copy of the slice is made)."""
    return _halves(x, pos0, theta, hd, rot or hd, yarn,
                   at or (0, x.shape[-1]), x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _halves(x, pos0, theta, hd, rot, yarn, at, W):
    cos, sin = tables_halves(x.shape[1], pos0, theta, hd, rot, yarn,
                             _period(hd) // hd)
    return _rotate(x, cos, sin, hd, 0, rot, at)


def _fwd_halves(x, *rest):
    return _halves(x, *rest), None


def _bwd_halves(pos0, theta, hd, rot, yarn, at, W, _, g):
    # the partner map is an involution and the signed sine changes sign under
    # it (also under YaRN's factor; a passing column's is 0): the transpose
    # is the same rotation by the opposite angle, laid into x's width
    cos, sin = tables_halves(g.shape[1], pos0, theta, hd, rot, yarn,
                             _period(hd) // hd)
    dx = _rotate(g, cos, -sin, hd, 0, rot)
    return (jnp.pad(dx, ((0, 0), (0, 0), (at[0], W - at[0] - at[1]))),)


_halves.defvjp(_fwd_halves, _bwd_halves)
