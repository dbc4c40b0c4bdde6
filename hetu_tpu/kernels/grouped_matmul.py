"""The experts' grouped matmul with tiles made from the widths.

`jax.lax.ragged_dot` is the TPU compiler's own grouped matmul kernel. It
walks the rows in tiles of 512 whatever a group holds, and tiles K and N
each by the largest of 512 / 256 / 128 that DIVIDES the width
(`ragged_dot_tiling` in the compiled HLO), so a weight block is never whole.
A width of an odd number of lane tiles (2,688 = 21 x 128) or of a half tile
(1,856 = 14.5 x 128) gets ONE lane tile, and a call is then thousands of
grid steps of 512 x 128 x 128; at widths it tiles by 512 (2,048 x 1,792,
1,024, 768, 512) it reads 39-70 % of peak on groups a few hundred to a few
thousand rows long, where these kernels read 78-94 (docs/KERNELS.md, "The
experts' grouped matmul in Pallas at every width", has every cell's calls
alone on the chip and in the step). Here the same three products run with a
weight block that is whole wherever VMEM holds it:

    forward   (M, K) x (E, K, N) -> (M, N)     rows sorted by group
    dx        (M, N) x (E, K, N)^T -> (M, K)   the weights read transposed
                                               in the kernel, no copy in HBM
    dW        (M, K)^T (M, N) -> (E, K, N)     a group's rows masked inside
                                               the row tiles its edges cut

each ONE Mosaic call, behind one `custom_vjp`. The design is
`jax/experimental/pallas/ops/tpu/megablox/gmm.py`'s: the grid runs over the
VISITS, one for each (row tile, group) pair that shares a row, a run-time
count made from `group_sizes` in XLA (`_visits`) and handed to the kernel by
scalar prefetch with the tables visit -> group and visit -> row tile. A row
tile that two groups share is visited once for each, one after the other, and
each visit writes its own rows only. Row tiles past the groups are not
visited: those rows are neither read into a sum nor written, as with
`ragged_dot` (a NaN there changes nothing inside the groups). An empty group
gets one visit in dW alone, which writes its zeros.

Precision as `ragged_dot(..., preferred_element_type=xs.dtype)` gives it:
operands as they come, float32 sums, results in the operands' dtype.

``grouped_matmul(xs, w, group_sizes)`` is the differentiable entry;
``takes(xs, w, mesh)`` is the ONE rule a caller asks, and the registry's
eligibility is the same rule without the backend (`registry.dispatch` asks
that itself). Called directly off a TPU the kernels are interpreted, which is
how tests drive them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from .registry import LANE
from .flash_attention import _dot, _dot_nt, _dot_tn

# the registry's name, and the kernels' names in the device trace
# (`mosaic:<name>`): forward and dx are one kernel, dW the other. Readers
# find the attention, selection, rotary and scan kernels by the substrings
# "flash", "dsa_", "rope" and "ssd": these names must hold none of them
GROUPED_MATMUL = "grouped_matmul"
GROUPED_MATMUL_DW = "grouped_matmul_dw"

# rows a visit takes. A visit multiplies the whole tile whatever part of it
# is the group's. Judged in the step (docs/KERNELS.md): on the nemotron
# cell's groups of ~380 rows 512-row visits read 38.8 % of peak, 256 49.8,
# 128 51.1 (alone on the chip at 1,536 rows a group 0.671, 0.711 and 0.747 ms
# a call); on OLMoE's groups of 4,096 rows and up 512 reads 86.9 % for 256's
# 89.2 and `tokens_per_s` 0.2 % less, on lfm2's 4,096 86.7 for 89.9 and 0.4 %
# less. One constant for every cell
_ROW_TILE = 256
# What a grid step may hold by `_vmem_bytes`' count, and what the call then
# asks of Mosaic (`vmem_limit_bytes`; the chip has 128 MiB, a kernel gets 16
# unasked): a whole weight block of the widest cell (2,688 x 1,856) is
# 10 MB, twice for the pipeline's two buffers. Whole, a group's block is
# fetched once for all its visits; in blocks of 896 of the contracted width
# it streams again every visit, and the step read 45.5 % of peak for 49.8
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 56 * 1024 * 1024
# three quarters of what Mosaic gives unasked: a count within it asks nothing
_VMEM_UNASKED = 12 * 1024 * 1024


def _interpreted() -> bool:
    # not `registry._on_tpu()`: a test that patches it to take the kernel
    # off the chip still needs the kernel interpreted there
    return jax.default_backend() != "tpu"


def _divisors(width):
    """The tiles of a CONTRACTED width, largest first: the width whole (a
    block equal to the dimension) and every multiple of 128 that divides
    it. A tail block there would add what lies past the array to a sum."""
    return [width] + [t for t in range(width - width % LANE, 0, -LANE)
                      if t < width and width % t == 0]


def _cuts(width):
    """The tiles of a width that is WRITTEN, largest first: whole, and every
    multiple of 128 below it (the tail block's columns past the array are
    computed from what the pipeline left there and clipped on the way out)."""
    return [width] + list(range((width - 1) // LANE * LANE, 0, -LANE))


def _vmem_bytes(tm, tc, to, itemsize, dw):
    """Bytes a grid step holds: blocks in and out twice (the pipeline's two
    buffers); forward and dx, rows (tm, tc) against weights (tc, to): the
    float32 product (tm, to) twice (a sum in scratch beside it); dW, (tm,
    tc) and (tm, to) -> (tc, to): the float32 sum and the two masked
    operands."""
    blocks = 2 * (tm * tc + tc * to + tm * to) * itemsize
    if dw:
        return blocks + tc * to * 4 + tm * (tc + to) * itemsize
    return blocks + 2 * tm * to * 4


def _tiles(M, K, N, itemsize):
    """(tm, forward, dx, dW) or None where no choice fits: the row tile, and
    for each product the two widths' tiles (contracted, written; dW: K's,
    N's). Of the pairs whose count fits `_VMEM_BUDGET`: the least work on a
    tail block's padding, then the fewest grid steps, then the wider lanes.
    2,688 x 1,856 in bfloat16 lies whole in forward and dx, and in three
    blocks of 896 x 1,856 (or 1,856 x 896) in dW."""
    tm = min(_ROW_TILE, -(-M // 16) * 16)

    def pick(A, firsts, B, seconds, dw):
        fits = [(-(-A // a) * a * -(-B // b) * b, -a * b, -b, (a, b))
                for a in firsts for b in seconds
                if _vmem_bytes(tm, a, b, itemsize, dw) <= _VMEM_BUDGET]
        return min(fits)[-1] if fits else None

    picks = (pick(K, _divisors(K), N, _cuts(N), False),
             pick(N, _divisors(N), K, _cuts(K), False),
             pick(K, _cuts(K), N, _cuts(N), True))
    return None if None in picks else (tm,) + picks


def _declines(xs, w, mesh=None):
    """Why the kernel does not take the call, or None: the rule without the
    backend."""
    if mesh is not None and mesh.size > 1:
        return "under a mesh (GSPMD cannot partition the custom kernel)"
    if xs.ndim != 2 or w.ndim != 3 or xs.shape[1] != w.shape[1]:
        return f"not (M, K) x (E, K, N): {xs.shape} x {w.shape}"
    if xs.dtype not in (jnp.bfloat16, jnp.float32) or w.dtype != xs.dtype:
        return f"operands {xs.dtype} and {w.dtype}: bfloat16 or float32, alike"
    K, N = w.shape[1:]
    if _tiles(xs.shape[0], K, N, xs.dtype.itemsize) is None:
        return f"no tiles of K {K} and N {N} fit VMEM"
    return None


def takes(xs, w, mesh=None) -> bool:
    """The ONE gating rule: the kernel multiplies xs (M, K) with w (E, K, N)
    on the single-program TPU path, in bfloat16 or float32, at every width
    whose tiles fit `_VMEM_BUDGET`. `ragged_dot` stays what it is for: under
    a mesh (GSPMD cannot partition the custom kernel), off a TPU (interpret
    mode would be slower), an operand type the kernel does not take, no
    tiles that fit."""
    return registry._on_tpu() and _declines(xs, w, mesh) is None


def _visits(group_sizes, M, tm, empties):
    """The grid's tables, made in XLA -> (offsets (E + 1,), group (V,), row
    tile (V,), count ()): group g's rows are [offsets[g], offsets[g + 1]);
    visit v < count multiplies row tile `tile[v]` for group `group[v]`,
    groups in order and a group's tiles in order. A group is visited once a
    row tile it has a row in, an empty one never, or once where `empties`
    (dW writes its zeros). V = tiles of M + E - 1 bounds the count."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if empties else 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(-(-M // tm) + E - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], 1), E - 1)
    tile = first[group] + v - (upto - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group.astype(jnp.int32),
            jnp.clip(tile, 0, -(-M // tm) - 1).astype(jnp.int32), upto[-1])


def _own_rows(offsets, group, tile, v, shape):
    """(rows, width) bool: the rows of visit v's tile that are its group's."""
    g = group[v]
    row = tile[v] * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, group, tile, x_ref, w_ref, o_ref, *acc, steps,
                transposed):
    """A visit's rows (tm, tc) against its group's weights, (tc, to) or
    transposed (to, tc): summed over the `steps` blocks of the contracted
    width in `acc`, or written at once where that is one block. Of the rows
    (tm, to) those of the visit's group are written; the others keep what
    the tile's earlier visit, if any, wrote."""
    v, c = pl.program_id(1), pl.program_id(2)
    part = (_dot_nt if transposed else _dot)(x_ref[...], w_ref[...])

    def write(total):
        own = _own_rows(offsets, group, tile, v, total.shape)
        o_ref[...] = jnp.where(own, total, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)

    if steps == 1:
        write(part)
        return
    acc, = acc

    @pl.when(c == 0)
    def _():
        acc[...] = part

    @pl.when(c > 0)
    def _():
        acc[...] += part

    @pl.when(c == steps - 1)
    def _():
        write(acc[...])


def _asking(count):
    """`CompilerParams` keywords: Mosaic is asked for `_VMEM_LIMIT` only
    where the step's count is over `_VMEM_UNASKED`."""
    return {"vmem_limit_bytes": _VMEM_LIMIT} if count > _VMEM_UNASKED else {}


# `_gmm` and `_dw` are jitted so that a SIGNATURE is traced and lowered once
# a process: a layer calls each product with one signature for `w1` and `w3`,
# again under `remat`, again in every run of layers and in the check's
# programs, and a call's trace (the visits' tables, the kernel's body) is
# ~0.1 s on the chip's host (docs/KERNELS.md). `interpret` is an argument so
# that the backend, which tests patch, is part of the key.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _gmm(xs, w, group_sizes, tm, tc, to, transposed, interpret):
    """Forward, or dx where `transposed`: xs (M, C) against w (E, C, O), or
    (E, O, C) read transposed -> (M, O). Grid (O's tiles, visits, C's
    blocks), the contraction innermost."""
    M, C = xs.shape
    O = w.shape[1] if transposed else w.shape[2]
    steps, tail = divmod(C, tc)
    assert not tail, f"a block of {tc} columns does not divide the {C} summed"
    offsets, group, tile, count = _visits(group_sizes, M, tm, False)
    if transposed:
        w_spec = pl.BlockSpec((None, to, tc),
                              lambda o, v, c, _, g, t: (g[v], o, c))
    else:
        w_spec = pl.BlockSpec((None, tc, to),
                              lambda o, v, c, _, g, t: (g[v], c, o))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, steps=steps, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(O, to), count, steps),
            in_specs=[pl.BlockSpec((tm, tc),
                                   lambda o, v, c, _, g, t: (t[v], c)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, to),
                                   lambda o, v, c, _, g, t: (t[v], o)),
            scratch_shapes=([pltpu.VMEM((tm, to), jnp.float32)]
                            if steps > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((M, O), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            **_asking(_vmem_bytes(tm, tc, to, xs.dtype.itemsize, False))),
        interpret=interpret, name=GROUPED_MATMUL,
    )(offsets, group, tile, xs, w)


def _dw_kernel(offsets, group, tile, x_ref, dy_ref, o_ref, acc):
    """A visit's rows of x (tm, tk) and dy (tm, tn), transposed-multiplied
    into the group's sum (tk, tn), which is written when the next visit is
    another group's. Rows of other groups, of none and past the array are
    zeroed in BOTH operands: what lies there may be NaN, and 0 x NaN is."""
    v = pl.program_id(2)
    g = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v, 1) - 1] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        own = _own_rows(offsets, group, tile, v, dy_ref.shape)
        dy = jnp.where(own, dy_ref[...], jnp.zeros_like(dy_ref))
        own = _own_rows(offsets, group, tile, v, x_ref.shape)
        x = jnp.where(own, x_ref[...], jnp.zeros_like(x_ref))
        acc[...] += _dot_tn(x, dy)

    last = pl.num_programs(2) - 1

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _dw(xs, dy, group_sizes, tm, tk, tn, interpret):
    """dW: xs (M, K) and dy (M, N) -> (E, K, N), group g's the product of
    its rows. Grid (K's tiles, N's tiles, visits), a group's visits in a
    row."""
    (M, K), N = xs.shape, dy.shape[1]
    E = group_sizes.shape[0]
    offsets, group, tile, count = _visits(group_sizes, M, tm, True)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(K, tk), pl.cdiv(N, tn), count),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda k, n, v, _, g, t: (t[v], k)),
                      pl.BlockSpec((tm, tn),
                                   lambda k, n, v, _, g, t: (t[v], n))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k, n, v, _, g, t: (g[v], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((E, K, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_asking(_vmem_bytes(tm, tk, tn, xs.dtype.itemsize, True))),
        interpret=interpret, name=GROUPED_MATMUL_DW,
    )(offsets, group, tile, xs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(xs, w, group_sizes, tiles):
    tm, (tc, to) = tiles[0], tiles[1]
    return _gmm(xs, w, group_sizes, tm, tc, to, False, _interpreted())


def _grouped_matmul_fwd(xs, w, group_sizes, tiles):
    return _grouped_matmul(xs, w, group_sizes, tiles), (xs, w, group_sizes)


def _grouped_matmul_bwd(tiles, saved, dy):
    xs, w, group_sizes = saved
    tm, _, (tc, to), (tk, tn) = tiles
    interpret = _interpreted()
    return (_gmm(dy, w, group_sizes, tm, tc, to, True, interpret),
            _dw(xs, dy, group_sizes, tm, tk, tn, interpret), None)


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(xs, w, group_sizes, mesh=None, tiles=None):
    """``jax.lax.ragged_dot(xs, w, group_sizes,
    preferred_element_type=xs.dtype)`` by the kernels, differentiable in xs
    and w: xs (M, K) rows sorted by group, w (E, K, N), group_sizes (E,)
    int. `tiles` (`_tiles`' form) is for tests and measurements; `mesh` is
    the rule's and is not read here."""
    del mesh
    M, (_, K, N) = xs.shape[0], w.shape
    return _grouped_matmul(xs, w, group_sizes,
                           tiles or _tiles(M, K, N, xs.dtype.itemsize))


def ragged_dot(xs, w, group_sizes, mesh=None):
    """The fallback, the CPU path, the mesh path and the oracle: the
    compiler's own grouped matmul."""
    del mesh
    return jax.lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=xs.dtype)


def _eligible(xs, w, group_sizes, mesh=None):
    reason = _declines(xs, w, mesh)
    return reason is None, reason


registry.register_kernel(GROUPED_MATMUL, pallas_fn=grouped_matmul,
                         xla_fallback=ragged_dot, eligibility=_eligible)
