"""The grouped matmul's Mosaic kernels (`hetu_tpu/kernels/grouped_matmul.py`)
in interpret mode on the CPU against `jax.lax.ragged_dot`: one table of calls
for each of the three products (forward, dx, dW), the grid's tables against a
count by hand, the tiles the widths get, the one gating rule as a table over
the six expert cells' calls, and the path `transformer._grouped_matmul`
takes by what the rule says. What the chip's compiler makes of the kernels at
the six cells' calls is in `tests/test_flash_compile_v5e.py`."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import grouped_matmul as gmm
from hetu_tpu.kernels import registry
from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib

# K of an odd number of lane tiles, N of one and a half
K, N, M = 384, 192, 96
SMALL = (M, K, N)
WHOLE = (32, (K, N), (N, K), (K, N))
# the contraction in blocks of one lane tile (a sum in scratch); the written
# width cut at a lane tile, so N's second block is a tail: 64 columns of 128
CUT = (32, (128, 128), (N, 128), (128, 128))
CHOSEN = None       # `_tiles`' own: at SMALL one row tile of all 96 rows,
                    # at W1 and W2 row tiles of 256; the widths whole

# group sizes (rows past their sum belong to no group), tiles, dtype, and
# the call's (rows, K, N)
CASES = [
    pytest.param((10, 50, 20, 16), WHOLE, jnp.float32, SMALL,
                 id="edges-inside-row-tiles"),
    pytest.param((10, 50, 20, 16), CUT, jnp.float32, SMALL,
                 id="edges-inside-row-tiles.cut"),
    pytest.param((32, 0, 40, 24), WHOLE, jnp.float32, SMALL,
                 id="an-empty-group"),
    pytest.param((0, 0, 70, 0), CUT, jnp.float32, SMALL,
                 id="empty-groups-first-and-last.cut"),
    pytest.param((0, 96, 0, 0), WHOLE, jnp.float32, SMALL,
                 id="all-rows-in-one-group"),
    pytest.param((0, 0, 0, 0), WHOLE, jnp.float32, SMALL,
                 id="no-row-in-any-group"),
    pytest.param((0, 0, 0, 0), CUT, jnp.float32, SMALL,
                 id="no-row-in-any-group.cut"),
    pytest.param((7, 9, 1, 30), WHOLE, jnp.float32, SMALL,
                 id="half-the-rows-past-the-groups"),
    pytest.param((33, 31, 1, 0), CUT, jnp.float32, SMALL,
                 id="a-row-tile-of-three-groups.cut"),
    pytest.param((24, 24, 24, 24), CHOSEN, jnp.float32, SMALL,
                 id="chosen-tiles"),
    pytest.param((10, 50, 20, 16), WHOLE, jnp.bfloat16, SMALL, id="bfloat16"),
    pytest.param((7, 9, 1, 30), CUT, jnp.bfloat16, SMALL, id="bfloat16.cut"),
    pytest.param((12, 0, 40, 3), CHOSEN, jnp.bfloat16, SMALL,
                 id="bfloat16.chosen-tiles"),
]


# what the nemotron-shaped cases do not reach: widths of whole 256- and
# 512-column tiles, which the compiler's kernel tiled by 256 and 512 and the
# rule left to it before PR 59, with 64 groups, a group across many row
# tiles, runs of empty groups and a share's rows past the groups
W1, W2 = (1024, 512, 256), (1024, 256, 512)
# OLMoE-like: 64 experts, every row held, the fullest group 7 x the mean
# and across four row tiles, several experts with no row
OLMOE_LIKE = (700, 0, 0, 3, 17, 0, 40, 8) + (4,) * 56 + (32,)
# share-like: 8 held experts' rows first, three quarters of the rows in no
# group; and 64 experts of which most hold nothing
SHARE_LIKE = (40, 0, 70, 33, 0, 1, 90, 22)
SPARSE_64 = (0,) * 20 + (300,) + (0,) * 20 + (5,) * 22 + (0,)
CASES += [
    pytest.param(OLMOE_LIKE, CHOSEN, jnp.bfloat16, W1, id="olmoe-like.w1"),
    pytest.param(OLMOE_LIKE, CHOSEN, jnp.bfloat16, W2, id="olmoe-like.w2"),
    pytest.param(OLMOE_LIKE, CHOSEN, jnp.float32, W1,
                 id="olmoe-like.w1.float32"),
    pytest.param(SHARE_LIKE, CHOSEN, jnp.bfloat16, W1, id="share-like.w1"),
    pytest.param(SHARE_LIKE, CHOSEN, jnp.bfloat16, W2, id="share-like.w2"),
    pytest.param(SPARSE_64, CHOSEN, jnp.bfloat16, W2,
                 id="64-experts-most-empty-rows-past.w2"),
    pytest.param(SPARSE_64, CHOSEN, jnp.float32, W1,
                 id="64-experts-most-empty-rows-past.w1.float32"),
]


@functools.lru_cache(maxsize=None)
def _products(sizes, tiles, dtype, shape):
    """-> (held, the kernels' (y, dx, dW), `ragged_dot`'s): seeded operands
    whose rows PAST the groups are NaN, in xs and in y's cotangent, for the
    kernels; the oracle gets zeros there (the rows are no group's, so its
    dW sees nothing of them either way)."""
    M, K, N = shape
    ks = jax.random.split(jax.random.PRNGKey(len(sizes) + sum(sizes)), 3)
    xs = jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (len(sizes), K, N), jnp.float32).astype(dtype)
    ct = jax.random.normal(ks[2], (M, N), jnp.float32).astype(dtype)
    group_sizes, held = jnp.asarray(sizes, jnp.int32), sum(sizes)
    past = (jnp.arange(M) >= held)[:, None]

    def all_three(fn, fill):
        a, c = (jnp.where(past, fill, t).astype(dtype) for t in (xs, ct))
        y, pull = jax.vjp(lambda a, b: fn(a, b, group_sizes), a, w)
        return (y,) + pull(c)

    kernels = functools.partial(gmm.grouped_matmul, tiles=tiles)
    return (held, jax.jit(functools.partial(all_three, kernels, jnp.nan))(),
            jax.jit(functools.partial(all_three, gmm.ragged_dot, 0.0))())


def _close(got, want, dtype):
    """Within float32's sum order, or within a bfloat16 rounding of a result
    both sides sum in float32."""
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    assert np.isfinite(got).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    else:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("sizes,tiles,dtype,shape", CASES)
def test_forward_is_ragged_dot_inside_the_groups(sizes, tiles, dtype, shape):
    held, got, want = _products(sizes, tiles, dtype, shape)
    _close(got[0][:held], want[0][:held], dtype)


@pytest.mark.parametrize("sizes,tiles,dtype,shape", CASES)
def test_dx_is_ragged_dots_inside_the_groups(sizes, tiles, dtype, shape):
    """The weights read transposed in the kernel; a NaN cotangent past the
    groups changes no row inside them."""
    held, got, want = _products(sizes, tiles, dtype, shape)
    _close(got[1][:held], want[1][:held], dtype)


@pytest.mark.parametrize("sizes,tiles,dtype,shape", CASES)
def test_dw_is_ragged_dots_and_finite(sizes, tiles, dtype, shape):
    """Every group's matrix, an empty group's zeros among them, with NaN in
    both operands' rows past the groups."""
    _, got, want = _products(sizes, tiles, dtype, shape)
    _close(got[2], want[2], dtype)
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[2][g], np.float32).any()


@pytest.mark.parametrize("empties", [False, True], ids=["forward", "dw"])
@pytest.mark.parametrize("sizes,rows,tm", [
    ((10, 50, 20, 16), 96, 32), ((32, 0, 40, 24), 96, 32),
    ((0, 0, 0, 0), 96, 32), ((0, 96, 0, 0), 96, 32), ((0, 0, 70, 0), 96, 32),
    ((33, 31, 1, 0), 96, 32), ((5, 5, 5), 40, 16), ((100,), 100, 16)])
def test_visits_are_the_pairs_of_row_tile_and_group(sizes, rows, tm, empties):
    """By hand: a visit for every (group, row tile) that share a row, groups
    in order and tiles in order; an empty group once where dW asks."""
    want, start = [], 0
    for g, size in enumerate(sizes):
        tiles = sorted({r // tm for r in range(start, start + size)})
        if not tiles and empties:
            tiles = [min(start // tm, -(-rows // tm) - 1)]
        want += [(g, t) for t in tiles]
        start += size
    offsets, group, tile, count = gmm._visits(
        jnp.asarray(sizes, jnp.int32), rows, tm, empties)
    assert group.shape == tile.shape == (-(-rows // tm) + len(sizes) - 1,)
    assert int(count) == len(want)
    assert list(zip(group[:len(want)].tolist(),
                    tile[:len(want)].tolist())) == want
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    # past the count the tables still index the arrays (the pipeline may
    # look one visit ahead)
    assert 0 <= int(group.min()) and int(group.max()) < len(sizes)
    assert 0 <= int(tile.min()) and int(tile.max()) < -(-rows // tm)


def test_tiles_at_the_nemotron_cells_widths():
    """Both matrices lie whole in forward and dx (a 10 MB block, twice for
    the pipeline), so a visit is ONE grid step; a width that no count fits
    gets none."""
    tm, fwd, dx, dw = gmm._tiles(49152, 2688, 1856, 2)
    assert (tm, fwd, dx) == (256, (2688, 1856), (1856, 2688))
    assert gmm._tiles(49152, 1856, 2688, 2)[1:3] == ((1856, 2688),
                                                      (2688, 1856))
    for tk, tn in (dw, gmm._tiles(49152, 1856, 2688, 2)[3]):
        assert gmm._vmem_bytes(tm, tk, tn, 2, True) <= gmm._VMEM_BUDGET
    assert gmm._tiles(8, 384, 192, 4)[0] == 16
    # a contracted width with no lane-tile divisor is taken whole or not at all
    assert gmm._divisors(1856) == [1856]
    assert gmm._divisors(2688) == [2688, 896, 384, 128]
    assert gmm._tiles(4096, 100_000 * 128 + 64, 192, 4) is None


# the six expert cells' calls: rows (tokens x picks), experts a call sees,
# and the (hidden, expert) widths: w1 is (K, N) = (D, F), w2 (F, D), and dx
# and dW of both are calls of the same two widths
CELLS = {"olmoe-1b-7b": (262144, 64, (2048, 1024)),
         "lfm2-8b-a1b": (131072, 8, (2048, 1792)),
         "kanana-2-30b-a3b": (196608, 16, (2048, 768)),
         "keye-vl-2.0-30b-a3b": (262144, 16, (2048, 768)),
         "laguna-xs.2": (131072, 32, (2048, 512)),
         "nemotron-twotower-30b-a3b": (49152, 8, (2688, 1856))}
RULE = [pytest.param(rows, E, D, F, id=f"{name}.{which}")
        for name, (rows, E, widths) in CELLS.items()
        for which, (D, F) in (("w1", widths), ("w2", widths[::-1]))]


def _call(K_, N_, dtype=jnp.bfloat16, M_=4096, E=8):
    return (jax.ShapeDtypeStruct((M_, K_), dtype),
            jax.ShapeDtypeStruct((E, K_, N_), dtype))


@pytest.mark.parametrize("rows,E,K_,N_", RULE)
def test_every_expert_cells_call_engages_on_a_tpu_in_one_program(
        monkeypatch, rows, E, K_, N_):
    """On a TPU, in one program, every cell's `w1` and `w2` call goes to the
    kernels, whatever the compiler would tile the widths by, with the
    weights' block whole in forward and dx; under a mesh and off a TPU none
    does."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    call = _call(K_, N_, M_=rows, E=E)
    assert gmm.takes(*call)
    assert gmm.takes(*_call(K_, N_, jnp.float32, rows, E))
    assert gmm._tiles(rows, K_, N_, 2)[:3] == (256, (K_, N_), (N_, K_))
    mesh = meshlib.make_mesh(dp=2, devices=jax.devices()[:2])
    assert not gmm.takes(*call, mesh)
    assert "under a mesh" in gmm._declines(*call, mesh)
    assert gmm.takes(*call, meshlib.make_mesh(
        dp=1, devices=jax.devices()[:1]))
    monkeypatch.setattr(registry, "_on_tpu", lambda: False)
    assert not gmm.takes(*call)


def _operands(xs_shape, w_shape, xs_dtype=jnp.bfloat16, w_dtype=None):
    return (jax.ShapeDtypeStruct(xs_shape, xs_dtype),
            jax.ShapeDtypeStruct(w_shape, w_dtype or xs_dtype))


@pytest.mark.parametrize("call,declines", [
    # no width condition: one lane tile for K, for N, for neither
    (_call(2688, 2048), None), (_call(3072, 1856), None),
    (_call(3072, 2048), None), (_call(2560, 1792), None),
    # float32 as bfloat16 (docs/KERNELS.md has the call timed on the chip)
    (_call(384, 192, jnp.float32), None),
    (_call(2048, 1024, jnp.float32, 262144, 64), None),
    # the operand types and shapes the kernel does not take
    (_call(2688, 1856, jnp.float16), "bfloat16 or float32, alike"),
    (_operands((4096, 512), (8, 512, 256), jnp.bfloat16, jnp.float32),
     "bfloat16 or float32, alike"),
    (_operands((4096, 512), (8, 384, 256)), "not (M, K) x (E, K, N)"),
    (_operands((4, 4096, 512), (8, 512, 256)), "not (M, K) x (E, K, N)"),
    # a contracted width with no lane-tile divisor that VMEM cannot hold whole
    (_call(100_000 * 128 + 64, 192, jnp.float32), "fit VMEM"),
    (_call(100_000 * 128 + 64, 2048), "fit VMEM")])
def test_rule_by_dtype_shape_and_vmem(monkeypatch, call, declines):
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    reason = gmm._declines(*call)
    assert gmm.takes(*call) is (declines is None)
    assert (reason is None) if declines is None else (declines in reason)
    assert gmm._eligible(*call, None) == (declines is None, reason)


def _served(monkeypatch, on_tpu, mode, K_, N_, mesh=None):
    """-> (the paths `registry.dispatch` counted, the primitives of the
    traced call) of `transformer._grouped_matmul` at (96, K_) x (4, K_, N_)."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: on_tpu)
    registry.reset_stats()
    xs = jnp.ones((96, K_), jnp.bfloat16)
    w = jnp.ones((4, K_, N_), jnp.float32)      # the master weights: cast here
    sizes = jnp.asarray((10, 50, 20, 16), jnp.int32)
    with registry.active(mode):
        jaxpr = jax.make_jaxpr(
            lambda a, b: tfm._grouped_matmul(a, b, sizes, mesh))(xs, w)
    paths = {path for (name, path), n in registry.dispatch_stats().items()
             if name == gmm.GROUPED_MATMUL and n}
    return paths, {str(e.primitive) for e in jaxpr.jaxpr.eqns}


@pytest.mark.parametrize("on_tpu,mode,K_,N_,meshed,path,kernel", [
    (True, "auto", 384, 192, False, "pallas", True),
    (True, "auto", 512, 256, False, "pallas", True),
    (True, "auto", 384, 192, True, "fallback", False),
    (False, "auto", 384, 192, False, "fallback", False),
    (True, "off", 384, 192, False, "off", False),
    (False, "force", 384, 192, False, "forced", True)])
def test_the_experts_call_takes_the_path_the_rule_names(
        monkeypatch, on_tpu, mode, K_, N_, meshed, path, kernel):
    """One call site, one dispatch: the kernel on a TPU at any width,
    `ragged_dot` under a mesh, off a TPU and with the tier off; the tier's
    counter says which."""
    mesh = (meshlib.make_mesh(dp=2, devices=jax.devices()[:2]) if meshed
            else None)
    paths, primitives = _served(monkeypatch, on_tpu, mode, K_, N_, mesh)
    assert paths == {path}
    if kernel:
        assert "custom_vjp_call" in primitives and (
            "ragged_dot_general" not in primitives)
    else:
        assert "ragged_dot_general" in primitives and (
            "custom_vjp_call" not in primitives)


def test_fallback_is_the_expression_it_was(monkeypatch):
    """Off a TPU the call traces to the very equations the parent's did: the
    weights' cast and one `ragged_dot` with the operands' dtype preferred."""
    xs, w = jnp.ones((96, 512), jnp.bfloat16), jnp.ones((4, 512, 256))
    sizes = jnp.asarray((10, 50, 20, 16), jnp.int32)
    was = jax.make_jaxpr(lambda a, b: jax.lax.ragged_dot(
        a, b.astype(a.dtype), sizes, preferred_element_type=a.dtype))(xs, w)
    now = jax.make_jaxpr(lambda a, b: tfm._grouped_matmul(a, b, sizes))(xs, w)
    assert str(now) == str(was)
