"""The grouped matmul's Mosaic kernels (`hetu_tpu/kernels/grouped_matmul.py`)
in interpret mode on the CPU against `jax.lax.ragged_dot`: one table of calls
for each of the three products (forward, dx, dW), the grid's tables against a
count by hand, the tiles the widths get, the one gating rule as a table over
the six expert cells' widths, and the path `transformer._grouped_matmul`
takes by what the rule says. What the chip's compiler makes of the kernels at
the nemotron cell's calls is in `tests/test_flash_compile_v5e.py`."""
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
WHOLE = (32, (K, N), (N, K), (K, N))
# the contraction in blocks of one lane tile (a sum in scratch); the written
# width cut at a lane tile, so N's second block is a tail: 64 columns of 128
CUT = (32, (128, 128), (N, 128), (128, 128))
CHOSEN = None       # `_tiles`' own: one row tile of all 96 rows, widths whole

# group sizes (rows past their sum belong to no group), tiles, dtype
CASES = [
    pytest.param((10, 50, 20, 16), WHOLE, jnp.float32,
                 id="edges-inside-row-tiles"),
    pytest.param((10, 50, 20, 16), CUT, jnp.float32,
                 id="edges-inside-row-tiles.cut"),
    pytest.param((32, 0, 40, 24), WHOLE, jnp.float32, id="an-empty-group"),
    pytest.param((0, 0, 70, 0), CUT, jnp.float32,
                 id="empty-groups-first-and-last.cut"),
    pytest.param((0, 96, 0, 0), WHOLE, jnp.float32,
                 id="all-rows-in-one-group"),
    pytest.param((0, 0, 0, 0), WHOLE, jnp.float32, id="no-row-in-any-group"),
    pytest.param((0, 0, 0, 0), CUT, jnp.float32,
                 id="no-row-in-any-group.cut"),
    pytest.param((7, 9, 1, 30), WHOLE, jnp.float32,
                 id="half-the-rows-past-the-groups"),
    pytest.param((33, 31, 1, 0), CUT, jnp.float32,
                 id="a-row-tile-of-three-groups.cut"),
    pytest.param((24, 24, 24, 24), CHOSEN, jnp.float32, id="chosen-tiles"),
    pytest.param((10, 50, 20, 16), WHOLE, jnp.bfloat16, id="bfloat16"),
    pytest.param((7, 9, 1, 30), CUT, jnp.bfloat16, id="bfloat16.cut"),
    pytest.param((12, 0, 40, 3), CHOSEN, jnp.bfloat16,
                 id="bfloat16.chosen-tiles"),
]


@functools.lru_cache(maxsize=None)
def _products(sizes, tiles, dtype):
    """-> (held, the kernels' (y, dx, dW), `ragged_dot`'s): seeded operands
    whose rows PAST the groups are NaN, in xs and in y's cotangent, for the
    kernels; the oracle gets zeros there (the rows are no group's, so its
    dW sees nothing of them either way)."""
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


@pytest.mark.parametrize("sizes,tiles,dtype", CASES)
def test_forward_is_ragged_dot_inside_the_groups(sizes, tiles, dtype):
    held, got, want = _products(sizes, tiles, dtype)
    _close(got[0][:held], want[0][:held], dtype)


@pytest.mark.parametrize("sizes,tiles,dtype", CASES)
def test_dx_is_ragged_dots_inside_the_groups(sizes, tiles, dtype):
    """The weights read transposed in the kernel; a NaN cotangent past the
    groups changes no row inside them."""
    held, got, want = _products(sizes, tiles, dtype)
    _close(got[1][:held], want[1][:held], dtype)


@pytest.mark.parametrize("sizes,tiles,dtype", CASES)
def test_dw_is_ragged_dots_and_finite(sizes, tiles, dtype):
    """Every group's matrix, an empty group's zeros among them, with NaN in
    both operands' rows past the groups."""
    _, got, want = _products(sizes, tiles, dtype)
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


# the six expert cells' (hidden, expert) widths: w1 is (K, N) = (D, F), w2
# (F, D), and dx and dW of both are calls of the same two widths
CELLS = {"olmoe-1b-7b": (2048, 1024), "lfm2-8b-a1b": (2048, 1792),
         "kanana-2-30b-a3b": (2048, 768), "keye-vl-2.0-30b-a3b": (2048, 768),
         "laguna-xs.2": (2048, 512),
         "nemotron-twotower-30b-a3b": (2688, 1856)}
RULE = [pytest.param(D, F, name.startswith("nemotron"), id=f"{name}.{which}")
        for name, widths in CELLS.items()
        for which, (D, F) in (("w1", widths), ("w2", widths[::-1]))]


def _call(K_, N_, dtype=jnp.bfloat16, M_=4096, E=8):
    return (jax.ShapeDtypeStruct((M_, K_), dtype),
            jax.ShapeDtypeStruct((E, K_, N_), dtype))


@pytest.mark.parametrize("K_,N_,engages", RULE)
def test_only_the_nemotron_cells_widths_engage(monkeypatch, K_, N_, engages):
    """The compiler's tile rule, the largest of 512 / 256 / 128 that divides
    a width: the kernel takes a call where that is ONE lane tile for K or
    for N. Under a mesh and off a TPU never."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    assert gmm.takes(*_call(K_, N_)) is engages
    assert gmm.takes(*_call(K_, N_, jnp.float32)) is engages
    mesh = meshlib.make_mesh(dp=2, devices=jax.devices()[:2])
    assert not gmm.takes(*_call(K_, N_), mesh)
    assert gmm.takes(*_call(K_, N_), meshlib.make_mesh(
        dp=1, devices=jax.devices()[:1])) is engages
    monkeypatch.setattr(registry, "_on_tpu", lambda: False)
    assert not gmm.takes(*_call(K_, N_))


@pytest.mark.parametrize("K_,N_,dtype,engages", [
    (2688, 2048, jnp.bfloat16, True),      # K alone of one lane tile
    (3072, 1856, jnp.bfloat16, True),      # N alone
    (3072, 2048, jnp.bfloat16, False), (2560, 1792, jnp.bfloat16, False),
    (2688, 1856, jnp.float16, False), (384, 192, jnp.float32, True)])
def test_rule_by_width_and_dtype(monkeypatch, K_, N_, dtype, engages):
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    assert gmm.takes(*_call(K_, N_, dtype)) is engages


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
    (True, "auto", 512, 256, False, "fallback", False),
    (True, "auto", 384, 192, True, "fallback", False),
    (False, "auto", 384, 192, False, "fallback", False),
    (True, "off", 384, 192, False, "off", False),
    (False, "force", 384, 192, False, "forced", True)])
def test_the_experts_call_takes_the_path_the_rule_names(
        monkeypatch, on_tpu, mode, K_, N_, meshed, path, kernel):
    """One call site, one dispatch: the kernel on a TPU at widths of one
    lane tile, `ragged_dot` at the others, under a mesh, off a TPU and with
    the tier off; the tier's counter says which."""
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
