"""The flash kernels under a SLIDING WINDOW (query t keeps the keys t - W < s
<= t), forward and the ONE backward kernel of the call, one tile and many,
against the same oracle and the same unfused reference as every other case,
the reference under the window's boolean mask. Cases over
`flash_harness.check`, not a harness of their own; and the loops' bounds
against the closed form of the pairs a window keeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_harness import DTYPES, Case, check, make
from hetu_tpu.kernels import flash_attention as fa


# heads, q/k width, v/o width, seq, block_q, block_k, heads a step, fused,
# window, key bias
@DTYPES
@pytest.mark.parametrize("h,d,dv,s,block_q,block_k,group,fused,window,bias", [
    (3, 64, 64, 128, None, None, None, False, 16, False),   # one tile
    (2, 128, 128, 256, None, None, None, True, 100, True),  # one tile, bias
    (2, 64, 64, 256, 128, 128, 2, False, 32, False),    # smaller than a tile
    (2, 64, 64, 512, 128, 128, 2, True, 128, False),    # equal to a tile
    (2, 64, 64, 512, 128, 128, 1, False, 300, False),   # larger than a tile
    (4, 64, 64, 512, 128, 64, 2, True, 100, False),     # block_q != block_k
    (4, 64, 64, 512, 64, 128, 4, False, 100, True),     # the other way, bias
    (2, 192, 128, 256, 128, 128, 2, False, 64, False),  # two head widths
    (2, 128, 128, 2048, 128, 128, 1, False, 512, False),    # sixteen blocks
    (2, 128, 128, 1024, 512, 512, 1, False, 512, False)],   # the cell's tiles
    ids=lambda x: str(x))
def test_kernels_under_a_sliding_window(h, d, dv, s, block_q, block_k, group,
                                        fused, window, bias, dtype, tol_fwd,
                                        tol_bwd):
    """`flash_fwd` and `flash_bwd` / `flash_bwd_dqkv` (interpret mode) with a
    window: output and gradients equal the reference's under the boolean mask
    and the blockwise oracle's, at the tolerances of the cases without."""
    tiles = None if block_q is None else (block_q, block_k, group)
    case = Case(h, d, dv, s, 2 if s < 2048 else 1, True,
                "tail" if bias else None, dtype, seed=s + d + window,
                qk_std=1.0, window=window)
    check(case, fused, tol_fwd, tol_bwd, tiles=tiles,
          kernel=fa.FLASH_BWD if tiles is None else fa.FLASH_BWD_DQKV)


def test_a_window_of_the_whole_sequence_is_the_causal_call_to_the_bit():
    case = Case(2, 64, 64, 256, 2, True, None, jnp.float32, seed=11)
    x = make(case)
    plain, vjp = jax.vjp(lambda *qkv: fa.flash_attention_btd(
        qkv, 2, block_q=128, block_k=128), x.q, x.k, x.v)
    out, vjp_w = jax.vjp(lambda *qkv: fa.flash_attention_btd(
        qkv, 2, block_q=128, block_k=128, window=256), x.q, x.k, x.v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    for a, b in zip(vjp_w(x.do), vjp(x.do)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kw", [dict(causal=False), dict(window=0),
                                dict(row_mask=(None, None))])
def test_a_window_is_a_causal_calls(kw):
    x = make(Case(2, 64, 64, 128, 1, True, None, jnp.float32, seed=3))
    with pytest.raises(ValueError, match="window="):
        fa.flash_attention_btd((x.q, x.k, x.v), 2, **{"window": 16, **kw})


@pytest.mark.parametrize("s,window,block_q,block_k", [
    (2048, 512, 512, 512), (2048, 512, 256, 256), (1024, 300, 256, 128),
    (1024, 64, 128, 256), (512, 512, 128, 128), (512, 1, 128, 128),
    (16384, 512, 512, 512)])
def test_the_loops_visit_every_kept_pair_and_no_tile_without_one(
        s, window, block_q, block_k):
    """`window_bounds`, the bounds the kernels' loops run to: every tile
    with a kept pair is visited, forward and backward, and a visited tile
    holds one (no key block wholly outside every row's window of its query
    block); the two walks visit the same tiles where the blocks are the
    same."""
    pos = np.arange(s)
    keep = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None]
                                             - window)
    assert keep.sum() == sum(min(t + 1, window) for t in range(s))
    tiles = keep.reshape(s // block_q, block_q, s // block_k, block_k).any(
        (1, 3))
    fwd, bwd = fa.window_bounds(s, window, block_q, block_k)
    seen = np.zeros_like(tiles)
    for i, (lo, hi) in enumerate(fwd):
        seen[i, lo:hi] = True
    np.testing.assert_array_equal(seen, tiles)
    seen = np.zeros_like(tiles)
    for j, (lo, hi) in enumerate(bwd):
        seen[lo:hi, j] = True
    np.testing.assert_array_equal(seen, tiles)
    if (s, window, block_q) == (16384, 512, 512):
        # the cell's: two key blocks a query block but the first
        assert tiles.sum() == 2 * 32 - 1
