"""The flash kernels under a selection that differs by QUERY ROW (learned
sparse attention: a query keeps the keys its indexer ranks highest), forward
and the ONE backward kernel of the call, one tile and many, the kept set
handed over one bit a pair (`pack_row_mask`), against the same oracle and the
same unfused reference as every other case, here under the boolean mask.
Cases over `flash_harness.check`, not a harness of their own."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_harness import DTYPES, Case, check, make, to_heads
from hetu_tpu.kernels import flash_attention as fa


# heads, q/k width, v/o width, seq, block_q, block_k, heads a step, fused,
# keys a query keeps
@DTYPES
@pytest.mark.parametrize("h,d,dv,s,block_q,block_k,group,fused,rows", [
    (3, 64, 64, 128, None, None, None, False, 16),      # one tile
    (2, 128, 128, 256, None, None, None, True, 40),     # one tile, fused qkv
    (2, 64, 64, 256, 128, 128, 2, False, 32),   # two key blocks, one plane
    (4, 64, 64, 512, 128, 64, 2, True, 100),    # block_q != block_k
    (2, 192, 128, 256, 128, 128, 2, False, 64),     # two head widths
    (2, 128, 128, 1024, 128, 128, 1, False, 200),   # two planes of 512
    (2, 128, 128, 2048, 128, 128, 2, False, 300)],  # four planes
    ids=lambda x: str(x))
def test_kernels_under_a_selection_by_query_row(
        h, d, dv, s, block_q, block_k, group, fused, rows, dtype, tol_fwd,
        tol_bwd):
    """`flash_fwd` and `flash_bwd` / `flash_bwd_dqkv` (interpret mode) read
    the kept set from a packed pair, the forward by query and the backward by
    key: output and gradients equal the reference's under the boolean mask
    and the blockwise oracle's, at the tolerances of the unmasked cases."""
    tiles = None if block_q is None else (block_q, block_k, group)
    case = Case(h, d, dv, s, 2 if s < 2048 else 1, True, None, dtype,
                seed=s + d + rows, qk_std=1.0, rows=rows)
    kept = fa.unpack_row_mask(make(case).row_mask[0])
    assert int(kept[0, -1].sum()) == rows and bool(
        (kept.sum(-1) == np.minimum(np.arange(s) + 1, rows)).all())
    check(case, fused, tol_fwd, tol_bwd, tiles=tiles,
          kernel=fa.FLASH_BWD if tiles is None else fa.FLASH_BWD_DQKV)


def test_the_causal_triangle_as_a_row_mask_is_the_causal_call_to_the_bit():
    """A selection that keeps every key a query sees computes what
    `causal=True` computes, forward and backward, bit for bit; the entry
    returns the row statistic too, which has no gradient."""
    case = Case(2, 64, 64, 256, 2, True, None, jnp.float32, seed=11)
    x = make(case)
    keep = jnp.broadcast_to(jnp.tril(jnp.ones((256, 256), bool)),
                            (2, 256, 256))
    pair = fa.pack_row_mask(keep)
    plain, vjp = jax.vjp(lambda *qkv: fa.flash_attention_btd(
        qkv, 2, block_q=128, block_k=128), x.q, x.k, x.v)
    (out, lse), vjp_rows = jax.vjp(lambda *qkv: fa.flash_attention_btd(
        qkv, 2, block_q=128, block_k=128, row_mask=pair), x.q, x.k, x.v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    for a, b in zip(vjp_rows((x.do, jnp.ones_like(lse))), vjp(x.do)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = jnp.einsum("bhqd,bhkd->bhqk", to_heads(x.q, 2), to_heads(x.k, 2)) / 8
    want = jax.nn.logsumexp(jnp.where(keep[:, None], s, -jnp.inf), -1)
    np.testing.assert_allclose(np.asarray(lse).reshape(2, 2, 256),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s,planes", [(64, 1), (512, 1), (1024, 2),
                                      (4096, 8), (16384, 32), (1536, 1)])
def test_mask_planes_are_whole_blocks(s, planes):
    assert fa.mask_planes(s) == planes
    assert (s // planes) % min(512, s) == 0
