"""The flash kernels' (batch, seq, heads*head_dim) contract (interpret mode)
at the tiles `_choose_tiles` picks: heads a grid step, column blocks a row,
one fused array or three. A table over `flash_harness.check`."""
import pytest

from flash_harness import DTYPES, chosen_case, check
from hetu_tpu.kernels import flash_attention as fa


# heads, head_dim, seq -> heads a grid step (and so column blocks a row)
@DTYPES
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "three"])
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False)])
@pytest.mark.parametrize("h,d,s,group", [
    (2, 64, 128, {2}), (4, 64, 256, {4}), (12, 64, 128, {4, 12}),
    (12, 64, 512, {2, 4, 6}), (4, 128, 512, {1, 2, 4}), (3, 64, 128, {3}),
    (12, 64, 1024, {2, 4}), (2, 128, 256, {1, 2})],
    ids=lambda x: str(x).replace(", ", "or").strip("{}"))
def test_flash_btd_layout_matches_reference(h, d, s, group, causal, bias,
                                            fused, dtype, tol_fwd, tol_bwd):
    """The (batch, seq, heads*head_dim) contract: 2, 4 and 12 heads of 64 a
    grid step, head size 128, more than one column block a row (12 heads in
    three blocks or in two, by dtype and mask; 4 heads of 128 in two or in
    one), q, k and v read out of one fused array or out of three. Three
    heads of 64 are 192 lanes, not whole tiles: given fused, they are cut
    in three first."""
    assert set(fa._choose_tiles(s, d, dtype, causal, h)[2].values()) <= group
    check(chosen_case(s, d, causal, bias, dtype, b=2 if s < 512 else 1, h=h),
          fused, tol_fwd, tol_bwd, against_oracle=False)
