"""`flash_bwd`, the ONE backward kernel of a sequence of one tile (interpret
mode), heads a step forced. A table over `flash_harness.check`; a file of its
own because a FILE is what `--dist loadfile` hands a worker (ISSUE 42)."""
import pytest

from flash_harness import DTYPES, chosen_case, check
from hetu_tpu.kernels import flash_attention as fa


@DTYPES
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "three"])
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False)])
@pytest.mark.parametrize("h,d,s,group", [
    (2, 64, 128, 2), (4, 64, 256, 4), (12, 64, 128, 12), (12, 64, 512, 4),
    (12, 64, 512, 2), (4, 128, 128, 4), (2, 128, 512, 1), (4, 128, 256, 2)],
    ids=lambda x: str(x))
def test_one_tile_backward_is_one_kernel(h, d, s, group, causal, bias, fused,
                                         dtype, tol_fwd, tol_bwd):
    """`flash_bwd` (interpret mode) with `group` heads a step against the
    XLA blockwise backward and the autodiff of the unfused reference, at the
    tolerances the two-kernel path has: one pallas_call under that name, the
    gradient in the form qkv came in, a fully padded batch row finite."""
    chosen = fa._choose_tiles(s, d, dtype, causal, h)
    assert chosen[:2] == (s, s) and fa.FLASH_BWD in chosen[2]
    check(chosen_case(s, d, causal, bias, dtype, b=2 if s < 512 else 1, h=h),
          fused, tol_fwd, tol_bwd, tiles=(s, s, group), kernel=fa.FLASH_BWD)
