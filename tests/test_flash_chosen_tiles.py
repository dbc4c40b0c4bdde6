"""The flash kernels (interpret mode) at the blocks and head groups
`_choose_tiles` picks itself (no block_q/block_k), forward and gradient
against the unfused reference in float32: a table over `flash_harness.check`.
The (batch, seq, heads*head_dim) layouts at chosen tiles are in
`test_flash_btd_layout.py`, the backward kernels at forced tiles, against the
blockwise oracle too, in `test_flash_backward_one_tile.py` and
`test_flash_backward.py`: a file each, because a FILE is what `--dist
loadfile` hands a worker (ISSUE 42)."""
import pytest

from flash_harness import DTYPES, chosen_case, check


@DTYPES
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False),
                                         (True, True), (False, False)])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [128, 256, 512, 1024])
def test_flash_chosen_blocks_match_reference(s, d, causal, bias, dtype,
                                             tol_fwd, tol_bwd):
    """Forward and dq, dk, dv at the blocks and head group `_choose_tiles`
    picks, three heads on three arrays, two batch rows, with a bias the
    second padded entirely."""
    check(chosen_case(s, d, causal, bias, dtype), False, tol_fwd, tol_bwd,
          against_oracle=False)
