"""ONE backward flash kernel a call (interpret mode), at forced tiles against
the XLA blockwise backward (the oracle) and the autodiff of the unfused
reference: `flash_bwd_dqkv` where the sequence is cut, which kernel by the
shapes alone, and the blocks a caller passes. `flash_bwd`'s own table, a
sequence of one tile, is in `test_flash_backward_one_tile.py`. Tables over
`flash_harness.check`: a new backward kernel's cases are one more table, here
or in a file of its own."""
import jax.numpy as jnp
import pytest

from flash_harness import DTYPES, Case, chosen_case, check
from hetu_tpu.kernels import flash_attention as fa


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 64), (64, 32)])
def test_pallas_backward_kernels_match_blockwise(causal, block_q, block_k):
    """The TPU backward path at blocks the caller passes (the one Pallas
    kernel, run here in interpret mode) must match the XLA blockwise
    backward (the oracle) and the autodiff of the unfused reference."""
    check(Case(2, 64, 64, 128, 2, causal, None, jnp.float32, seed=2), False,
          2e-5, 2e-4, tiles=(block_q, block_k, None))


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_kernels_with_bias(causal):
    """The TPU backward kernels (interpret mode) must handle the key bias
    identically to the blockwise oracle and the reference autodiff."""
    check(Case(2, 64, 64, 128, 2, causal, "tail", jnp.float32, seed=7), False,
          2e-5, 2e-4, tiles=(64, 64, None))


# heads, q/k width, v/o width, seq, block_q, block_k, heads a step, fused
@DTYPES
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False)])
@pytest.mark.parametrize("h,d,dv,s,block_q,block_k,group,fused", [
    (2, 64, 64, 256, 128, 128, 2, True),       # two key blocks, heads paired
    (2, 64, 64, 256, 128, 128, 2, False),
    (4, 64, 64, 512, 128, 64, 2, True),        # two column blocks a row
    (4, 64, 64, 512, 64, 128, 4, False),       # block_q != block_k
    (2, 192, 128, 256, 128, 128, 2, False),    # latent attention's widths
    (2, 128, 128, 2048, 128, 128, 1, True),    # sixteen key blocks
    (2, 128, 128, 2048, 128, 128, 2, False),
    # the forward's tiles at heads of 64 since PR 46: 512 x 512, heads paired
    (2, 64, 64, 1024, 512, 512, 2, False)],
    ids=lambda x: str(x))
def test_many_tile_backward_is_one_kernel(h, d, dv, s, block_q, block_k,
                                          group, fused, causal, bias, dtype,
                                          tol_fwd, tol_bwd):
    """`flash_bwd_dqkv` (interpret mode) against the XLA blockwise backward
    and the autodiff of the unfused reference, at the tolerances the pair of
    kernels it replaced had: ONE pallas_call under that name, whose first
    result is dq; dq summed over 2, 4, 8 and 16 key blocks in its scratch
    (zeroed at the first, written out at the last, anew for the next head
    group and batch row); the gradient in the form qkv came in. q and k are
    drawn at deviation 1, each row's tail padded where there is a bias."""
    case = Case(h, d, dv, s, 2 if s < 2048 else 1, causal,
                "tail" if bias else None, dtype, seed=s + d + causal,
                qk_std=1.0)
    check(case, fused, tol_fwd, tol_bwd, tiles=(block_q, block_k, group),
          kernel=fa.FLASH_BWD_DQKV)


@pytest.mark.parametrize("s,block_q,block_k,names", [
    (256, None, None, ["flash_bwd"]),
    (512, 512, 512, ["flash_bwd"]),
    (64, None, None, ["flash_bwd"]),
    (1024, None, None, ["flash_bwd_dqkv"]),
    (256, 128, None, ["flash_bwd_dqkv"]),
    (256, None, 128, ["flash_bwd_dqkv"]),
    (384, None, None, ["flash_bwd_dqkv"])],
    ids=lambda x: str(x))
def test_backward_kernels_by_tiles(s, block_q, block_k, names):
    """Which backward kernel, by the shapes alone: `flash_bwd` iff the
    call's sequence is one tile; a sequence of two tiles, or one a caller's
    block cuts, runs `flash_bwd_dqkv`, and matches the oracle."""
    check(chosen_case(s, 64, False, True, jnp.float32, b=1, h=2), True,
          2e-5, 2e-4, tiles=(block_q, block_k, None), kernel=names[0])
