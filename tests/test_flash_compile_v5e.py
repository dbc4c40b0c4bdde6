"""The three flash kernels compiled for a v5e that is described, not
attached (the TPU's compiler is installed here): what Mosaic refuses at the
real shapes — a misaligned slice, too much VMEM, a transpose it does not
take — fails here and costs no chip time. Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module-scoped fixture, never at import:
one process at a time may load libtpu, and under xdist every worker imports
this file. All such compiles stay in this one file and in the test's own
process."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from hetu_tpu.kernels import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or libtpu held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to jax's persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (batch, heads, seq, head_dim), dtype, causal, key bias
SHAPES = [
    pytest.param((128, 12, 512, 64), jnp.bfloat16, False, True,
                 id="bert-seq512"),
    pytest.param((512, 12, 128, 64), jnp.bfloat16, False, True,
                 id="bert-seq128"),
    pytest.param((8, 16, 2048, 128), jnp.bfloat16, True, False,
                 id="causal-2048-d128"),
    pytest.param((2, 4, 1024, 128), jnp.float32, False, True,
                 id="f32-1024-d128"),
]


@pytest.mark.parametrize("shape,dtype,causal,bias", SHAPES)
def test_flash_compiles_for_v5e(one_chip, no_compile_cache, shape, dtype,
                                causal, bias):
    """Forward and gradient at the blocks the chooser picks: three Mosaic
    custom calls under the kernels' names in the compiled program."""
    b, _, s, d = shape
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kb = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=one_chip)
    scale = 1.0 / d ** 0.5

    # `flash_attention` asks jax.default_backend() which backward to take
    # and sees the CPU here: compile what it runs on a TPU, kernel by kernel
    def fwd_and_grads(q, k, v, k_bias, do):
        k_bias = k_bias if bias else None
        out, lse = fa._fwd_pallas(q, k, v, k_bias, scale, causal, None,
                                  None, interpret=False)
        return out, fa._bwd_pallas(
            (q, k, v, out, lse, k_bias), do, scale=scale, causal=causal,
            block_q=None, block_k=None, interpret=False)

    text = jax.jit(fwd_and_grads).lower(qkv, qkv, qkv, kb, qkv) \
        .compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3, text
    for name in (fa.FLASH_FWD, fa.FLASH_BWD_DQ, fa.FLASH_BWD_DKV):
        assert sum(f"%{name}" in c.split("=")[0] for c in calls) == 1, (
            name, [c.split("=")[0] for c in calls])
