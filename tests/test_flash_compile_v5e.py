"""The three flash kernels, the three of the fused CE, the rotary kernel and
the two of the chunked scan compiled for a v5e that is described, not attached (the TPU's compiler is
installed here): what Mosaic refuses at the real shapes — a misaligned slice, too much VMEM, a transpose it does not
take — fails here and costs no chip time. Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module-scoped fixture, never at import:
one process at a time may load libtpu, and under xdist every worker imports
this file. All such compiles stay in this one file and in the test's own
process."""
import base64
import dataclasses
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from hetu_tpu.kernels import dsa
from hetu_tpu.kernels import flash_attention as fa
from hetu_tpu.kernels import fused_ce as fc
from hetu_tpu.kernels import grouped_matmul as gmm
from hetu_tpu.kernels import gdn as gdn_kernel
from hetu_tpu.kernels import kda as kda_kernel
from hetu_tpu.kernels import rope
from hetu_tpu.kernels import ssd


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


# (batch, heads, seq, head_dim), dtype, causal, key bias, one fused array
SHAPES = [
    pytest.param((128, 12, 512, 64), jnp.bfloat16, False, True, True,
                 id="bert-seq512"),
    pytest.param((512, 12, 128, 64), jnp.bfloat16, False, True, True,
                 id="bert-seq128"),
    pytest.param((8, 16, 2048, 128), jnp.bfloat16, True, False, False,
                 id="causal-2048-d128"),
    pytest.param((2, 4, 1024, 128), jnp.float32, False, True, False,
                 id="f32-1024-d128"),
    pytest.param((8, 16, 4096, 128), jnp.bfloat16, True, False, False,
                 id="olmoe-seq4096"),
    pytest.param((4, 3, 512, 64), jnp.bfloat16, False, True, False,
                 id="three-heads-192-lanes"),
    # one tile, so one backward kernel, beyond BERT's two shapes
    pytest.param((128, 12, 512, 64), jnp.bfloat16, False, False, False,
                 id="one-tile-512-three-arrays"),
    pytest.param((16, 16, 512, 128), jnp.bfloat16, True, False, False,
                 id="one-tile-512-causal-d128"),
    pytest.param((256, 12, 256, 64), jnp.bfloat16, True, True, True,
                 id="one-tile-256-causal-bias"),
    pytest.param((4, 4, 512, 128), jnp.float32, False, True, False,
                 id="one-tile-512-f32-d128"),
    pytest.param((8, 16, 64, 64), jnp.bfloat16, True, False, False,
                 id="one-tile-64"),
    # the other decoder cells' calls (`flash_bwd_dqkv`, PR 41; kanana's is
    # among TWO_WIDTHS): one fused array in place, and heads of 64 in pairs
    pytest.param((1, 16, 4096, 128), jnp.bfloat16, True, False, True,
                 id="ouro-seq4096-fused"),
    pytest.param((1, 32, 8192, 64), jnp.bfloat16, True, False, False,
                 id="granite-seq8192"),
    pytest.param((4, 32, 8192, 64), jnp.bfloat16, True, False, False,
                 id="lfm2-seq8192"),
]

FLASH_KERNELS = (fa.FLASH_FWD, fa.FLASH_BWD, fa.FLASH_BWD_DQKV)


def _kernel_calls(text):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _count_by_name(calls, names=FLASH_KERNELS):
    """Calls by kernel: the instruction is named after the kernel, wrapped
    where jax wrapped its scope (`%jvp_flash_fwd_.1`); `flash_bwd` is not
    `flash_bwd_dqkv`."""
    return {name: sum(bool(re.search(name + "(?!_?[a-z])", c.split("=")[0]))
                      for c in calls) for name in names}


def _flash_fwd_and_grads(one_chip, shape, dtype, causal, bias, fused):
    """-> (the function, its abstract arguments): forward and gradient at
    the blocks the chooser picks. `flash_attention_btd` asks
    jax.default_backend() which backward to take and sees the CPU here:
    this is what it runs on a TPU, kernel by kernel."""
    b, h, s, d = shape

    def arr(columns):
        return jax.ShapeDtypeStruct((b, s, columns), dtype,
                                    sharding=one_chip)

    qkv = arr(3 * h * d) if fused else (arr(h * d),) * 3
    kb = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=one_chip)
    scale = 1.0 / d ** 0.5

    def fwd_and_grads(qkv, k_bias, do):
        k_bias = k_bias if bias else None
        out, lse = fa._fwd_pallas(qkv, h, k_bias, scale, causal, None, None,
                                  interpret=False)
        return out, fa._bwd_pallas(
            (qkv, out, lse, k_bias), do, n_heads=h, scale=scale,
            causal=causal, block_q=None, block_k=None, interpret=False)

    return fwd_and_grads, (qkv, kb, arr(h * d))


@pytest.mark.parametrize("shape,dtype,causal,bias,fused", SHAPES)
def test_flash_compiles_for_v5e(one_chip, no_compile_cache, shape, dtype,
                                causal, bias, fused):
    """Forward and gradient at the blocks the chooser picks, on the
    (batch, seq, heads * head_dim) arrays the trunk hands over (one fused
    [q|k|v] array, or three): a Mosaic custom call for each kernel of the
    call, under its name, in the compiled program: `flash_fwd` and
    `flash_bwd` where the sequence is one tile, else `flash_fwd` and
    `flash_bwd_dqkv`."""
    _, _, s, d = shape
    fn, args = _flash_fwd_and_grads(one_chip, shape, dtype, causal, bias,
                                    fused)
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = fa._choose_tiles(s, d, dtype, causal, shape[1])[2]
    assert (fa.FLASH_BWD in kernels) == (s <= 512)
    assert _count_by_name(_kernel_calls(text)) == {
        name: int(name in kernels) for name in FLASH_KERNELS}, text


# latent attention (PR 39): q and k of one head width, v and o of another
TWO_WIDTHS = [
    pytest.param((4, 32, 8192, 192, 128), True, id="kanana-seq8192"),
    pytest.param((2, 4, 512, 192, 128), True, id="one-tile-512"),
    pytest.param((2, 6, 1024, 64, 128), False, id="v-wider-than-q"),
]


@pytest.mark.parametrize("shape,causal", TWO_WIDTHS)
def test_flash_compiles_for_v5e_at_two_head_widths(one_chip,
                                                   no_compile_cache, shape,
                                                   causal):
    """q . k at `d` columns a head and p . v at `dv`, forward and gradient
    on three arrays: Mosaic takes the head groups (whole lane tiles on both
    arrays), the lane slice of a head that starts mid-tile and the 192-deep
    contraction; the cell's call, whose whole k and v are over what Mosaic
    gives unasked, compiles under the VMEM it asks for."""
    b, h, s, d, dv = shape

    def arr(columns):
        return jax.ShapeDtypeStruct((b, s, columns), jnp.bfloat16,
                                    sharding=one_chip)

    scale = 1.0 / d ** 0.5

    def fwd_and_grads(qkv, do):
        out, lse = fa._fwd_pallas(qkv, h, None, scale, causal, None, None,
                                  interpret=False)
        return out, fa._bwd_pallas(
            (qkv, out, lse, None), do, n_heads=h, scale=scale, causal=causal,
            block_q=None, block_k=None, interpret=False)

    compiled = jax.jit(fwd_and_grads).lower(
        (arr(h * d), arr(h * d), arr(h * dv)), arr(h * dv)).compile()
    out, (dq, dk, d_v) = compiled.out_info
    assert out.shape == d_v.shape == (b, s, h * dv)
    assert dq.shape == dk.shape == (b, s, h * d)
    bq, bk, kernels = fa._choose_tiles(s, d, jnp.bfloat16, causal, h, dv=dv)
    assert _count_by_name(_kernel_calls(compiled.as_text())) == {
        name: int(name in kernels) for name in FLASH_KERNELS}
    asked = {k: fa._vmem_limit(k, s, d, dv, jnp.bfloat16, *(
        fa._bwd_blocks(s, d, jnp.bfloat16, g, dv=dv)
        if k == fa.FLASH_BWD_DQKV else (bq, bk)), g)
        for k, g in kernels.items()}
    # the cell's call: k and v whole forward, the 64 MiB a call could ask
    # for since PR 39; q, dO, o, dq and dq's f32 sum whole backward, the
    # step above it
    assert asked == (
        {fa.FLASH_FWD: fa._VMEM_LIMITS[0],
         fa.FLASH_BWD_DQKV: fa._VMEM_LIMITS[1]} if s == 8192
        else dict.fromkeys(kernels))


# (batch, heads, seq, q/k width, v/o width), causal bf16 on three arrays:
# the five decoder cells' calls
DECODERS = {
    "kanana-2-30b-a3b": (4, 32, 8192, 192, 128),
    "lfm2-8b-a1b": (4, 32, 8192, 64, 64),
    "granite-4.0-h-micro": (1, 32, 8192, 64, 64),
    "ouro-2.6b": (1, 16, 4096, 128, 128),
    "olmoe-1b-7b": (8, 16, 4096, 128, 128),
}


@pytest.mark.parametrize("cell", sorted(DECODERS))
def test_one_backward_kernel_fits_what_it_counts(one_chip, no_compile_cache,
                                                 monkeypatch, cell):
    """`flash_bwd_dqkv` at a decoder cell's call: 512 x 512 blocks, the
    forward's heads, `_vmem_bytes`' count within the budget of what the call
    asks Mosaic for, and the count is no less than the compiler's own: the
    call compiles when it asks for exactly the count."""
    b, h, s, d, dv = DECODERS[cell]
    group = fa._choose_tiles(s, d, jnp.bfloat16, True, h, dv=dv)[2][
        fa.FLASH_BWD_DQKV]
    blocks = fa._bwd_blocks(s, d, jnp.bfloat16, group, dv=dv)
    assert blocks == (512, 512)
    count = fa._vmem_bytes(s, d, 2, *blocks, group, fa.FLASH_BWD_DQKV, dv)
    asked = fa._vmem_limit(fa.FLASH_BWD_DQKV, s, d, dv, jnp.bfloat16,
                           *blocks, group)
    assert asked == fa._VMEM_LIMITS[cell == "kanana-2-30b-a3b"]
    assert count <= fa._VMEM_BUDGETS[fa._VMEM_LIMITS.index(asked)]

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def backward(qkv, o, lse, do):
        return fa._bwd_pallas((qkv, o, lse, None), do, n_heads=h,
                              scale=d ** -0.5, causal=True, block_q=None,
                              block_k=None, interpret=False)

    monkeypatch.setattr(fa, "_vmem_limit", lambda *call: count)
    text = jax.jit(backward).lower(
        (arr(b, s, h * d), arr(b, s, h * d), arr(b, s, h * dv)),
        arr(b, s, h * dv), arr(b * h, 1, s, dtype=jnp.float32),
        arr(b, s, h * dv)).compile().as_text()
    assert _count_by_name(_kernel_calls(text)) == {
        fa.FLASH_FWD: 0, fa.FLASH_BWD: 0, fa.FLASH_BWD_DQKV: 1}


# The FORWARD kernel of a many-tile call is what it was before `flash_bwd`
# (PR 33) and before `flash_bwd_dqkv` (PR 41): the lowered forward of the
# decoders' calls, the Mosaic module inside read back as text WITHOUT its
# locations (a line number of this repo's files is in every one). OLMoE's and
# Ouro's forward digests were made on 2679af4, PR 41's parent, and hold since;
# the backward's, one Mosaic module where the pair was two, on PR 41's tree,
# and change only with the kernel or with what chooses its blocks. Heads of 64
# at 8,192 keys take 512 x 512 forward tiles since PR 46 (the forward's own
# VMEM count): Granite's and lfm2's forward digests are of that tree (62f352d,
# its parent, reads b977b04d7446a74f and f7bb1d8146b9cc2a); their backward
# digests, and both of kanana's and of keye's masked call, read the same on
# 62f352d and on PR 46's tree: those cells' kernels are the parent's.
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
MANY_TILE = {
    "olmoe-1b-7b": ("def97c0e0b7be1c8", "a81a4518f460617a"),
    "ouro-2.6b": ("a882d7247355e90b", "96ec2fdeafd0ad37"),
    "granite-4.0-h-micro": ("fc93facf10f45c07", "5d5c44bf5ea770e4"),
    "lfm2-8b-a1b": ("51ac672fb96f2d70", "aab8b6895a095154"),
    "kanana-2-30b-a3b": ("950277e43efc72e2", "17d9d5847e06a17e"),
    "keye-vl-2.0-30b-a3b": ("7a8ee9c33115df00", "bef6e18c3f1b3b2a"),
}
# keye's call (`DECODERS` has the other five) comes under a packed row mask
KEYE = {"keye-vl-2.0-30b-a3b": (2, 32, 16384, 128, 128)}


def _lowered_without_locations(text):
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    kernels = []
    for body in _BODY.findall(text):
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            kernels.append(module.operation.get_asm(enable_debug_info=False))
    return _BODY.sub("BODY", text) + "\n".join(kernels), len(kernels)


def _many_tile_halves(one_chip, shape, masked=False):
    """-> (forward, its arguments), (backward, its arguments) of a causal
    bf16 call on three arrays, each a program of ONE kernel; `masked`: under
    a packed row mask."""
    b, h, s, d, dv = shape

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = (arr(b, s, h * d), arr(b, s, h * d), arr(b, s, h * dv))
    pair = ((arr(b, s, s // fa.mask_planes(s), dtype=jnp.int32),) * 2
            if masked else None)
    scale = 1.0 / d ** 0.5

    def forward(qkv, pair):
        return fa._fwd_pallas(qkv, h, None, scale, True, None, None,
                              interpret=False, row_mask=pair)

    def backward(qkv, out, lse, do, pair):
        return fa._bwd_pallas(
            (qkv, out, lse, None) + ((pair,) if masked else ()), do,
            n_heads=h, scale=scale, causal=True, block_q=None, block_k=None,
            interpret=False)

    return (forward, (qkv, pair)), (backward, (
        qkv, arr(b, s, h * dv), arr(b * h, 1, s, dtype=jnp.float32),
        arr(b, s, h * dv), pair))


@pytest.mark.parametrize("half", ["forward", "backward"])
@pytest.mark.parametrize("cell", sorted(MANY_TILE))
def test_many_tile_kernels_lower_to_what_they_were(one_chip, cell, half):
    at = ("forward", "backward").index(half)
    fn, args = _many_tile_halves(one_chip, {**DECODERS, **KEYE}[cell],
                                 cell in KEYE)[at]
    text, kernels = _lowered_without_locations(
        jax.jit(fn).lower(*args).as_text())
    assert kernels == 1
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == MANY_TILE[cell][at]


@pytest.mark.parametrize("heads,window", [
    (64, 512), (48, None), (28, 4096), (28, None)],
    ids=["window-64-heads", "full-48-heads", "smallthinker-window-28-heads",
         "smallthinker-full-28-heads"])
def test_flash_compiles_for_v5e_at_lagunas_two_head_counts(
        one_chip, no_compile_cache, heads, window):
    """laguna-xs.2's two calls: 1 x 16,384 tokens of 128-column heads,
    bfloat16, three arrays; a window layer's 64 heads under a window of 512
    (the loops' bounds read at run time: a lower one forward, an upper one
    backward) and a full layer's 48. One kernel each way, at 512 x 512
    tiles, k and v whole in the VMEM the call asks for. And
    smallthinker-21b-a3b's two at the same tokens: 28 heads (four groups of
    seven, the k/v heads repeated), a window of 4,096 = eight key tiles deep,
    and the NoPE global layer's."""
    # (qkv, o, lse, dO): the backward half's abstract arguments
    args = _many_tile_halves(one_chip, (1, heads, 16384, 128, 128))[1][1][:4]
    scale = 1.0 / 128 ** 0.5

    def fwd_and_grads(qkv, out, lse, do):
        o, _ = fa._fwd_pallas(qkv, heads, None, scale, True, None, None,
                              interpret=False, window=window)
        return o, fa._bwd_pallas(
            (qkv, out, lse, None), do, n_heads=heads, scale=scale,
            causal=True, block_q=None, block_k=None, interpret=False,
            window=window)

    text = jax.jit(fwd_and_grads).lower(*args).compile().as_text()
    assert _count_by_name(_kernel_calls(text)) == {
        fa.FLASH_FWD: 1, fa.FLASH_BWD: 0, fa.FLASH_BWD_DQKV: 1}
    assert fa._choose_tiles(16384, 128, jnp.bfloat16, True, heads,
                            window=window)[:2] == (512, 512)


# (rows, vocabulary, width) of the calls the benchmark's cells make, bf16
CE_SHAPES = [
    pytest.param(8 * 4096, 50304, 2048, id="olmoe-1b-7b.pretrain-seq4096"),
    pytest.param(4 * 4096, 49152, 2048, id="ouro-2.6b.pretrain-seq4096-b1"),
    pytest.param(128 * 80, 30522, 768, id="bert-base.pretrain-seq512"),
    pytest.param(512 * 20, 30522, 768, id="bert-base.pretrain-seq128"),
]


@pytest.mark.parametrize("layout", ["dv", "vd"])
@pytest.mark.parametrize("n,v,d", CE_SHAPES)
def test_fused_ce_compiles_for_v5e(one_chip, no_compile_cache, monkeypatch,
                                   n, v, d, layout):
    """Value and the three gradients at the blocks `_choose_blocks` picks,
    in both weight orientations (the decoders send `dv`, BERT `vd`): three
    Mosaic custom calls under the kernels' names, within the
    `vmem_limit_bytes` the calls ask for."""
    monkeypatch.setattr(fc, "_on_tpu", lambda: True)   # not interpret mode

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, w, b, t):
        return jnp.sum(fc.fused_linear_nll(h, w, b, t, w_layout=layout))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        arr((n, d), jnp.bfloat16),
        arr((d, v) if layout == "dv" else (v, d), jnp.bfloat16),
        arr((v,), jnp.float32), arr((n,), jnp.int32)).compile().as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 3, text
    names = (fc.FUSED_CE_FWD, fc.FUSED_CE_BWD_DH, fc.FUSED_CE_BWD_DW)
    # "fused_ce_bwd_dw" and "_dh" do not contain "fused_ce_fwd" or each other
    assert set(_count_by_name(calls, names).values()) == {1}, calls


def test_flash_compiles_for_v5e_under_a_row_mask_at_keyes_shape(
        one_chip, no_compile_cache):
    """keye-vl-2.0-30b-a3b's call: 2 x 16,384 tokens, 32 heads of 128,
    bfloat16, the kept set a packed pair of (2, 16384, 512) int32 (32 planes
    of 512): `flash_fwd` reads it by query and `flash_bwd_dqkv` by key, each
    one Mosaic call; the pair is 64 MiB a sequence."""
    b, h, s, d = 2, 32, 16384, 128
    assert fa.mask_planes(s) == 32

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = (arr((b, s, h * d)),) * 3
    pair = (arr((b, s, s // 32), jnp.int32),) * 2
    scale = 1.0 / d ** 0.5

    def fwd_and_grads(qkv, pair, do):
        out, lse = fa._fwd_pallas(qkv, h, None, scale, True, None, None,
                                  interpret=False, row_mask=pair)
        return out, lse, fa._bwd_pallas(
            (qkv, out, lse, None, pair), do, n_heads=h, scale=scale,
            causal=True, block_q=None, block_k=None, interpret=False)

    text = jax.jit(fwd_and_grads).lower(
        qkv, pair, arr((b, s, h * d))).compile().as_text()
    assert _count_by_name(_kernel_calls(text)) == {
        fa.FLASH_FWD: 1, fa.FLASH_BWD: 0, fa.FLASH_BWD_DQKV: 1}, text
    assert 2 * s * (s // 32) * 4 == 64 << 20


def test_dsa_kernels_compile_for_v5e_at_keyes_shape(one_chip,
                                                    no_compile_cache):
    """The indexer's three kernels on a block of 512 rows against 16,384
    keys: 16 index heads of 64 columns, 32 query heads of 128 on 4 k/v
    heads, bfloat16 operands, the block's first row a scalar in SMEM: one
    Mosaic call each under its name."""
    R, T = dsa.row_block(16384), 16384
    assert R == 512

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def all_three(qI, w, kI, first, d_scores, q, k, lse):
        return (dsa._index_scores_pallas(qI, w, kI, first),
                dsa._index_bwd_pallas(qI, w, kI, first, d_scores),
                dsa._head_probs_pallas(q, k, lse, 128 ** -0.5, first))

    text = jax.jit(all_three).lower(
        arr((R, 16 * 64)), arr((R, 16), jnp.float32), arr((T, 64)),
        arr((), jnp.int32), arr((R, T), jnp.float32), arr((R, 32 * 128)),
        arr((T, 4 * 128)), arr((R, 32), jnp.float32)).compile().as_text()
    names = (dsa.DSA_INDEX, dsa.DSA_INDEX_BWD, dsa.DSA_PROBS)
    assert _count_by_name(_kernel_calls(text), names) == dict.fromkeys(
        names, 1), text


def test_rope_pairs_compiles_for_v5e_at_kananas_q(one_chip, no_compile_cache,
                                                  monkeypatch):
    """The rotation of latent attention's q at the cell's shape, (4, 8192,
    6144) bfloat16 in heads of 128 + 64, forward and transposed: two Mosaic
    calls under the kernel's name inside the VMEM Mosaic gives unasked, and
    around them no float32 array of q's size and no table wider than one
    period of 384 columns."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    q = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16,
                             sharding=one_chip)

    def forward_and_transposed(x, g):
        out, vjp = jax.vjp(
            lambda x: rope.rope_interleaved(x, 0, 1e6, 192, 128), x)
        return out, vjp(g)[0]

    assert rope._blocks(q.shape, 192, 128, 2) == (512, 768)
    text = jax.jit(forward_and_transposed).lower(q, q).compile().as_text()
    assert _count_by_name(_kernel_calls(text), (rope.ROPE_PAIRS,)) == {
        rope.ROPE_PAIRS: 2}, text
    assert "vmem_limit_bytes" not in text
    shapes = set(re.findall(r"(f32|bf16)\[([\d,]+)\]", text))
    assert ("bf16", "4,8192,6144") in shapes
    assert max(math.prod(map(int, dims.split(",")))
               for kind, dims in shapes if kind == "f32") == 8192 * 384


@pytest.mark.parametrize("B,T,nh,nkv,rot,yarn,blocks", [
    pytest.param(1, 16384, 64, 8, 0, None, ((512, 512), (512, 512)),
                 id="laguna-window-64-heads"),
    pytest.param(1, 16384, 48, 8, 64, True, ((512, 768), (512, 512)),
                 id="laguna-full-half-a-head-under-yarn"),
    pytest.param(1, 4096, 16, 16, 0, None, ((512, 512), (512, 512)),
                 id="ouro-one-sequence"),
    pytest.param(1, 16384, 28, 4, 0, None, ((512, 512), (512, 512)),
                 id="smallthinker-window-28-on-4")])
def test_rope_halves_compiles_for_v5e_in_the_projection(
        one_chip, no_compile_cache, monkeypatch, B, T, nh, nkv, rot, yarn,
        blocks):
    """The rotate-half rotation of q and k at the cells' shapes, heads of
    128 bfloat16 read out of the fused [q | k | v] projection where it
    stands, forward and transposed: four Mosaic calls under the kernel's
    name inside the VMEM Mosaic gives unasked; around them no float32 array
    of q's size and no table wider than a lane tile."""
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    hd = 128
    yarn = tfm.YarnConfig(64.0, 4096, 64.0, 1.0, 1.4158883) if yarn else None
    qkv = jax.ShapeDtypeStruct((B, T, (nh + 2 * nkv) * hd), jnp.bfloat16,
                               sharding=one_chip)
    cut = ((0, nh * hd), (nh * hd, nkv * hd))
    assert blocks == tuple(rope._blocks(qkv.shape, hd, 0, 2, rot, at)
                           for at in cut)

    def forward_and_transposed(x, gq, gk):
        (q, k), vjp = jax.vjp(lambda x: tuple(
            rope.rope_halves(x, 0, 5e5, hd, rot, yarn, at) for at in cut), x)
        return q, k, vjp((gq, gk))[0]

    q, k = (jax.ShapeDtypeStruct((B, T, w), jnp.bfloat16, sharding=one_chip)
            for _, w in cut)
    text = jax.jit(forward_and_transposed).lower(qkv, q, k).compile().as_text()
    assert _count_by_name(_kernel_calls(text), (rope.ROPE_HALVES,)) == {
        rope.ROPE_HALVES: 4}, text
    assert "vmem_limit_bytes" not in text
    # what the program holds in memory: its entry computation's arrays (the
    # sum of q's and k's cotangents adds in float32 inside one fusion)
    shapes = re.findall(r"(f32|bf16)\[([\d,]+)\]", text[text.index("ENTRY"):])
    assert max(math.prod(map(int, dims.split(",")))
               for kind, dims in shapes if kind == "f32") == T * hd


# ---------------------------------------------------------------------------
# One layer of the trunk, forward and gradient, as the step runs it (`remat`
# on, flash forced on): between the projections' matmuls and the kernels
# nothing of an activation's size may be copied. Before PR 26 a BERT layer
# held 23 such copies (q, k, v cut out of the projection and transposed to
# (B, nh, T, hd), o and every gradient transposed back): 92 ms of a 571 ms
# step.
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(([^()]*)\)")


def _entry_graph(text):
    """The entry computation of a compiled program's text as {name:
    (opcode, operand names, result elements, is a matmul)}. A fusion is a
    matmul where the computation it calls holds a convolution or a dot."""
    matmuls, name = set(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
        elif re.search(r" (convolution|dot)\(", line):
            matmuls.add(name)
    graph = {}
    entry = text[text.index("\nENTRY "):]
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OPCODE.search(m.group(2))
        if not op:
            continue
        dims = re.match(r"\w+\[([\d,]*)\]", m.group(2))
        calls = re.search(r"calls=%([\w.\-]+)", line)
        graph[m.group(1)] = (
            op.group(1), re.findall(r"%([\w.\-]+)", op.group(2)),
            math.prod(int(x) for x in dims.group(1).split(",") if x)
            if dims else 0,
            op.group(1) in ("convolution", "dot")
            or bool(calls and calls.group(1) in matmuls))
    return graph


def _copies_around_kernels(graph, at_least):
    """`copy` instructions of `at_least` elements that lie between a Mosaic
    call and the nearest matmul (or parameter, or result) on either side."""
    users = {}
    for name, (_, operands, _, _) in graph.items():
        for o in operands:
            users.setdefault(o, []).append(name)
    found = set()
    for step in (lambda n: graph[n][1], lambda n: users.get(n, [])):
        seen = set()
        todo = [n for n, v in graph.items() if v[0] == "custom-call"]
        todo = [m for n in todo for m in step(n)]
        while todo:
            n = todo.pop()
            if n in seen or n not in graph:
                continue
            seen.add(n)
            opcode, _, elements, matmul = graph[n]
            if matmul or opcode == "custom-call":
                continue
            if opcode == "copy" and elements >= at_least:
                found.add(n)
            todo += step(n)
    return sorted(found)


def _bert_layer():
    from hetu_tpu.models import bert
    return bert.BertConfig(attn_impl="flash").trunk()


def _bert_hf_layer():
    """BERT as the benchmark's cells build it: post-LN, biases, erf GELU."""
    from hetu_tpu.models import bert
    return bert.BertConfig.hf(dtype=jnp.bfloat16, attn_impl="flash").trunk()


def _olmoe_layer():
    """OLMoE-1B-7B's attention (16 heads of 128, causal, RoPE, QK-norm,
    RMSNorm) over a dense SwiGLU: the experts are not attention's."""
    from hetu_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        d_model=2048, n_heads=16, d_ff=1024, max_seq_len=4096,
        dtype=jnp.bfloat16, causal=True, attn_impl="flash", norm="rmsnorm",
        rope=True, mlp="swiglu", use_pos_emb=False, qk_norm=True)


def _entry_matmuls(graph):
    return sum(matmul for _, _, _, matmul in graph.values())


@pytest.mark.parametrize("config,batch,seq,bias", [
    pytest.param(_bert_layer, 128, 512, True, id="bert-seq512"),
    pytest.param(_bert_layer, 512, 128, True, id="bert-seq128"),
    pytest.param(_olmoe_layer, 8, 4096, False, id="olmoe-seq4096"),
    pytest.param(_bert_hf_layer, 128, 512, True, id="bert-hf-seq512"),
])
def test_layer_copies_nothing_around_attention(one_chip, no_compile_cache,
                                               monkeypatch, config, batch,
                                               seq, bias):
    """The layer under a BARE `jax.checkpoint` (what the trunk runs where
    `_remat_names` admits nothing), then under the trunk's own policy with
    every name admitted."""
    from hetu_tpu.models import transformer as tfm
    from hetu_tpu.telemetry.tracing import REMAT_CANDIDATES

    # the trunk and the kernels ask the backend which path to take
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(config(), n_layers=1)
    layer = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: tfm.init_trunk_params(
            jax.random.PRNGKey(0), cfg))["blocks"])
    h = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype,
                             sharding=one_chip)
    attn_bias = jax.ShapeDtypeStruct((batch, 1, 1, seq), jnp.float32,
                                     sharding=one_chip)

    def compiled(policy):
        def loss(h, layer, attn_bias):
            block = jax.checkpoint(lambda h, layer: tfm._block(
                h, layer, cfg, None, attn_bias if bias else None)[0],
                policy=policy)
            return jnp.sum(block(h, layer).astype(jnp.float32) ** 2)

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            h, layer, attn_bias).compile().as_text()
        graph = _entry_graph(text)
        copies = _copies_around_kernels(graph, batch * seq * cfg.d_model)
        assert not copies, [(c, graph[c]) for c in copies]
        return _count_by_name(_kernel_calls(text)), _entry_matmuls(graph)

    # the bare policy's contract: the forward runs twice, once as it is,
    # once under `remat`; one backward kernel: `flash_bwd` for a sequence of
    # one tile (BERT's), `flash_bwd_dqkv` for OLMoE's eight
    backward = {fa.FLASH_BWD if seq <= 512 else fa.FLASH_BWD_DQKV: 1}
    none = dict.fromkeys(FLASH_KERNELS, 0)
    bare_kernels, bare_matmuls = compiled(None)
    assert bare_kernels == {**none, fa.FLASH_FWD: 2, **backward}
    # every name kept: the forward kernel runs once, and the recomputed
    # wo (and w2, where a norm reads its sum) is gone
    kernels, matmuls = compiled(
        jax.checkpoint_policies.save_only_these_names(
            *sum(REMAT_CANDIDATES, ())))
    assert kernels == {**none, fa.FLASH_FWD: 1, **backward}
    assert matmuls <= bare_matmuls - (2 if cfg.post_ln else 1), (
        matmuls, bare_matmuls)


def _recomputed_matmuls(text):
    """{block scope: matmuls} among the instructions of a compiled program
    that a `jax.checkpoint` runs again (`rematted_computation` in their
    `op_name`), inside fusions and loop bodies too."""
    found = {}
    for line in text.splitlines():
        op_name = re.search(r'op_name="([^"]+)"', line)
        if (op_name and "rematted_computation" in op_name.group(1)
                and re.search(r" (convolution|dot)\(", line)):
            scope = re.findall(r"hetu_blk_\w+", op_name.group(1))[-1]
            found[scope] = found.get(scope, 0) + 1
    return found


def test_ouro_trunk_keeps_what_a_norm_or_a_kernel_reads(one_chip,
                                                        no_compile_cache,
                                                        monkeypatch):
    """`encode` at the Ouro cell's shapes, 24 block applications, under the
    trunk's own policy at the limit a v5e reports: every candidate is
    admitted, the backward scan runs `w1` and `w3` again and no other
    matmul, the forward kernel once, and what keeping adds to the plan's
    scratch stays under the budget `_remat_names` found for it."""
    from hetu_tpu.models import transformer as tfm
    from hetu_tpu.telemetry import tracing as tr
    from test_remat import _ouro    # the cell's config, abstract parameters

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = _ouro()
    assert cfg.rope and cfg.remat
    h = jax.ShapeDtypeStruct((1, 4096, cfg.d_model), cfg.dtype,
                             sharding=one_chip)
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), params)

    def loss(params, h):
        exits, _ = tfm.encode(params, h, cfg)
        return jnp.sum(exits.astype(jnp.float32) ** 2)

    def compiled(limit):
        monkeypatch.setattr(tfm, "_device_bytes_limit", lambda: limit)
        c = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, h).compile()
        text = c.as_text()
        return (_recomputed_matmuls(text), _count_by_name(_kernel_calls(text)),
                c.memory_analysis().temp_size_in_bytes)

    up, down = tr.SCOPE_BLK_MLP_UP, tr.SCOPE_BLK_MLP_DOWN
    # no limit reported: the bare checkpoint, the whole layer again
    again, kernels, bare_temp = compiled(None)
    assert again == {tr.SCOPE_BLK_QKV: 1, tr.SCOPE_BLK_WO: 1, up: 2, down: 1}
    assert kernels[fa.FLASH_FWD] == 2

    names, held, budget = tfm._remat_names(
        cfg, params, h, None, bytes_limit=int(15.75 * 2 ** 30))
    assert names == sum(tr.REMAT_CANDIDATES, ())
    again, kernels, temp = compiled(int(15.75 * 2 ** 30))
    assert again == {up: 2}                    # w1 and w3: SiLU(gate) * up
    assert kernels == {fa.FLASH_FWD: 1, fa.FLASH_BWD: 0,
                       fa.FLASH_BWD_DQKV: 1}
    # the plan holds more than the stacks themselves (a custom call's
    # operand is copied out of its stack), and still fits
    assert held <= temp - bare_temp <= budget, (held, temp - bare_temp, budget)


def test_share_layer_loops_over_its_rows_in_place(one_chip, no_compile_cache):
    """One expert layer of the `lfm2-8b-a1b` cell (8 of 32 experts held,
    32,768 tokens x 4 picks), forward and gradient, compiled for the v5e:
    the grouped matmuls calls over the whole arrays (three projections,
    forward and both cotangents, not a call a chunk), the passes around them
    loops to a run-time bound (a `while` whose trip count is no constant),
    and no whole-array pass beside them: no gather and no copy of all
    131,072 rows (the loops write their chunks in place)."""
    import json
    from hetu_tpu.models import hf_lfm2, transformer as tfm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/lfm2-8b-a1b/config.json")
              ) as f:
        cfg = hf_lfm2.config_from_hf(json.load(f), dtype=jnp.bfloat16)
    assert tfm._row_chunk(4 * 8192, cfg.d_model, cfg.dtype) == 1024
    blocks = tfm.run_blocks(cfg, jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))["blocks"])[1]
    p = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape[1:], x.dtype, sharding=one_chip), blocks)
    h = jax.ShapeDtypeStruct((4, 8192, cfg.d_model), cfg.dtype,
                             sharding=one_chip)

    def loss(h, p):
        out, _ = tfm._moe_mlp(h, p, cfg, None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(h, p).compile().as_text()
    rows = 4 * 8192 * cfg.n_experts_per_tok
    whole = [l for l in text.splitlines()
             if re.search(rf" = \w+\[{rows},\d+\]\S* (gather|copy)\(", l)]
    assert not whole, whole[:3]
    # the grouped matmuls stay whole-array calls, three projections, forward
    # and both cotangents, and never a call a chunk: by the name that
    # `benchmark/reduce/moe.py` finds them by in a trace
    assert len(re.findall(r"ragged-dot-none[.\d]* = ", text)) == 9
    # dispatch, activation, combine forward; combine, activation, the sum
    # of the two projections' cotangents, dispatch backward: each a loop
    # whose trip count the compiler does not know
    loops = [l for l in text.splitlines() if re.search(r" while\(", l)]
    assert sum("known_trip_count" not in l for l in loops) >= 7, loops


# granite-4.0-h-micro.pretrain-seq8192-b1's scan: (batch, seq, heads, head
# columns, groups, state, chunk); digests of the lowered forward and backward
# halves, locations cut, as `MANY_TILE` holds the attention kernels': a PR
# that changes a kernel of `kernels/ssd.py` on purpose re-pins its half
GRANITE_SCAN = (1, 8192, 64, 64, 1, 128, 256)
SSD_LOWERED = {"forward": "e1690c5609fd625f", "backward": "83d065e4abef390b"}


@pytest.mark.parametrize("half", ["forward", "backward"])
def test_ssd_kernels_compile_for_v5e_at_granites_scan(
        one_chip, no_compile_cache, monkeypatch, half):
    """The chunked scan's two kernels at the cell's call, one sequence of
    8,192 positions, 64 heads of 64 columns on one group's state of 128, in
    chunks of 256, bfloat16: ONE Mosaic call a half under the kernel's name,
    16 heads a grid step inside the VMEM Mosaic gives unasked, the entering
    states the only array of the states' size, and the lowered kernel the
    one that was measured."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    B, T, H, P, G, N, Q = GRANITE_SCAN

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, row, bc = arr(B, T, H, P), arr(B, T, H, dtype=jnp.float32), arr(
        B, T, G, N)
    assert ssd.takes(x, bc, Q) and ssd._heads(H, G, P, N, Q, 2) == (16, False)
    if half == "forward":
        fn, args, name = (lambda *a: ssd._forward(*a, Q),
                          (x, row, row, bc, bc), ssd.SSD_FWD)
    else:
        entering = arr(B, T // Q, H // 16, N, 16 * P, dtype=jnp.float32)
        fn, args, name = (lambda *a: ssd._backward(*a, Q),
                          (x, row, row, bc, bc, entering,
                           arr(B, T, H, P, dtype=jnp.float32)), ssd.SSD_BWD)
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    assert _count_by_name(_kernel_calls(text), (ssd.SSD_FWD, ssd.SSD_BWD)) == {
        ssd.SSD_FWD: int(half == "forward"),
        ssd.SSD_BWD: int(half == "backward")}, name
    assert "vmem_limit_bytes" not in text
    # nothing (H, Q, Q) or (chunks, H, Q, Q) leaves the kernel: the largest
    # float32 array is y's or dy's, (T, H * P)
    sizes = {math.prod(map(int, dims.split(",")))
             for kind, dims in re.findall(r"(f32)\[([\d,]+)\]", text)}
    assert max(sizes) == T * H * P
    lowered_text, kernels = _lowered_without_locations(lowered.as_text())
    assert kernels == 1
    assert hashlib.sha256(lowered_text.encode()).hexdigest()[:16] == (
        SSD_LOWERED[half])


# kimi-linear-48b-a3b.pretrain-seq16384-b1-ep32share's scan (batch, seq,
# heads, head columns) and the two heads its check's part (C) asks
KIMI_SCAN = (1, 16384, 32, 128)


@pytest.mark.parametrize("half", ["forward", "backward", "terms-two-heads"])
def test_kda_kernels_compile_for_v5e_at_kimis_scan(
        one_chip, no_compile_cache, monkeypatch, half):
    """Kimi Delta Attention's two kernels at the cell's call, one sequence
    of 16,384 positions, 32 heads of 128 columns in chunks of 64, bfloat16,
    and the forward kernel writing its parts for TWO heads: ONE Mosaic call
    a half under the kernel's name, inside the VMEM Mosaic gives unasked;
    the entering states (256 chunks x 32 heads x 128 x 128) are the largest
    float32 array: nothing (C, C, K) and nothing a segment wide leaves a
    kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    B, T, H, K = KIMI_SCAN
    H = 2 if half == "terms-two-heads" else H
    n = T // kda_kernel.CHUNK

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, g, beta = arr(B, T, H, K), arr(B, T, H, K, dtype=jnp.float32), arr(
        B, T, H, dtype=jnp.float32)
    assert kda_kernel.takes(x, x, x, g, beta, kda_kernel.CHUNK)
    if half == "forward":
        fn, args = (lambda *a: kda_kernel._kda_fwd(*a, kda_kernel.CHUNK)[0],
                    (x, x, x, g, beta))
    elif half == "backward":
        fn, args = (lambda *a: kda_kernel._backward(*a, kda_kernel.CHUNK),
                    (x, x, x, g, beta,
                     arr(B, n, H, K, K, dtype=jnp.float32),
                     arr(B, T, H, K, dtype=jnp.float32)))
    else:
        fn, args = (lambda *a: kda_kernel.terms(*a, kda_kernel.CHUNK),
                    (x, x, x, g, beta))
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = (kda_kernel.KDA_FWD, kda_kernel.KDA_BWD)
    assert _count_by_name(_kernel_calls(text), names) == {
        kda_kernel.KDA_FWD: int(half != "backward"),
        kda_kernel.KDA_BWD: int(half == "backward")}
    assert "vmem_limit_bytes" not in text
    sizes = {math.prod(map(int, dims.split(",")))
             for kind, dims in re.findall(r"(f32)\[([\d,]+)\]", text)}
    assert max(sizes) <= max(n * H * K * K, T * H * K)


# qwen3-next-80b-a3b.pretrain-seq16384-b1-ep16share's scan (batch, seq, key
# heads, value heads, head columns) and the two heads its check's part (C)
# asks, their q and k repeated a value head by `gdn_terms`
QWEN_SCAN = (1, 16384, 16, 32, 128)


@pytest.mark.parametrize("half", ["forward", "backward", "terms-two-heads"])
def test_gdn_kernels_compile_for_v5e_at_qwens_scan(
        one_chip, no_compile_cache, monkeypatch, half):
    """Gated DeltaNet's two kernels at the cell's call, one sequence of
    16,384 positions, 16 key heads under 32 value heads of 128 columns in
    chunks of 64, bfloat16, four value heads a grid step, and the forward
    kernel writing its parts for TWO heads: ONE Mosaic call a half under the
    kernel's name, inside the VMEM Mosaic gives unasked; the entering states
    are the largest float32 array, q, k and their cotangents stay a KEY
    head's and nothing (T, Hv * K) float32 but o and its cotangent exists."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    B, T, Hk, Hv, K = QWEN_SCAN
    if half == "terms-two-heads":
        Hk = Hv = 2
    n = T // kda_kernel.CHUNK

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key, x, row = arr(B, T, Hk, K), arr(B, T, Hv, K), arr(
        B, T, Hv, dtype=jnp.float32)
    assert gdn_kernel.refusal(key, key, x, row, row, kda_kernel.CHUNK) is None
    assert kda_kernel._heads(Hv) == (2 if half == "terms-two-heads" else 4)
    if half == "forward":
        fn, args = (lambda *a: gdn_kernel._gdn_fwd(*a, kda_kernel.CHUNK)[0],
                    (key, key, x, row, row))
    elif half == "backward":
        fn, args = (lambda *a: gdn_kernel._backward(*a, kda_kernel.CHUNK),
                    (key, key, x, row, row,
                     arr(B, n, Hv, K, K, dtype=jnp.float32),
                     arr(B, T, Hv, K, dtype=jnp.float32)))
    else:
        fn, args = (lambda *a: gdn_kernel.terms(*a, kda_kernel.CHUNK),
                    (key, key, x, row, row))
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = (gdn_kernel.GDN_FWD, gdn_kernel.GDN_BWD, kda_kernel.KDA_FWD,
             kda_kernel.KDA_BWD)
    assert _count_by_name(_kernel_calls(text), names) == {
        gdn_kernel.GDN_FWD: int(half != "backward"),
        gdn_kernel.GDN_BWD: int(half == "backward"),
        kda_kernel.KDA_FWD: 0, kda_kernel.KDA_BWD: 0}
    assert "vmem_limit_bytes" not in text
    sizes = {math.prod(map(int, dims.split(",")))
             for kind, dims in re.findall(r"(f32)\[([\d,]+)\]", text)}
    assert max(sizes) <= max(n * Hv * K * K, T * Hv * K)
    if half == "backward":      # dq, dk leave the kernel a key head's
        assert f"bf16[{B},{T},{Hk * K}]" in text


# an expert layer's grouped matmuls at the six expert cells' real calls:
# rows (tokens x picks), the experts a call sees (a share's held ones) and
# the matrix (K, N). The nemotron cell's two matrices (8,192 tokens x 6
# picks, 8 of 128 experts held; PR 56), and `w1` of the five cells whose
# widths the compiler tiles by 256 or 512 and the rule left to it until PR
# 59 (`w2` is the same two widths the other way round, which dx already is)
EXPERT_CALLS = {
    "nemotron-twotower-30b-a3b.w1": (49152, 8, 2688, 1856),
    "nemotron-twotower-30b-a3b.w2": (49152, 8, 1856, 2688),
    "lfm2-8b-a1b.w1": (131072, 8, 2048, 1792),
    "olmoe-1b-7b.w1": (262144, 64, 2048, 1024),
    "kanana-2-30b-a3b.w1": (196608, 16, 2048, 768),
    "keye-vl-2.0-30b-a3b.w1": (262144, 16, 2048, 768),
    "laguna-xs.2.w1": (131072, 32, 2048, 512),
    "smallthinker-21b-a3b.w1": (98304, 16, 2560, 768),
    "smallthinker-21b-a3b.w2": (98304, 16, 768, 2560)}


@pytest.mark.parametrize("product", ["forward", "dx", "dw"])
@pytest.mark.parametrize("call", list(EXPERT_CALLS))
def test_grouped_matmul_compiles_for_v5e_at_the_cells_calls(
        one_chip, no_compile_cache, monkeypatch, call, product):
    """The three products at each cell's real call, bfloat16: the rule takes
    them, each is ONE Mosaic call under the kernel's name (the readers of
    `moe_held_experts_roofline_pct` and `moe_experts_roofline_pct` count
    calls), no `ragged-dot` is left, dx reads the weights where they lie (no
    transposed copy of their size) and the VMEM the compiled kernel holds is
    within the count that chose its tiles, or within what Mosaic gives a
    kernel that asks for nothing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled
    M, E, K, N = EXPERT_CALLS[call]

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    xs, w, ct, sizes = arr(M, K), arr(E, K, N), arr(M, N), arr(
        E, dtype=jnp.int32)
    assert gmm.takes(xs, w)
    tm, forward, dx, dw = gmm._tiles(M, K, N, 2)

    def run(xs, w, ct, sizes):
        y, pull = jax.vjp(lambda a, b: gmm.grouped_matmul(a, b, sizes), xs, w)
        return {"forward": lambda: y, "dx": lambda: pull(ct)[0],
                "dw": lambda: pull(ct)[1]}[product]()

    text = jax.jit(run).lower(xs, w, ct, sizes).compile().as_text()
    names = (gmm.GROUPED_MATMUL, gmm.GROUPED_MATMUL_DW)
    assert _count_by_name(_kernel_calls(text), names) == {
        gmm.GROUPED_MATMUL: int(product != "dw"),
        gmm.GROUPED_MATMUL_DW: int(product == "dw")}
    assert len(_kernel_calls(text)) == 1 and "ragged-dot" not in text
    # the weights' array, and dW's, in one orientation only
    assert f"bf16[{E},{N},{K}]" not in text
    count = gmm._vmem_bytes(tm, *{"forward": forward, "dx": dx,
                                  "dw": dw}[product], 2, product == "dw")
    used, = map(int, re.findall(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
        _kernel_calls(text)[0]))
    # a count within `_VMEM_UNASKED` asks Mosaic for nothing and has a
    # quarter of the default to be wrong by: laguna's forward, the smallest
    # weight block (2,048 x 512), holds 8,011,776 bytes for 7,864,320 counted
    assert used <= max(count, gmm._VMEM_UNASKED)
    assert count <= gmm._VMEM_BUDGET < gmm._VMEM_LIMIT
