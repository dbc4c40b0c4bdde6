"""Flash attention + ring attention numerics vs the unfused oracle
(the reference framework's BatchMatMul+Softmax attention)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from flash_harness import (padding_bias, pallas_calls, rand_qkv, to_heads,
                           to_rows)
from hetu_tpu.kernels.flash_attention import flash_attention, mha_reference
from hetu_tpu.parallel.ring_attention import ring_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = rand_qkv(np.random.RandomState(0))
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _assert_same_grads(attend, qkv, causal, k_bias=None):
    """d sum(out ** 2) / d(q, k, v) of `attend(q, k, v)` against the unfused
    reference's. Jitted: an eager `shard_map` runs a ring op by op on every
    device."""
    def grads(fn):
        return jax.jit(jax.grad(lambda *qkv: jnp.sum(fn(*qkv) ** 2),
                                argnums=(0, 1, 2)))(*qkv)

    want = grads(lambda q, k, v: mha_reference(q, k, v, causal,
                                               k_bias=k_bias))
    for a, b in zip(grads(attend), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_backward_matches_reference():
    _assert_same_grads(lambda q, k, v: flash_attention(q, k, v, True),
                       rand_qkv(np.random.RandomState(1), s=128), True)


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (32, 256), (128, 64)])
def test_flash_causal_uneven_blocks(block_q, block_k):
    """block_q != block_k regression: the causal key-block bound must use
    ceil division — flooring drops the diagonal block when block_q < block_k
    and the first query rows silently output zeros."""
    q, k, v = rand_qkv(np.random.RandomState(3))
    out = flash_attention(q, k, v, causal=True,
                          block_q=block_q, block_k=block_k)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_key_bias_matches_reference(causal):
    """The fused kernel must fold a key-padding bias exactly like the
    unfused form — masked BERT batches no longer leave the flash path."""
    rng = np.random.RandomState(5)
    q, k, v = rand_qkv(rng, s=256)
    k_bias = padding_bias(rng, q.shape[0], q.shape[2])
    out = flash_attention(q, k, v, causal=causal, k_bias=k_bias)
    ref = mha_reference(q, k, v, causal=causal, k_bias=k_bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_key_bias_gradients():
    rng = np.random.RandomState(6)
    qkv = rand_qkv(rng, s=128)
    k_bias = padding_bias(rng, 2, 128)
    _assert_same_grads(lambda q, k, v: flash_attention(
        q, k, v, False, k_bias=k_bias), qkv, False, k_bias)


def test_masked_bert_encoder_flash_matches_dot():
    """End to end: a padded BERT batch through the encoder with
    attn_impl='flash' (interpret off-TPU) equals attn_impl='dot' — the mask
    no longer forces the unfused path."""
    from hetu_tpu.models import bert as bertlib
    from hetu_tpu.models import transformer as tfm

    outs = {}
    for impl in ("dot", "flash"):
        cfg = bertlib.BertConfig(vocab_size=128, d_model=64, n_heads=4,
                                 n_layers=2, d_ff=128, max_seq_len=64,
                                 dtype=jnp.float32, remat=False,
                                 attn_impl=impl)
        params = bertlib.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(8)
        ids = jnp.asarray(rng.randint(0, 128, (2, 64)), jnp.int32)
        seg = jnp.zeros((2, 64), jnp.int32)
        mask = jnp.asarray(
            np.arange(64)[None, :] < np.array([[40], [64]]), jnp.int32)
        # resolution: a key-padding bias keeps the requested fused impl
        bias = (1.0 - mask.astype(jnp.float32))[:, None, None, :] * -1e9
        assert tfm._resolve_attn_impl(cfg.trunk(), None, 64, bias) == impl
        outs[impl] = bertlib.encode(params, ids, seg, cfg, input_mask=mask)
    np.testing.assert_allclose(np.asarray(outs["flash"]),
                               np.asarray(outs["dot"]), rtol=2e-4, atol=2e-4)


def test_nonpadding_bias_still_falls_back_to_dot():
    """A full (B, nh, T, T) additive bias is NOT key-padding-shaped: an
    explicit fused request degrades loudly to 'dot'."""
    from hetu_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(attn_impl="flash")
    full_bias = jnp.zeros((2, 4, 64, 64), jnp.float32)
    with pytest.warns(UserWarning, match="non-key-padding"):
        assert tfm._resolve_attn_impl(cfg, None, 64, full_bias) == "dot"
    # masked + block-indivisible seq keeps the pre-existing graceful
    # fallback instead of tripping the kernel's divisibility error
    pad_bias = jnp.zeros((2, 1, 1, 192), jnp.float32)
    with pytest.warns(UserWarning, match="divisible by 128"):
        assert tfm._resolve_attn_impl(cfg, None, 192, pad_bias) == "dot"


def test_flash_nondivisible_raises():
    q, k, v = rand_qkv(np.random.RandomState(2), s=96)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True, None, 128, 64)


def _ring(causal, bias=False):
    """Ring attention over four devices along the sequence, jitted (an eager
    `shard_map` runs the ring op by op on every device); with `bias`, a
    fourth argument: the (batch, seq) key-padding bias."""
    return jax.jit(jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=Mesh(np.array(jax.devices()[:4]), ("sp",)),
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),) * bias,
        out_specs=P(None, None, "sp", None)))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    q, k, v = rand_qkv(np.random.RandomState(3), b=1, h=2, s=128, d=32)
    out = _ring(causal)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_key_bias_matches_full(causal):
    """The key-padding bias rotates with its k/v chunk around the ring and
    must reproduce the full-attention oracle, padded tails included."""
    rng = np.random.RandomState(9)
    q, k, v = rand_qkv(rng, b=2, h=2, s=128, d=32)
    k_bias = padding_bias(rng, 2, 128)
    out = _ring(causal, bias=True)(q, k, v, k_bias)
    ref = mha_reference(q, k, v, causal=causal, k_bias=k_bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_key_bias_gradients():
    rng = np.random.RandomState(10)
    qkv = rand_qkv(rng, b=1, h=2, s=64, d=16)
    k_bias = padding_bias(rng, 1, 64)
    ring = _ring(False, bias=True)
    _assert_same_grads(lambda q, k, v: ring(q, k, v, k_bias), qkv, False,
                       k_bias)


def test_ring_attention_gradients():
    _assert_same_grads(_ring(True), rand_qkv(np.random.RandomState(4), b=1,
                                             h=1, s=64, d=16), True)


def test_flash_bf16():
    q, k, v = rand_qkv(np.random.RandomState(5), s=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "three"])
def test_flash_btd_entry_gradients(fused):
    """`flash_attention_btd` under jax.grad: the gradient comes back in the
    form qkv went in, one array or three."""
    from hetu_tpu.kernels.flash_attention import flash_attention_btd

    rng = np.random.RandomState(11)
    q, k, v = rand_qkv(rng, b=2, h=4, s=128, d=64)
    k_bias = padding_bias(rng, 2, 128)
    qkv = tuple(to_rows(x) for x in (q, k, v))
    if fused:
        qkv = jnp.concatenate(qkv, axis=-1)

    def loss(qkv):
        return jnp.sum(flash_attention_btd(qkv, 4, False, k_bias=k_bias) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, False, k_bias=k_bias) ** 2)

    got = jax.grad(loss)(qkv)
    got = jnp.split(got, 3, axis=-1) if fused else got
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(to_heads(a, 4)), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [64, 128, 256, 384, 512, 1024, 2048, 4096])
def test_choose_tiles(s, d, causal, dtype):
    """The chooser is a pure function of the call's shapes: its blocks
    divide s, its head group divides the heads it may group, what it picks
    fits the VMEM budget by its own count, and the same shapes give the same
    answer."""
    from hetu_tpu.kernels import flash_attention as fa

    for heads in (1, 3, 12, 16, 96):
        picked = fa._choose_tiles(s, d, dtype, causal, heads)
        assert picked == fa._choose_tiles(s, d, dtype, causal, heads)
        block_q, block_k, groups = picked
        assert s % block_q == 0 and s % block_k == 0
        assert (block_q, block_k) == (s, s) if s < 128 else (
            block_q % 128 == 0 and block_k % 128 == 0)
        # a group for each kernel the call runs: `flash_bwd` iff the
        # sequence is one tile, else `flash_bwd_dqkv`, which takes at most
        # the forward's heads and blocks of its own
        assert tuple(groups) == fa._kernels_of(s, block_q, block_k)
        assert len(groups) == 2
        assert (fa.FLASH_BWD in groups) == (block_q == block_k == s)
        if fa.FLASH_BWD not in groups:
            assert groups[fa.FLASH_BWD_DQKV] <= groups[fa.FLASH_FWD]
        itemsize = jnp.dtype(dtype).itemsize
        for kernel, group in groups.items():
            assert heads % group == 0 and 1 <= group <= fa._MAX_HEADS
            # the lane rule: a step's columns are whole 128-lane tiles, or
            # every head of the array
            assert (group * d) % 128 == 0 or group == heads
            # what fits unasked is taken before anything else; where
            # nothing does (whole f32 k and v at s = 4096, d = 128) the call
            # asks Mosaic for `_VMEM_LIMIT` and chooses within its budget;
            # the floor is taken where that fits nothing either
            floor = (128, 128, fa._head_groups(heads, d)[0])
            blocks = (block_q, block_k)
            if kernel == fa.FLASH_BWD_DQKV:
                # the largest blocks that fit the most a call may ask for
                blocks = fa._bwd_blocks(s, d, dtype, group)
                assert blocks[0] >= block_q and blocks[1] >= block_k
                assert s % blocks[0] == 0 and s % blocks[1] == 0
            count = fa._vmem_bytes(s, d, itemsize, *blocks, group, kernel)
            asked = fa._vmem_limit(kernel, s, d, d, dtype, *blocks, group)
            assert (asked is None) == (count <= fa._VMEM_BUDGET)
            assert (asked is None) == (
                fa._asking(kernel, s, d, d, dtype, *blocks, group) == {})
            if kernel == fa.FLASH_BWD_DQKV:
                # asks for the lower step wherever its budget holds the count
                assert count <= fa._VMEM_BUDGETS[-1]
                assert asked in (None,) + fa._VMEM_LIMITS
                assert asked != fa._VMEM_LIMITS[-1] or (
                    count > fa._VMEM_BUDGETS[0])
                continue
            assert (*blocks, group) == floor or count <= (
                fa._VMEM_BUDGET if asked is None else fa._VMEM_BUDGET_ASKED)
        if fa.FLASH_BWD not in groups:
            # the forward's blocks by the forward's OWN count (PR 46; the
            # loop above held it to its budget): no larger tile of `_picks`
            # fits where this one was taken, at any head group
            budget = (fa._VMEM_BUDGET if fa._asking(
                fa.FLASH_FWD, s, d, d, dtype, block_q, block_k,
                groups[fa.FLASH_FWD]) == {} else fa._VMEM_BUDGET_ASKED)
            assert all(fa._vmem_bytes(s, d, itemsize, *p, g, fa.FLASH_FWD)
                       > budget for p in fa._picks(s, None, None)
                       if p[0] * p[1] > block_q * block_k
                       for g in fa._head_groups(heads, d))
    # blocks the caller passes are kept, and still get a head group
    assert fa._choose_tiles(s, d, dtype, causal, 12, 64, 32)[:2] == (
        min(64, s), min(32, s))


@pytest.mark.parametrize("dtype,s,d,dv,heads,blocks,asked", [
    # the one family the forward's own count would have sent to a SMALLER
    # tile than the count of PR 41's pair did: 128 x 512 fits unasked (0.903
    # ms a call alone on the chip, PR 46), 512 x 512 asks and ran in 0.574
    (jnp.float32, 2048, 192, 128, 8, (512, 512), True),
    (jnp.bfloat16, 3072, 192, 128, 3, (512, 512), True),
    # half the largest side is still taken unasked
    (jnp.bfloat16, 4096, 192, 128, 16, (256, 256), False),
    (jnp.bfloat16, 4096, 256, 256, 8, (512, 256), False),
    # a sequence whose largest pick is 128 or 256 a side keeps its 128
    (jnp.float32, 384, 128, 128, 4, (128, 128), False),
    (jnp.float32, 1280, 512, 512, 4, (128, 256), False),
])
def test_many_tile_forward_takes_no_quarter_side_unasked(dtype, s, d, dv,
                                                         heads, blocks,
                                                         asked):
    """A many-tile call's forward blocks by the forward's own count: a pick
    whose shorter side is under half the largest pick's is not taken for
    fitting what Mosaic gives unasked; the larger tile that asks is."""
    from hetu_tpu.kernels import flash_attention as fa
    block_q, block_k, groups = fa._choose_tiles(s, d, dtype, True, heads,
                                                dv=dv)
    assert (block_q, block_k) == blocks
    limit = fa._vmem_limit(fa.FLASH_FWD, s, d, dv, dtype, block_q, block_k,
                           groups[fa.FLASH_FWD])
    assert limit == (fa._VMEM_LIMITS[0] if asked else None)


@pytest.mark.parametrize("heads,d,groups", [
    (12, 64, [2, 4, 6, 12]), (3, 64, [3]), (1, 64, [1]), (4, 32, [4]),
    (16, 128, [1, 2, 4, 8, 16]), (96, 64, [2, 4, 6, 8, 12, 16]),
    (20, 64, [2, 4, 10]), (17, 64, [17])])
def test_head_groups_lane_rule(heads, d, groups):
    """Heads a grid step may take, at most `_MAX_HEADS`: `g * d` whole lane
    tiles, or every head; every head where that leaves none."""
    from hetu_tpu.kernels import flash_attention as fa
    assert fa._head_groups(heads, d) == groups


def _dot_generals(jaxpr):
    """Every dot_general in a jaxpr, kernels' and loops' bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(sub)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False)])
@pytest.mark.parametrize("s,n_dots", [(256, (2, 5)), (1024, (2, 5))],
                         ids=["one-tile", "two-tiles"])
def test_flash_mxu_operands_follow_the_caller(dtype, causal, bias, s, n_dots):
    """The dtype rule, pinned on the kernels' jaxprs: every matmul of the
    kernels takes both operands in the caller's dtype (bf16 in, bf16 on the
    MXU; f32 in, f32) and accumulates in f32. Two matmuls in the forward;
    FIVE in the one backward kernel, whether the sequence is one tile
    (`flash_bwd`) or cut (`flash_bwd_dqkv`, whose loop over query blocks is
    traced once); times the heads of a step."""
    from hetu_tpu.kernels import flash_attention as fa

    x = jax.ShapeDtypeStruct((2, s, 3 * 64), dtype)     # three heads of 64
    k_bias = jax.ShapeDtypeStruct((2, s), jnp.float32) if bias else None
    heads = fa._choose_tiles(s, 64, dtype, causal, 3)[2]
    assert len(heads) == len(n_dots)

    def both(q, k, v, do, k_bias):
        qkv = (q, k, v)
        out, lse = fa._fwd_pallas(qkv, 3, k_bias, 0.125, causal, None, None,
                                  interpret=False)
        return fa._bwd_pallas((qkv, out, lse, k_bias), do, n_heads=3,
                              scale=0.125, causal=causal, block_q=None,
                              block_k=None, interpret=False)

    calls = pallas_calls(jax.make_jaxpr(both)(x, x, x, x, k_bias).jaxpr)
    assert len(calls) == len(n_dots)
    for call, n, kernel in zip(calls, n_dots, heads):
        dots = list(_dot_generals(call.params["jaxpr"]))
        # not of the rule: delta's sums over each head's columns, in the
        # backward kernel, which makes delta (`flash_bwd`; `flash_bwd_dqkv`
        # at its first key block): dO * O in two bf16 pieces (three from an
        # f32 caller) against a 0/1 matrix, exact whatever the dtype
        sums = [e for e in dots
                if e.invars[1].aval.shape == (3 * 64, 128)]
        makes_delta = kernel in (fa.FLASH_BWD, fa.FLASH_BWD_DQKV)
        assert len(sums) == makes_delta * (3 if dtype == jnp.float32 else 2)
        for eqn in sums:
            assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
        dots = [e for e in dots if e not in sums]
        assert len(dots) == n * heads[kernel]
        for eqn in dots:
            assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
            assert eqn.outvars[0].aval.dtype == jnp.float32
            assert eqn.params["preferred_element_type"] == jnp.float32


# (seq, head_dim, heads, causal) of the attention calls the benchmark's
# cells make, bf16 -> (block_q, block_k, heads a step of each kernel)
@pytest.mark.parametrize("s,d,heads,causal,blocks,groups", [
    pytest.param(128, 64, 12, False, (128, 128),
                 {"flash_fwd": 4, "flash_bwd": 12},
                 id="bert-base.pretrain-seq128"),
    pytest.param(512, 64, 12, False, (512, 512),
                 {"flash_fwd": 4, "flash_bwd": 4},
                 id="bert-base.pretrain-seq512"),
    pytest.param(4096, 128, 16, True, (512, 512),
                 {"flash_fwd": 1, "flash_bwd_dqkv": 1},
                 id="olmoe-1b-7b.pretrain-seq4096"),
    pytest.param(4096, 128, 16, True, (512, 512),
                 {"flash_fwd": 1, "flash_bwd_dqkv": 1},
                 id="ouro-2.6b.pretrain-seq4096-b1"),
    pytest.param(8192, 64, 32, True, (512, 512),
                 {"flash_fwd": 2, "flash_bwd_dqkv": 2},
                 id="granite-4.0-h-micro.pretrain-seq8192-b1"),
    pytest.param(8192, 64, 32, True, (512, 512),
                 {"flash_fwd": 2, "flash_bwd_dqkv": 2},
                 id="lfm2-8b-a1b.pretrain-seq8192-ep4load"),
    # one tile at head size 128: whole lane tiles, no cap on the forward
    pytest.param(128, 128, 16, False, (128, 128),
                 {"flash_fwd": 16, "flash_bwd": 16}, id="one-tile-d128"),
    pytest.param(256, 64, 12, False, (256, 256),
                 {"flash_fwd": 4, "flash_bwd": 12}, id="one-tile-256-d64"),
])
def test_choose_tiles_at_the_cells_shapes(s, d, heads, causal, blocks,
                                          groups):
    """What the per-kernel rule picks at the shapes the cells run: the
    one-tile BERT shapes as the chip had them best (PR 33), the many-tile
    decoders' forward the largest tile its own count fits (PR 46: 512 x 512
    at two heads of 64 over 8,192 keys too, 11.7 MiB); their one backward
    kernel takes the forward's heads and 512 x 512 blocks of its own, under
    VMEM it asks for (PR 41)."""
    from hetu_tpu.kernels import flash_attention as fa
    assert fa._choose_tiles(s, d, jnp.bfloat16, causal, heads) == (
        *blocks, groups)
    if fa.FLASH_BWD_DQKV in groups:
        group = groups[fa.FLASH_BWD_DQKV]
        assert fa._bwd_blocks(s, d, jnp.bfloat16, group) == (512, 512)
        assert fa._vmem_limit(fa.FLASH_BWD_DQKV, s, d, d, jnp.bfloat16, 512,
                              512, group) == fa._VMEM_LIMITS[0]
