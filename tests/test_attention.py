"""Flash attention + ring attention numerics vs the unfused oracle
(the reference framework's BatchMatMul+Softmax attention)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_tpu.kernels.flash_attention import flash_attention, mha_reference
from hetu_tpu.parallel.ring_attention import ring_attention


def _rand_qkv(rng, b=2, h=2, s=256, d=64):
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    return q, k, v


def _rows(x):
    """(b, h, s, d) -> (b, s, h*d): the layout the kernels index."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _heads(x, h):
    """(b, s, h*d) -> (b, h, s, d): the layout `mha_reference` takes."""
    b, s, w = x.shape
    return x.reshape(b, s, h, w // h).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = _rand_qkv(np.random.RandomState(0))
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_backward_matches_reference():
    q, k, v = _rand_qkv(np.random.RandomState(1), s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(64, 64), (32, 64), (64, 32)])
def test_pallas_backward_kernels_match_blockwise(causal, block_q, block_k):
    """The TPU backward path (dq + fused dk/dv Pallas kernels, run here in
    interpret mode) must match the XLA blockwise backward (the oracle) and
    the autodiff of the unfused reference."""
    from hetu_tpu.kernels import flash_attention as fa

    q, k, v = _rand_qkv(np.random.RandomState(2), s=128)
    h = q.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    qkv = tuple(_rows(x) for x in (q, k, v))
    out, lse = fa._fwd_pallas(qkv, h, None, scale, causal, block_q, block_k,
                              interpret=True)
    rng = np.random.RandomState(3)
    do = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    res = (qkv, out, lse, None)

    dq_p, dk_p, dv_p = fa._bwd_pallas(res, _rows(do), n_heads=h, scale=scale,
                                      causal=causal, block_q=block_q,
                                      block_k=block_k, interpret=True)
    dq_b, dk_b, dv_b = fa._bwd_blockwise(res, _rows(do), n_heads=h,
                                         scale=scale, causal=causal,
                                         block_k=block_k)
    for a, b in zip((dq_p, dk_p, dv_p), (dq_b, dk_b, dv_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    def loss_ref(q, k, v):
        return jnp.vdot(mha_reference(q, k, v, causal), do)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq_p, dk_p, dv_p), gr):
        np.testing.assert_allclose(np.asarray(_heads(a, h)), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (32, 256), (128, 64)])
def test_flash_causal_uneven_blocks(block_q, block_k):
    """block_q != block_k regression: the causal key-block bound must use
    ceil division — flooring drops the diagonal block when block_q < block_k
    and the first query rows silently output zeros."""
    q, k, v = _rand_qkv(np.random.RandomState(3))
    out = flash_attention(q, k, v, causal=True,
                          block_q=block_q, block_k=block_k)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _padding_bias(rng, b, s, min_valid=8):
    """(b, s) key-padding bias: 0 for valid keys, -1e9 for a padded tail."""
    lens = rng.randint(min_valid, s + 1, b)
    pos = np.arange(s)[None, :]
    return jnp.asarray(np.where(pos < lens[:, None], 0.0, -1e9), jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_key_bias_matches_reference(causal):
    """The fused kernel must fold a key-padding bias exactly like the
    unfused form — masked BERT batches no longer leave the flash path."""
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng, s=256)
    k_bias = _padding_bias(rng, q.shape[0], q.shape[2])
    out = flash_attention(q, k, v, causal=causal, k_bias=k_bias)
    ref = mha_reference(q, k, v, causal=causal, k_bias=k_bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_key_bias_gradients():
    rng = np.random.RandomState(6)
    q, k, v = _rand_qkv(rng, s=128)
    k_bias = _padding_bias(rng, q.shape[0], q.shape[2])

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, k_bias=k_bias) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, False, k_bias=k_bias) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_kernels_with_bias(causal):
    """The TPU backward kernels (interpret mode) must handle the key bias
    identically to the blockwise oracle and the reference autodiff."""
    from hetu_tpu.kernels import flash_attention as fa

    rng = np.random.RandomState(7)
    q, k, v = _rand_qkv(rng, s=128)
    h = q.shape[1]
    k_bias = _padding_bias(rng, q.shape[0], q.shape[2])
    scale = 1.0 / np.sqrt(q.shape[-1])
    qkv = tuple(_rows(x) for x in (q, k, v))
    out, lse = fa._fwd_pallas(qkv, h, k_bias, scale, causal, 64, 64,
                              interpret=True)
    do = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    res = (qkv, out, lse, k_bias)
    grads = fa._bwd_pallas(res, _rows(do), n_heads=h, scale=scale,
                           causal=causal, block_q=64, block_k=64,
                           interpret=True)

    def loss_ref(q, k, v):
        return jnp.vdot(mha_reference(q, k, v, causal, k_bias=k_bias), do)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, gr):
        np.testing.assert_allclose(np.asarray(_heads(a, h)), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


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
    q, k, v = _rand_qkv(np.random.RandomState(2), s=96)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True, None, 128, 64)


def _sp_mesh(n=4):
    devs = jax.devices()[:n]
    return Mesh(np.array(devs), ("sp",))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = _sp_mesh(4)
    q, k, v = _rand_qkv(np.random.RandomState(3), b=1, h=2, s=128, d=32)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = ring(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_key_bias_matches_full(causal):
    """The key-padding bias rotates with its k/v chunk around the ring and
    must reproduce the full-attention oracle, padded tails included."""
    mesh = _sp_mesh(4)
    rng = np.random.RandomState(9)
    q, k, v = _rand_qkv(rng, b=2, h=2, s=128, d=32)
    k_bias = _padding_bias(rng, 2, 128)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None))
    out = ring(q, k, v, k_bias)
    ref = mha_reference(q, k, v, causal=causal, k_bias=k_bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_key_bias_gradients():
    mesh = _sp_mesh(4)
    rng = np.random.RandomState(10)
    q, k, v = _rand_qkv(rng, b=1, h=2, s=64, d=16)
    k_bias = _padding_bias(rng, 1, 64)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=False),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v, k_bias) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, False, k_bias=k_bias) ** 2)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_gradients():
    mesh = _sp_mesh(4)
    q, k, v = _rand_qkv(np.random.RandomState(4), b=1, h=1, s=64, d=16)

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, True) ** 2)

    gf = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_bf16():
    q, k, v = _rand_qkv(np.random.RandomState(5), s=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# the tile program with the blocks it chooses itself (no block_q/block_k)
# ---------------------------------------------------------------------------

def _chosen_case(s, d, causal, bias, dtype, b=2, h=3):
    """Seeded (q, k, v, dO, k_bias) in `dtype`, and the same values in f32
    for the oracle (so the inputs' own rounding is not counted). h = 3 is a
    multiple of no power-of-two head group (three heads of 64 go as one
    block of 192 lanes); with a bias the second batch row is padded
    entirely."""
    rng = np.random.RandomState(s + d + causal + 2 * bias)
    q, k, v = _rand_qkv(rng, b=b, h=h, s=s, d=d)
    do = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k_bias = None
    if bias:
        k_bias = _padding_bias(rng, b, s).at[1].set(-1e9)
    given = tuple(x.astype(dtype) for x in (q, k, v, do))
    return given, tuple(x.astype(jnp.float32) for x in given), k_bias


def _check_chosen_blocks(case, causal, dtype, tol_fwd, tol_bwd, fused):
    """Forward and dq, dk, dv of the three kernels (interpret mode) at the
    blocks and head group `_choose_tiles` picks, against the unfused
    reference in f32. The kernels get (b, s, h*d) arrays: three, or with
    `fused` the one [q|k|v] array a fused projection writes."""
    from hetu_tpu.kernels import flash_attention as fa

    (q, k, v, do), (qf, kf, vf, dof), k_bias = case
    h, d = q.shape[1], q.shape[3]
    scale = 1.0 / np.sqrt(d)
    qkv = tuple(_rows(x) for x in (q, k, v))
    if fused:
        qkv = jnp.concatenate(qkv, axis=-1)
    out, lse = fa._fwd_pallas(qkv, h, k_bias, scale, causal, None, None,
                              interpret=True)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    ref, vjp = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, causal, k_bias=k_bias),
        qf, kf, vf)
    np.testing.assert_allclose(np.asarray(_heads(out, h), np.float32),
                               np.asarray(ref), rtol=tol_fwd, atol=tol_fwd)
    grads = fa._bwd_pallas((qkv, out, lse, k_bias), _rows(do), n_heads=h,
                           scale=scale, causal=causal, block_q=None,
                           block_k=None, interpret=True)
    if fused:
        assert grads.shape == qkv.shape
        grads = jnp.split(grads, 3, axis=-1)
    # A row with every key padded: its forward is the reference's uniform
    # softmax (checked above), its backward never was: lse = -1e9 + log(l)
    # rounds to -1e9 in f32, so the rebuilt p is 1 and not 1/l. Such a
    # row's dO is zero in a real loss; here its gradients must be finite.
    rows = slice(0, 1) if k_bias is not None else slice(None)
    for got, want in zip(grads, vjp(dof)):
        assert got.dtype == dtype
        got = _heads(got, h)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_allclose(np.asarray(got[rows], np.float32),
                                   np.asarray(want[rows]), rtol=tol_bwd,
                                   atol=tol_bwd)


_DTYPES = pytest.mark.parametrize("dtype,tol_fwd,tol_bwd", [
    (jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 2e-2, 2e-2)],
    ids=["f32", "bf16"])


@_DTYPES
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False),
                                         (True, True), (False, False)])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [128, 256, 512, 1024])
def test_flash_chosen_blocks_match_reference(s, d, causal, bias, dtype,
                                             tol_fwd, tol_bwd):
    _check_chosen_blocks(_chosen_case(s, d, causal, bias, dtype), causal,
                         dtype, tol_fwd, tol_bwd, fused=False)


# heads, head_dim, seq -> heads a grid step (and so column blocks a row)
@_DTYPES
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
    from hetu_tpu.kernels import flash_attention as fa

    assert set(fa._choose_tiles(s, d, dtype, causal, h)[2].values()) <= group
    case = _chosen_case(s, d, causal, bias, dtype, b=2 if s < 512 else 1,
                        h=h)
    if bias and s >= 512:       # one batch row: pad its tail, not all of it
        case = case[:2] + (_padding_bias(np.random.RandomState(s), 1, s),)
    _check_chosen_blocks(case, causal, dtype, tol_fwd, tol_bwd, fused)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "three"])
def test_flash_btd_entry_gradients(fused):
    """`flash_attention_btd` under jax.grad: the gradient comes back in the
    form qkv went in, one array or three."""
    from hetu_tpu.kernels.flash_attention import flash_attention_btd

    rng = np.random.RandomState(11)
    q, k, v = _rand_qkv(rng, b=2, h=4, s=128, d=64)
    k_bias = _padding_bias(rng, 2, 128)
    qkv = tuple(_rows(x) for x in (q, k, v))
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
        np.testing.assert_allclose(np.asarray(_heads(a, 4)), np.asarray(b),
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
        # a group for each kernel the call runs: one backward kernel iff
        # the sequence is one tile
        assert tuple(groups) == fa._kernels_of(s, block_q, block_k)
        assert (fa.FLASH_BWD in groups) == (block_q == block_k == s)
        if fa.FLASH_BWD not in groups:
            assert len(set(groups.values())) == 1
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
            count = fa._vmem_bytes(s, d, jnp.dtype(dtype).itemsize, block_q,
                                   block_k, group, kernel)
            unasked = fa._asking(kernel, s, d, d, dtype, block_q, block_k,
                                 group) == {}
            assert unasked == (count <= fa._VMEM_BUDGET)
            assert (block_q, block_k, group) == floor or count <= (
                fa._VMEM_BUDGET if unasked else fa._VMEM_BUDGET_ASKED)
    # blocks the caller passes are kept, and still get a head group
    assert fa._choose_tiles(s, d, dtype, causal, 12, 64, 32)[:2] == (
        min(64, s), min(32, s))


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
@pytest.mark.parametrize("s,n_dots", [(256, (2, 5)), (1024, (2, 3, 4))],
                         ids=["one-tile", "two-tiles"])
def test_flash_mxu_operands_follow_the_caller(dtype, causal, bias, s, n_dots):
    """The dtype rule, pinned on the kernels' jaxprs: every matmul of the
    kernels takes both operands in the caller's dtype (bf16 in, bf16 on the
    MXU; f32 in, f32) and accumulates in f32. Two matmuls in the forward;
    FIVE in the one backward kernel of a one-tile sequence; three in dq and
    four in dk+dv where the sequence is cut; times the heads of a step."""
    from hetu_tpu.kernels import flash_attention as fa

    (q, k, v, do), _, k_bias = _chosen_case(s, 64, causal, bias, dtype)
    heads = fa._choose_tiles(s, 64, dtype, causal, 3)[2]
    assert len(heads) == len(n_dots)

    def both(q, k, v, do):
        qkv = (q, k, v)
        out, lse = fa._fwd_pallas(qkv, 3, k_bias, 0.125, causal, None, None,
                                  interpret=False)
        return fa._bwd_pallas((qkv, out, lse, k_bias), do, n_heads=3,
                              scale=0.125, causal=causal, block_q=None,
                              block_k=None, interpret=False)

    calls = [e for e in jax.make_jaxpr(both)(
        *(_rows(x) for x in (q, k, v, do))).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert len(calls) == len(n_dots)
    for call, n, kernel in zip(calls, n_dots, heads):
        dots = list(_dot_generals(call.params["jaxpr"]))
        # not of the rule: delta's sums over each head's columns, once a
        # step of the kernel that makes delta (`flash_bwd`, `flash_bwd_dq`):
        # dO * O in two bf16 pieces (three from an f32 caller) against a
        # 0/1 matrix, exact whatever the dtype
        sums = [e for e in dots
                if e.invars[1].aval.shape == (3 * 64, 128)]
        makes_delta = kernel in (fa.FLASH_BWD, fa.FLASH_BWD_DQ)
        assert len(sums) == makes_delta * (3 if dtype == jnp.float32 else 2)
        for eqn in sums:
            assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
        dots = [e for e in dots if e not in sums]
        assert len(dots) == n * heads[kernel]
        for eqn in dots:
            assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
            assert eqn.outvars[0].aval.dtype == jnp.float32
            assert eqn.params["preferred_element_type"] == jnp.float32


# ---------------------------------------------------------------------------
# one backward kernel where the sequence is one tile (`flash_bwd`), two
# where it is cut (`flash_bwd_dq` + `flash_bwd_dkv`); heads a step by kernel
# ---------------------------------------------------------------------------

def _pallas_calls(fn, *args):
    """The names of the pallas_calls `fn` makes, in order."""
    return [e.params["name"]
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]


@_DTYPES
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "three"])
@pytest.mark.parametrize("causal,bias", [(False, True), (True, False)])
@pytest.mark.parametrize("h,d,s,group", [
    (2, 64, 128, 2), (4, 64, 256, 4), (12, 64, 128, 12), (12, 64, 512, 4),
    (12, 64, 512, 2), (4, 128, 128, 4), (2, 128, 512, 1), (4, 128, 256, 2)],
    ids=lambda x: str(x))
def test_one_tile_backward_is_one_kernel(h, d, s, group, causal, bias, fused,
                                         dtype, tol_fwd, tol_bwd,
                                         monkeypatch):
    """`flash_bwd` (interpret mode) with `group` heads a step against the
    XLA blockwise backward and the autodiff of the unfused reference, at the
    tolerances the two-kernel path has: one pallas_call under that name, the
    gradient in the form qkv came in, a fully padded batch row finite."""
    from hetu_tpu.kernels import flash_attention as fa

    b = 2 if s < 512 else 1
    (q, k, v, do), (qf, kf, vf, dof), k_bias = _chosen_case(
        s, d, causal, bias, dtype, b=b, h=h)
    if bias and b == 1:         # one batch row: pad its tail, not all of it
        k_bias = _padding_bias(np.random.RandomState(s), 1, s)
    chosen = fa._choose_tiles(s, d, dtype, causal, h)
    assert chosen[:2] == (s, s) and fa.FLASH_BWD in chosen[2]
    monkeypatch.setattr(fa, "_choose_tiles", lambda *a, **kw: (
        s, s, {fa.FLASH_FWD: group, fa.FLASH_BWD: group}))
    scale = 1.0 / np.sqrt(d)
    qkv = tuple(_rows(x) for x in (q, k, v))
    if fused:
        qkv = jnp.concatenate(qkv, axis=-1)
    out, lse = fa._fwd_pallas(qkv, h, k_bias, scale, causal, None, None,
                              interpret=True)
    res = (qkv, out, lse, k_bias)
    kw = dict(n_heads=h, scale=scale, causal=causal)

    def backward(res, do):
        return fa._bwd_pallas(res, do, block_q=None, block_k=None,
                              interpret=True, **kw)

    assert _pallas_calls(backward, res, _rows(do)) == [fa.FLASH_BWD]
    got = backward(res, _rows(do))
    oracle = fa._bwd_blockwise(res, _rows(do), block_k=s, **kw)
    if fused:
        assert got.shape == qkv.shape
        got, oracle = (jnp.split(x, 3, axis=-1) for x in (got, oracle))
    want = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal,
                                                 k_bias=k_bias),
                   qf, kf, vf)[1](dof)
    rows = slice(0, 1) if k_bias is not None else slice(None)
    for a, o, w in zip(got, oracle, want):
        assert a.dtype == dtype
        a, o = (np.asarray(_heads(x, h), np.float32) for x in (a, o))
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a[rows], o[rows], rtol=tol_bwd,
                                   atol=tol_bwd)
        np.testing.assert_allclose(a[rows], np.asarray(w[rows]),
                                   rtol=tol_bwd, atol=tol_bwd)


@pytest.mark.parametrize("s,block_q,block_k,names", [
    (256, None, None, ["flash_bwd"]),
    (512, 512, 512, ["flash_bwd"]),
    (64, None, None, ["flash_bwd"]),
    (1024, None, None, ["flash_bwd_dq", "flash_bwd_dkv"]),
    (256, 128, None, ["flash_bwd_dq", "flash_bwd_dkv"]),
    (256, None, 128, ["flash_bwd_dq", "flash_bwd_dkv"]),
    (384, None, None, ["flash_bwd_dq", "flash_bwd_dkv"])],
    ids=lambda x: str(x))
def test_backward_kernels_by_tiles(s, block_q, block_k, names):
    """One backward kernel iff the call's sequence is one tile, by the
    shapes alone: a sequence of two tiles, or one a caller's block cuts,
    keeps `flash_bwd_dq` + `flash_bwd_dkv`, and matches the oracle."""
    from hetu_tpu.kernels import flash_attention as fa

    (q, k, v, do), _, k_bias = _chosen_case(s, 64, False, True, jnp.float32,
                                            b=1, h=2)
    k_bias = _padding_bias(np.random.RandomState(s), 1, s)
    qkv = jnp.concatenate([_rows(x) for x in (q, k, v)], axis=-1)
    out, lse = fa._fwd_pallas(qkv, 2, k_bias, 0.125, False, block_q, block_k,
                              interpret=True)
    res = (qkv, out, lse, k_bias)
    kw = dict(n_heads=2, scale=0.125, causal=False)

    def backward(res, do):
        return fa._bwd_pallas(res, do, block_q=block_q, block_k=block_k,
                              interpret=True, **kw)

    assert _pallas_calls(backward, res, _rows(do)) == names
    np.testing.assert_allclose(
        np.asarray(backward(res, _rows(do))),
        np.asarray(fa._bwd_blockwise(res, _rows(do), block_k=min(s, 128),
                                     **kw)), rtol=2e-4, atol=2e-4)


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
                 {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1},
                 id="olmoe-1b-7b.pretrain-seq4096"),
    pytest.param(4096, 128, 16, True, (512, 512),
                 {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1},
                 id="ouro-2.6b.pretrain-seq4096-b1"),
    pytest.param(8192, 64, 32, True, (256, 256),
                 {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2},
                 id="granite-4.0-h-micro.pretrain-seq8192-b1"),
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
    decoders exactly what they had before it."""
    from hetu_tpu.kernels import flash_attention as fa
    assert fa._choose_tiles(s, d, jnp.bfloat16, causal, heads) == (
        *blocks, groups)
