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
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = fa._fwd_pallas(q, k, v, None, scale, causal, block_q, block_k,
                              interpret=True)
    rng = np.random.RandomState(3)
    do = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    res = (q, k, v, out, lse, None)

    dq_p, dk_p, dv_p = fa._bwd_pallas(res, do, scale=scale, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=True)
    dq_b, dk_b, dv_b = fa._bwd_blockwise(res, do, scale=scale, causal=causal,
                                         block_k=block_k)
    for a, b in zip((dq_p, dk_p, dv_p), (dq_b, dk_b, dv_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    def loss_ref(q, k, v):
        return jnp.vdot(mha_reference(q, k, v, causal), do)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq_p, dk_p, dv_p), gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
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
    k_bias = _padding_bias(rng, q.shape[0], q.shape[2])
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = fa._fwd_pallas(q, k, v, k_bias, scale, causal, 64, 64,
                              interpret=True)
    do = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    res = (q, k, v, out, lse, k_bias)
    dq_p, dk_p, dv_p = fa._bwd_pallas(res, do, scale=scale, causal=causal,
                                      block_q=64, block_k=64, interpret=True)

    def loss_ref(q, k, v):
        return jnp.vdot(mha_reference(q, k, v, causal, k_bias=k_bias), do)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq_p, dk_p, dv_p), gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
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
