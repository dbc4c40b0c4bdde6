"""The three Mosaic kernels of learned sparse attention (`kernels/dsa.py`,
interpret mode) against the jnp expressions they stand in for: the index
scores of a block of rows, their cotangents, and the head-summed
probabilities; each skips the key blocks that no row of its block sees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import dsa


def _inputs(R, T, J, c, H, G, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return dict(
        qI=n(ks[0], R, J * c).astype(dtype), kI=n(ks[1], T, c).astype(dtype),
        w=n(ks[2], R, J) * 0.1, q=(0.3 * n(ks[3], R, H * d)).astype(dtype),
        k=(0.3 * n(ks[4], T, G * d)).astype(dtype),
        lse=2.0 + 0.1 * n(ks[5], R, H), g=n(ks[6], R, T))


def _causal(first, R, T):
    return np.arange(T)[None, :] <= first + np.arange(R)[:, None]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("R,T,first", [(128, 1024, 0), (128, 1024, 640),
                                       (512, 1024, 512), (64, 512, 448)])
def test_index_scores_and_their_cotangents(R, T, first, dtype, tol):
    x = _inputs(R, T, 4, 64, 4, 2, 128, dtype)
    seen = _causal(first, R, T)
    got = dsa._index_scores_pallas(x["qI"], x["w"], x["kI"], first,
                                   interpret=True)
    want, back = jax.vjp(dsa._index_scores_jnp, x["qI"], x["w"], x["kI"])
    np.testing.assert_allclose(np.where(seen, got, 0),
                               np.where(seen, want, 0), rtol=tol, atol=tol)
    # whole key blocks past the block's last row are skipped: zeros
    past = (first + R - 1) // dsa.KEYS + 1
    assert not np.asarray(got)[:, past * dsa.KEYS:].any()
    # the cotangent a loss hands back is zero off the causal triangle
    g = jnp.where(seen, x["g"], 0.0)
    dq, dw, dk = dsa._index_bwd_pallas(x["qI"], x["w"], x["kI"], first, g,
                                       interpret=True)
    for a, b in zip((dq, dw, dk), back(g)):
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol * scale)
    # through the entry's own `custom_vjp`
    via = jax.grad(lambda q, w, k: jnp.sum(dsa._index_scores_kernels(
        q, w, k, jnp.int32(first), True) * g), (0, 1, 2))(
        x["qI"], x["w"], x["kI"])
    for a, b in zip(via, (dq, dw, dk)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b.astype(a.dtype), np.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("R,T,first,H,G", [(128, 1024, 896, 4, 2),
                                           (256, 512, 0, 8, 1),
                                           (128, 512, 384, 2, 2)])
def test_head_summed_probabilities(R, T, first, H, G, dtype, tol):
    x = _inputs(R, T, 4, 64, H, G, 128, dtype, seed=1)
    seen = _causal(first, R, T)
    scale = 1.0 / np.sqrt(128)
    got = dsa._head_probs_pallas(x["q"], x["k"], x["lse"], scale, first,
                                 interpret=True)
    want = dsa._head_probs_jnp(x["q"], x["k"], x["lse"], scale)
    np.testing.assert_allclose(np.where(seen, got, 0),
                               np.where(seen, want, 0), rtol=tol, atol=tol)
    # a query head reads ITS k/v head: against the heads one at a time
    q = np.asarray(x["q"], np.float64).reshape(R, H, 128)
    k = np.asarray(x["k"], np.float64).reshape(T, G, 128)
    by_hand = sum(np.exp(q[:, h] @ k[:, h // (H // G)].T * scale
                         - np.asarray(x["lse"], np.float64)[:, h:h + 1])
                  for h in range(H))
    np.testing.assert_allclose(np.where(seen, got, 0),
                               np.where(seen, by_hand, 0), rtol=tol,
                               atol=tol)


def test_the_kernels_are_taken_on_a_tpu_alone(monkeypatch):
    assert not dsa._kernels_take(512, 16384, 64)           # the CPU
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    assert dsa._kernels_take(512, 16384, 64, 256)
    assert not dsa._kernels_take(32, 32, 8)                # a toy sequence
    assert not dsa._kernels_take(512, 16384, 48)
    assert dsa.row_block(16384) == 512 and dsa.row_block(96) == 32
