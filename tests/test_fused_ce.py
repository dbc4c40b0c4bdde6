"""Fused linear+softmax-CE kernel vs the materializing oracle: values and
all three gradients, including non-block-divisible N and V (padding/tail
masking) and bf16 inputs. Runs the Pallas kernels in interpret mode on the
CPU backend."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.kernels import fused_ce as fc
from hetu_tpu.kernels.fused_ce import fused_linear_nll, linear_nll_reference


def _data(rng, n, v, d, dtype=jnp.float32):
    h = jnp.asarray(rng.randn(n, d), dtype) * 0.5
    w = jnp.asarray(rng.randn(v, d), dtype) * 0.3
    b = jnp.asarray(rng.randn(v), jnp.float32) * 0.1
    t = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    return h, w, b, t


@pytest.mark.parametrize("n,v,d,bn,bv", [
    (64, 256, 32, 32, 64),     # clean tiles
    (50, 300, 16, 32, 128),    # both axes ragged (pad + tail mask)
    (16, 40, 8, 128, 512),     # blocks larger than the problem
])
def test_forward_matches_reference(n, v, d, bn, bv):
    h, w, b, t = _data(np.random.RandomState(0), n, v, d)
    out = fused_linear_nll(h, w, b, t, block_n=bn, block_v=bv)
    ref = linear_nll_reference(h, w, b, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bert_vocab_30522_forced_path():
    """The EXACT BERT-base vocab (30522 = 59*512 + 314: ragged against the
    default 512 vocab block) through the fused kernel — the bench's BERT
    cell must not discover a padding/tail-mask bug on its one hardware
    run. Small N/D keep interpret mode fast; the vocab axis is full."""
    h, w, b, t = _data(np.random.RandomState(1), 8, 30522, 16)
    out = fused_linear_nll(h, w, b, t, block_n=8, block_v=512)
    ref = linear_nll_reference(h, w, b, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_match_reference():
    h, w, b, t = _data(np.random.RandomState(1), 48, 200, 24)
    ct = jnp.asarray(np.random.RandomState(2).rand(48), jnp.float32)

    def loss_fused(h, w, b):
        return jnp.vdot(fused_linear_nll(h, w, b, t, block_n=16,
                                         block_v=64), ct)

    def loss_ref(h, w, b):
        return jnp.vdot(linear_nll_reference(h, w, b, t), ct)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(h, w, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(h, w, b)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_bf16_inputs():
    h, w, b, t = _data(np.random.RandomState(3), 32, 128, 16, jnp.bfloat16)
    out = fused_linear_nll(h, w, b, t, block_n=16, block_v=64)
    ref = linear_nll_reference(h, w, b, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    # grads keep the input dtypes
    g = jax.grad(lambda h, w, b: jnp.sum(
        fused_linear_nll(h, w, b, t, block_n=16, block_v=64)),
        argnums=(0, 1, 2))(h, w, b)
    assert g[0].dtype == jnp.bfloat16 and g[1].dtype == jnp.bfloat16


def test_weighted_mean_composes():
    """The MLM-style weighted mean (callers weight and normalize the
    per-row nll) differentiates through the kernel correctly."""
    h, w, b, t = _data(np.random.RandomState(4), 40, 96, 16)
    wt = jnp.asarray((np.random.RandomState(5).rand(40) > 0.3), jnp.float32)

    def mlm_loss(fn):
        def f(h, w, b):
            per = fn(h, w, b, t) if fn is linear_nll_reference else \
                fn(h, w, b, t, 16, 32)
            return jnp.sum(per * wt) / jnp.maximum(jnp.sum(wt), 1.0)
        return f

    lf = mlm_loss(fused_linear_nll)(h, w, b)
    lr = mlm_loss(linear_nll_reference)(h, w, b)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
    gf = jax.grad(mlm_loss(fused_linear_nll), argnums=(0, 1))(h, w, b)
    gr = jax.grad(mlm_loss(linear_nll_reference), argnums=(0, 1))(h, w, b)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the tile program: blocks chosen from the call's shapes
# ---------------------------------------------------------------------------

KERNELS = (fc.FUSED_CE_FWD, fc.FUSED_CE_BWD_DH, fc.FUSED_CE_BWD_DW)


@pytest.mark.parametrize("n,v,d,blocks,asks", [
    pytest.param(32768, 50304, 2048, ((512, 256), (512, 512), (512, 512)),
                 (False, True, True), id="olmoe-1b-7b.pretrain-seq4096"),
    pytest.param(16384, 49152, 2048, ((512, 256), (512, 512), (512, 512)),
                 (False, True, True), id="ouro-2.6b.pretrain-seq4096-b1"),
    pytest.param(128 * 80, 30522, 768,
                 ((1024, 256), (512, 512), (512, 512)),
                 (False, False, False), id="bert-base.pretrain-seq512"),
    pytest.param(512 * 20, 30522, 768,
                 ((1024, 256), (512, 512), (512, 512)),
                 (False, False, False), id="bert-base.pretrain-seq128"),
])
def test_choose_blocks_at_the_cells_shapes(n, v, d, blocks, asks):
    """What the benchmark's cells send (bf16): the picks; which of them ask
    Mosaic for more VMEM than it gives unasked (those that could not have
    512 rows otherwise), each under its budget by the count; the next row
    block up over it or absent."""
    picked = fc._choose_blocks(n, v, d, jnp.bfloat16)
    assert picked == blocks
    assert picked == fc._choose_blocks(n, v, d, jnp.bfloat16)
    for (bn, bv), kernel, ask in zip(picked, KERNELS, asks):
        assert n % bn == 0                       # no padding copy of h
        assert bn >= fc._RIDGE_ROWS
        count = fc._vmem_bytes(bn, bv, d, 2, kernel)
        assert (count > fc._VMEM_BUDGET_UNASKED) == ask
        budget = fc._VMEM_BUDGET if ask else fc._VMEM_BUDGET_UNASKED
        assert count <= budget
        narrowest = 512 if kernel == fc.FUSED_CE_BWD_DW else 128
        if bn < fc._ROW_BLOCKS[0]:
            assert fc._vmem_bytes(2 * bn, narrowest, d, 2, kernel) > budget
    assert picked[2][1] >= 512                   # dw streams h no oftener


@pytest.mark.parametrize("n,v,given,blocks", [
    # N below 128 is one block, V below a block one tile
    pytest.param(50, 40, {}, ((50, 40),) * 3, id="small-whole"),
    # N = 200: 128 and 256 both pad to 256, the larger goes
    pytest.param(200, 300, {}, ((256, 300),) * 3, id="n200-ties-to-256"),
    # N = 10,000: 128 pads 112 rows, every larger block 240
    pytest.param(10000, 30522, {}, ((128, 512),) * 3, id="pads-least"),
    # a vocabulary with a tail keeps the 512 block (60 tiles, 198 masked)
    pytest.param(512, 30522, {}, ((512, 512),) * 3, id="vocab-tail"),
    # one block of 256 rows is all of N: enough, whatever the ridge
    pytest.param(256, 30522, {}, ((256, 512),) * 3, id="n-below-the-ridge"),
    pytest.param(4096, 1000, {"block_n": 16, "block_v": 64},
                 ((16, 64),) * 3, id="given-both-kept"),
    pytest.param(4096, 1000, {"block_n": 256}, ((256, 512),) * 3,
                 id="given-rows-kept"),
    pytest.param(4096, 1000, {"block_v": 64}, ((1024, 64),) * 3,
                 id="given-vocab-kept"),
    pytest.param(8, 30522, {"block_n": 128, "block_v": 512},
                 ((8, 512),) * 3, id="given-larger-than-n"),
])
def test_choose_blocks_rule(n, v, given, blocks):
    assert fc._choose_blocks(n, v, 64, jnp.float32, **given) == blocks


def test_choose_blocks_gives_way_to_the_budget(monkeypatch):
    """A wide model in float32: the row block shrinks kernel by kernel (dh
    carries a (bn, D) f32 sum, dw a W-tile-sized one) and the forward's
    vocabulary block before its row block."""
    picked = fc._choose_blocks(32768, 50304, 2048, jnp.float32)
    assert picked == ((1024, 128), (256, 512), (128, 512))
    for (bn, bv), kernel in zip(picked[:2], KERNELS):
        assert fc._vmem_bytes(bn, bv, 2048, 4, kernel) <= fc._VMEM_BUDGET
    # dw is at its floor there (Mosaic's limit is a third above the
    # budget); with nothing that fits, so is every kernel
    assert fc._vmem_bytes(128, 512, 2048, 4, KERNELS[2]) < fc._VMEM_LIMIT
    monkeypatch.setattr(fc, "_VMEM_BUDGET_UNASKED", 1)
    monkeypatch.setattr(fc, "_VMEM_BUDGET", 1)
    assert fc._choose_blocks(32768, 50304, 2048, jnp.float32) == (
        (128, 128), (128, 128), (128, 512))


def _grads(fn, h, w, b, t, ct, **kw):
    return jax.value_and_grad(
        lambda h, w, b: jnp.vdot(fn(h, w, b, t, **kw), ct),
        argnums=(0, 1, 2))(h, w, b)


@pytest.mark.parametrize("layout", ["vd", "dv"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n,v,blocks", [
    # one row block of 1024; 700 = 512 + a tail of 188
    pytest.param(1024, 700, ((1024, 512),) * 3, id="1024x512-tail"),
    # 1536 pads nothing at 512; V = 300 is one tile that is not whole
    # 128-lane tiles (the forward's state is 300 lanes wide)
    pytest.param(1536, 300, ((512, 300),) * 3, id="512x300-whole"),
])
def test_parity_at_the_chosen_blocks(n, v, blocks, dtype, layout):
    """Value and all three gradients against the oracle with NO block
    passed: the call runs at what `_choose_blocks` picks."""
    d = 32
    assert fc._choose_blocks(n, v, d, dtype) == blocks
    rng = np.random.RandomState(7)
    h, w, b, t = _data(rng, n, v, d, dtype)
    if layout == "dv":
        w = w.T
    ct = jnp.asarray(rng.rand(n), jnp.float32)
    vf, gf = _grads(fused_linear_nll, h, w, b, t, ct, w_layout=layout)
    vr, gr = _grads(linear_nll_reference, h, w, b, t, ct, w_layout=layout)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(float(vf), float(vr), rtol=tol)
    for a, r in zip(gf, gr):
        assert a.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", ["vd", "dv"])
def test_row_blocks_that_differ_by_kernel(monkeypatch, layout):
    """Kernels at row blocks of their own (a budget small enough to part
    them: dw's 256 rows under it are short of the ridge, so it takes what
    the larger budget admits): N is padded once, to the largest, which the
    others divide; the padded rows carry ct = 0 and a real lse. The forward
    folds three 128-wide tiles, the last with a tail, into its lanes; dw
    takes V whole."""
    n, v, d = 2000, 300, 64
    monkeypatch.setattr(fc, "_VMEM_BUDGET_UNASKED", 4_000_000)
    blocks = fc._choose_blocks(n, v, d, jnp.float32)
    assert blocks == ((512, 128), (512, 128), (1024, 300))
    rng = np.random.RandomState(8)
    h, w, b, t = _data(rng, n, v, d)
    if layout == "dv":
        w = w.T
    ct = jnp.asarray(rng.rand(n), jnp.float32)
    vf, gf = _grads(fused_linear_nll, h, w, b, t, ct, w_layout=layout)
    vr, gr = _grads(linear_nll_reference, h, w, b, t, ct, w_layout=layout)
    np.testing.assert_allclose(float(vf), float(vr), rtol=2e-5)
    for a, r in zip(gf, gr):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_mxu_operands_follow_the_caller():
    """bf16 h and W reach every dot as bf16 (g is cast to them before the
    backward's second matmul), float32 ones as float32; every dot
    accumulates in float32 and nothing else in the kernels is bf16 math."""
    def dots(dtype):
        h, w, b, t = _data(np.random.RandomState(9), 16, 128, 8, dtype)
        jaxpr = jax.make_jaxpr(jax.grad(lambda h, w, b: jnp.sum(
            fused_linear_nll(h, w, b, t)), argnums=(0, 1, 2)))(h, w, b)
        found = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "dot_general":
                    found.append(tuple(v.aval.dtype for v in eqn.invars)
                                 + (eqn.outvars[0].aval.dtype,))
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (list, tuple)) else [p]):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            walk(sub)
        walk(jaxpr.jaxpr)
        return found

    for dtype in (jnp.bfloat16, jnp.float32):
        found = dots(dtype)
        assert len(found) == 5, found     # fwd 1, dh 2, dw 2
        assert set(found) == {(jnp.dtype(dtype),) * 2
                              + (jnp.dtype(jnp.float32),)}, found
