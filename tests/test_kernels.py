"""hetukern (docs/KERNELS.md): the Pallas kernel tier.

ISSUE 12 acceptance pinned here:
- every kernel has an interpret-mode equality test vs its XLA fallback
  (force vs off through the REAL registry dispatch, both sides under jit
  so they compile through the same XLA pipeline);
- the registry's mode semantics: off = pre-hetukern expression verbatim,
  auto = per-shape fallback (always fallback off-TPU), force = kernel or
  KernelEligibilityError;
- kernels="off" is bit-identical at the executor level (off vs the
  default auto on CPU train the same bits, with zero pallas dispatches);
- the PS sparse-push dedup-sum (sort + reduceat) equals the old
  np.add.at scatter EXACTLY on duplicate-heavy indices;
- the PS-push rows route: an explicit embedding_lookup_gradient_op
  consumed by a PS push skips the dense zeros-table scatter and hands the
  runtime (rows, grads).
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import comm_quant
from hetu_tpu.kernels import (
    registry, embed_grad, csr_spmm, quant_comm, fused_opt,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_stats():
    registry.reset_stats()
    yield
    registry.reset_stats()


def _force(fn):
    @jax.jit
    def wrapped(*a):
        with registry.active("force"):
            return fn(*a)
    return wrapped


def _off(fn):
    @jax.jit
    def wrapped(*a):
        with registry.active("off"):
            return fn(*a)
    return wrapped


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_modes_and_counters():
    rng = np.random.RandomState(0)
    sv = jnp.asarray(rng.randn(128, 128).astype(np.float32))
    seg = jnp.zeros((128,), jnp.int32)
    with registry.active("off"):
        registry.dispatch("fused_embed_grad", sv, seg)
    with registry.active("auto"):     # CPU: eligible shape still falls back
        registry.dispatch("fused_embed_grad", sv, seg)
    with registry.active("force"):
        registry.dispatch("fused_embed_grad", sv, seg)
    s = registry.dispatch_stats()
    assert s[("fused_embed_grad", "off")] == 1
    assert s[("fused_embed_grad", "fallback")] == 1
    # force-mode servings count under the distinct "forced" path so the
    # lint's auto-only fallback_ratio cannot be diluted by smoke runs
    assert s[("fused_embed_grad", "forced")] == 1
    assert registry.fallback_ratio("fused_embed_grad") == 1.0


def test_registry_force_ineligible_raises():
    bad = jnp.ones((16, 20), jnp.float32)      # dim 20: not lane-aligned
    seg = jnp.zeros((16,), jnp.int32)
    with registry.active("force"):
        with pytest.raises(registry.KernelEligibilityError) as e:
            registry.dispatch("fused_embed_grad", bad, seg)
    assert "fused_embed_grad" in str(e.value)
    # the same shape under auto falls back per-call instead
    with registry.active("auto"):
        out = registry.dispatch("fused_embed_grad", bad, seg)
    assert out.shape == (16, 20)
    assert registry.dispatch_stats()[("fused_embed_grad", "fallback")] == 1


def test_registry_mode_resolution(monkeypatch):
    assert registry.resolve_mode("force") == "force"
    monkeypatch.setenv("HETU_KERNELS", "off")
    assert registry.resolve_mode(None) == "off"
    monkeypatch.delenv("HETU_KERNELS")
    assert registry.resolve_mode(None) == "auto"
    with pytest.raises(ValueError):
        registry.resolve_mode("maybe")
    # scopes nest, innermost wins
    with registry.active("off"):
        with registry.active("force"):
            assert registry.current_mode() == "force"
        assert registry.current_mode() == "off"


def test_dispatch_counter_exports_to_telemetry(tmp_path):
    from hetu_tpu import telemetry as tel
    t = tel.activate("metrics", out_dir=str(tmp_path))
    try:
        sv = jnp.ones((128, 128), jnp.float32)
        with registry.active("force"):
            registry.dispatch("fused_embed_grad", sv,
                              jnp.zeros((128,), jnp.int32))
        snap = t.metrics.snapshot()
        key = ('hetu_kernel_dispatch_total'
               '{kernel="fused_embed_grad",path="forced"}')
        assert snap.get(key) == 1.0
    finally:
        tel.shutdown()


# ---------------------------------------------------------------------------
# kernel 1: fused sparse embedding grad
# ---------------------------------------------------------------------------

def test_embed_grad_rows_equality_duplicate_heavy():
    rng = np.random.RandomState(0)
    vec = jnp.asarray(rng.randn(4, 64, 128).astype(np.float32))
    # duplicate-heavy: 256 lookups over only 17 distinct rows
    idx = jnp.asarray(rng.randint(0, 17, (4, 64)))
    f = _force(lambda v, i: embed_grad.embed_grad_rows(v, i, 1000))
    o = _off(lambda v, i: embed_grad.embed_grad_rows(v, i, 1000))
    rows_f, grads_f, cnt_f = f(vec, idx)
    rows_o, grads_o, cnt_o = o(vec, idx)
    assert int(cnt_f) == int(cnt_o) == 17
    assert np.array_equal(np.asarray(rows_f), np.asarray(rows_o))
    # sentinel-padded tail: vocab sentinel + zero grads
    assert np.all(np.asarray(rows_f)[17:] == 1000)
    assert np.all(np.asarray(grads_f)[17:] == 0.0)
    np.testing.assert_allclose(np.asarray(grads_f), np.asarray(grads_o),
                               atol=1e-4)
    # and the sums are RIGHT: compare against a numpy oracle
    fi = np.asarray(idx).reshape(-1)
    fv = np.asarray(vec).reshape(-1, 128)
    want = np.zeros((17, 128), np.float32)
    for r, v in zip(fi, fv):
        want[r] += v
    np.testing.assert_allclose(np.asarray(grads_o)[:17], want, atol=1e-4)


def test_embed_grad_dense_off_is_pre_hetukern_bit_identical():
    rng = np.random.RandomState(1)
    vec = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 50, (32,)))
    shape = (100, 128)
    g = ht.embedding_lookup_gradient_op(
        ht.Variable(name="v", value=np.asarray(vec), trainable=False),
        ht.Variable(name="i", value=np.asarray(idx), dtype=np.int64,
                    trainable=False), shape)
    with registry.active("off"):
        got = g.fn(vec, idx)
    want = embed_grad.embed_grad_dense_xla(vec, idx, shape)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_embed_grad_dense_force_matches_fallback():
    rng = np.random.RandomState(2)
    vec = jnp.asarray(rng.randn(128, 128).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 33, (128,)))
    shape = (64, 128)
    f = _force(lambda v, i: embed_grad.embed_grad_dense(v, i, shape))
    want = embed_grad.embed_grad_dense_xla(vec, idx, shape)
    np.testing.assert_allclose(np.asarray(f(vec, idx)), np.asarray(want),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# kernel 2: CSR spmm
# ---------------------------------------------------------------------------

def test_csr_spmm_equality():
    rng = np.random.RandomState(0)
    nnz, k, n, f = 500, 16, 8, 128
    vals = jnp.asarray(rng.randn(nnz).astype(np.float32))
    rows = jnp.asarray(rng.randint(0, n, nnz).astype(np.int32))
    cols = jnp.asarray(rng.randint(0, k, nnz).astype(np.int32))
    b = jnp.asarray(rng.randn(k, f).astype(np.float32))
    ff = _force(lambda v, r, c, bb: csr_spmm.coo_matmat(v, r, c, n, bb))
    oo = _off(lambda v, r, c, bb: csr_spmm.coo_matmat(v, r, c, n, bb))
    np.testing.assert_allclose(np.asarray(ff(vals, rows, cols, b)),
                               np.asarray(oo(vals, rows, cols, b)),
                               atol=1e-4)


def test_csr_matvec_equality():
    rng = np.random.RandomState(3)
    nnz, k, n = 200, 16, 8
    vals = jnp.asarray(rng.randn(nnz).astype(np.float32))
    rows = jnp.asarray(rng.randint(0, n, nnz).astype(np.int32))
    cols = jnp.asarray(rng.randint(0, k, nnz).astype(np.int32))
    x = jnp.asarray(rng.randn(k).astype(np.float32))
    ff = _force(lambda v, r, c, xx: csr_spmm.coo_matvec(v, r, c, n, xx))
    oo = _off(lambda v, r, c, xx: csr_spmm.coo_matvec(v, r, c, n, xx))
    np.testing.assert_allclose(np.asarray(ff(vals, rows, cols, x)),
                               np.asarray(oo(vals, rows, cols, x)),
                               atol=1e-4)


def test_csr_op_auto_on_cpu_is_fallback():
    """The graph-level csrmm_op under the default mode on CPU must count a
    fallback dispatch, never a pallas one (nothing in the existing test
    matrix changes behavior by default)."""
    from tests.test_ops import run_graph  # same-suite helper
    from hetu_tpu.ndarray import ND_Sparse_Array
    rng = np.random.RandomState(0)
    dense = (rng.rand(6, 5) < 0.4) * rng.randn(6, 5)
    r, c = np.nonzero(dense)
    spv = ND_Sparse_Array(dense[r, c].astype(np.float32), r, c, 6, 5)
    a = ht.graph.ops.matmul.SparseInputOp()
    m = ht.Variable(name="m", value=rng.randn(5, 4).astype(np.float32),
                    trainable=False)
    out = run_graph(ht.csrmm_op(a, m), {a: spv, m: m.value})
    np.testing.assert_allclose(out, dense @ m.value, atol=1e-5)
    s = registry.dispatch_stats()
    assert s.get(("csr_spmm", "pallas")) is None
    assert s.get(("csr_spmm", "fallback"), 0) >= 1


# ---------------------------------------------------------------------------
# kernel 3: quant-fused comm legs (wire payloads must be bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quant_blocks_bit_identical(mode):
    if mode == "fp8" and comm_quant.fp8_dtype() is None:
        pytest.skip("no float8_e4m3fn in this jax build")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4096).astype(np.float32))
    ff = _force(lambda v: quant_comm.quantize_blocks(v, 256, mode))
    oo = _off(lambda v: comm_quant.quantize_blocks(v, 256, mode))
    qf, sf, nf = ff(x)
    qo, so, no = oo(x)
    assert nf == no
    assert np.array_equal(np.asarray(sf), np.asarray(so))
    assert np.array_equal(np.asarray(qf).view(np.uint8),
                          np.asarray(qo).view(np.uint8))
    # dequant leg, same contract
    df = _force(lambda q, s: quant_comm.dequantize_blocks(q, s, 4096, 256))
    do = _off(lambda q, s: comm_quant.dequantize_blocks(q, s, 4096, 256))
    assert np.array_equal(np.asarray(df(qf, sf)), np.asarray(do(qo, so)))


def test_quant_blocks_all_zero_block_and_padding():
    x = np.zeros(300, np.float32)       # 300 pads to 2 blocks of 256
    x[0] = 3.0
    xj = jnp.asarray(x)
    ff = _force(lambda v: quant_comm.quantize_blocks(v, 256, "int8"))
    q, s, n = ff(xj)
    qo, so, no = comm_quant.quantize_blocks(xj, 256, "int8")
    assert n == no == 300
    assert np.array_equal(np.asarray(q), np.asarray(qo))
    assert np.asarray(s)[1] == 0.0      # all-zero block stores scale 0


# ---------------------------------------------------------------------------
# kernel 4: fused optimizer step
# ---------------------------------------------------------------------------

class _AdamCfg:
    beta1, beta2, epsilon, weight_decay, l2reg = 0.9, 0.999, 1e-7, 0.01, 0.0


def test_fused_adam_exact_over_steps():
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(16, 128).astype(np.float32))
    slot_f = {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p),
              "t": jnp.zeros((), jnp.float32)}
    slot_o = {k: v for k, v in slot_f.items()}
    pf, po = p, p
    ff = _force(lambda pp, gg, mm, vv, tt: fused_opt.adam_step(
        _AdamCfg, pp, gg, {"m": mm, "v": vv, "t": tt}, 0.01))
    oo = _off(lambda pp, gg, mm, vv, tt: fused_opt.adam_step(
        _AdamCfg, pp, gg, {"m": mm, "v": vv, "t": tt}, 0.01))
    for step in range(3):
        g = jnp.asarray(rng.randn(16, 128).astype(np.float32))
        pf, slot_f = ff(pf, g, slot_f["m"], slot_f["v"], slot_f["t"])
        po, slot_o = oo(po, g, slot_o["m"], slot_o["v"], slot_o["t"])
    assert np.array_equal(np.asarray(pf), np.asarray(po))
    for k in ("m", "v", "t"):
        assert np.array_equal(np.asarray(slot_f[k]), np.asarray(slot_o[k]))
    assert float(slot_f["t"]) == 3.0


def test_fused_sgd_exact_with_l2():
    class _S:
        l2reg = 0.01
    rng = np.random.RandomState(1)
    p = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    g = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    ff = _force(lambda pp, gg: fused_opt.sgd_step(_S, pp, gg, 0.05))
    oo = _off(lambda pp, gg: fused_opt.sgd_step(_S, pp, gg, 0.05))
    assert np.array_equal(np.asarray(ff(p, g)), np.asarray(oo(p, g)))


def test_fused_adam_odd_shape_padded_exact():
    """Odd-sized params (biases) are eligible — the kernel pads to the
    8x128 tile internally and slices back; still exact vs the XLA rule."""
    rng = np.random.RandomState(4)
    p = jnp.asarray(rng.randn(5, 7).astype(np.float32))
    g = jnp.asarray(rng.randn(5, 7).astype(np.float32))
    slot = {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p),
            "t": jnp.zeros((), jnp.float32)}
    ff = _force(lambda pp, gg: fused_opt.adam_step(_AdamCfg, pp, gg,
                                                   slot, 0.01))
    oo = _off(lambda pp, gg: fused_opt.adam_step(_AdamCfg, pp, gg,
                                                 slot, 0.01))
    pf, sf = ff(p, g)
    po, so = oo(p, g)
    assert pf.shape == (5, 7)
    # slots are exact; the param update may differ by 1 ulp — XLA makes
    # different FMA decisions for the padded-shape program (the same
    # compile-level noise class the jit-vs-eager gotcha documents)
    assert np.array_equal(np.asarray(sf["m"]), np.asarray(so["m"]))
    assert np.array_equal(np.asarray(sf["v"]), np.asarray(so["v"]))
    np.testing.assert_allclose(np.asarray(pf), np.asarray(po),
                               atol=1e-6, rtol=0)
    class _S:
        l2reg = 0.0
    sgf = _force(lambda pp, gg: fused_opt.sgd_step(_S, pp, gg, 0.05))(p, g)
    sgo = _off(lambda pp, gg: fused_opt.sgd_step(_S, pp, gg, 0.05))(p, g)
    np.testing.assert_allclose(np.asarray(sgf), np.asarray(sgo),
                               atol=1e-6, rtol=0)


def test_fused_adam_odd_shape_falls_back_in_auto():
    p = jnp.ones((5, 7), jnp.float32)
    slot = {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p),
            "t": jnp.zeros((), jnp.float32)}
    with registry.active("auto"):
        new_p, new_slot = fused_opt.adam_step(_AdamCfg, p,
                                              jnp.ones_like(p), slot, 0.01)
    assert new_p.shape == (5, 7)
    assert registry.dispatch_stats()[("fused_adam", "fallback")] == 1


# ---------------------------------------------------------------------------
# executor level: off is bit-identical, force trains
# ---------------------------------------------------------------------------

def _mlp_executor(kernels, width=128, seed=7):
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    w1 = ht.init.random_normal((width, width), stddev=0.05, name="w1")
    w2 = ht.init.random_normal((width, 8), stddev=0.05, name="w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    opt = ht.optim.AdamOptimizer(0.01).minimize(loss)
    ex = ht.Executor({"train": [loss, opt]}, ctx=ht.cpu(0), seed=seed,
                     kernels=kernels)
    return ex, x, y_


def _train(ex, x, y_, steps=4, width=128):
    rng = np.random.RandomState(0)
    bx = rng.randn(16, width).astype(np.float32)
    by = np.eye(8, dtype=np.float32)[rng.randint(0, 8, 16)]
    losses = []
    for _ in range(steps):
        losses.append(float(np.mean(
            ex.run("train", feed_dict={x: bx, y_: by})[0].asnumpy())))
    params = {n.name: np.asarray(ex.state["params"][id(n)])
              for n in ex.param_nodes}
    return losses, params


def test_executor_off_bit_identical_to_default_auto_on_cpu():
    """kernels='off' must train the same BITS as the default (auto) on
    CPU — auto's off-TPU fallback IS the pre-hetukern expression — and
    the dispatch counter must show zero pallas servings either way."""
    ex_off, x1, y1 = _mlp_executor("off")
    l_off, p_off = _train(ex_off, x1, y1)
    registry.reset_stats()
    ex_auto, x2, y2 = _mlp_executor("auto")
    l_auto, p_auto = _train(ex_auto, x2, y2)
    assert l_off == l_auto
    for k in p_off:
        assert np.array_equal(p_off[k], p_auto[k])
    s = registry.dispatch_stats()
    assert not any(path == "pallas" for _k, path in s)
    assert s.get(("fused_adam", "fallback"), 0) >= 1


def test_executor_force_trains_and_dispatches_pallas():
    ex_f, xf, yf = _mlp_executor("force")
    l_f, p_f = _train(ex_f, xf, yf)
    ex_o, xo, yo = _mlp_executor("off")
    l_o, p_o = _train(ex_o, xo, yo)
    # interpret-mode kernels inside the same jit pipeline: the fused-adam
    # math is the same expression sequence, losses agree to f32 noise
    np.testing.assert_allclose(l_f, l_o, atol=1e-5)
    assert registry.dispatch_stats()[("fused_adam", "forced")] >= 1


def test_hetuconfig_rejects_bad_kernels_mode():
    x = ht.Variable(name="x", trainable=False)
    with pytest.raises(ValueError, match="kernels"):
        ht.Executor({"d": [ht.relu_op(x)]}, ctx=ht.cpu(0),
                    kernels="sometimes")


# ---------------------------------------------------------------------------
# satellite: PS dedup-sum sort+reduceat == np.add.at, exactly
# ---------------------------------------------------------------------------

def test_ps_dedup_sum_reduceat_exact():
    from hetu_tpu.graph.ps_runtime import _dedup_sum_rows
    rng = np.random.RandomState(0)
    # duplicate-heavy (zipf-ish): 5000 pushes over ~40 distinct rows
    flat_idx = (rng.zipf(1.2, 5000) % 40).astype(np.int64)
    g = rng.randn(5000, 16).astype(np.float32)
    uniq, inv = np.unique(flat_idx, return_inverse=True)
    want = np.zeros((uniq.size, 16), np.float32)
    np.add.at(want, inv, g)                      # the old scatter loop
    got_idx, got = _dedup_sum_rows(flat_idx, g)
    assert got.dtype == np.float32
    assert np.array_equal(got_idx, uniq)
    # reduceat sums pairwise (more accurate than the sequential scatter):
    # equal to the old path within f32 rounding, and at least as close to
    # the float64 oracle
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    oracle = np.zeros((uniq.size, 16), np.float64)
    np.add.at(oracle, inv, g.astype(np.float64))
    assert (np.abs(got - oracle).max()
            <= np.abs(want - oracle).max() + 1e-6)
    # no-duplicate fast path: inputs pass through untouched
    ni = np.arange(8, dtype=np.int64)
    ng = rng.randn(8, 16).astype(np.float32)
    oi, og = _dedup_sum_rows(ni, ng)
    assert oi is ni and og is ng


# ---------------------------------------------------------------------------
# satellite: PS-push rows route (no dense zeros-table on the push path)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ps_push_rows_route():
    from hetu_tpu.ps.local_cluster import local_cluster
    from hetu_tpu.graph.ops.embedding import IndexedRows
    vocab, dim = 50, 8
    with local_cluster(n_servers=1, n_workers=1):
        table = ht.init.zeros((vocab, dim), name="emb_rows_route",
                              is_embed=True)
        idx = ht.Variable(name="idx", dtype=np.int64, trainable=False)
        vec = ht.Variable(name="vec", trainable=False)
        look = ht.embedding_lookup_op(table, idx)
        loss = ht.reduce_mean_op(look, [0, 1])
        g = ht.embedding_lookup_gradient_op(vec, idx, (vocab, dim))
        push = ht.parameterServerCommunicate_op(g, ps_id=table.name)
        ex = ht.Executor({"train": [loss, push]}, ctx=ht.cpu(0),
                         comm_mode="PS", seed=0, prefetch=False)
        try:
            # the rewire flipped the grad op into rows mode
            assert g.rows_mode is True
            assert push.ps_param_node is table
            bi = np.array([3, 7, 3, 9], np.int64)     # duplicate row 3
            bv = np.arange(4 * dim, dtype=np.float32).reshape(4, dim)
            ex.run("train", feed_dict={idx: bi, vec: bv})
            # the traced push output is the compact rows pair
            grad_out = ex.subexecutors["train"].ps_comm_ops
            assert len(grad_out) == 1
            ex.ps_runtime.drain()
            p = ex.ps_runtime.params[id(table)]
            got = ex.ps_runtime.pull_sparse_rows(
                p, np.array([3, 7, 9, 0], np.int64))
            # server-side prescaled SGD: w += -lr * summed_grad
            lr = ex.ps_runtime._prescale_lr(0)
            want3 = -(bv[0] + bv[2]) * lr
            np.testing.assert_allclose(got[0], want3, atol=1e-5)
            np.testing.assert_allclose(got[1], -bv[1] * lr, atol=1e-5)
            np.testing.assert_allclose(got[2], -bv[3] * lr, atol=1e-5)
            np.testing.assert_allclose(got[3], np.zeros(dim), atol=0)

            # guard: a grad op with ANOTHER consumer (here an eval
            # target needing the dense table) must stay dense — flipping
            # it would hand that consumer an IndexedRows pair
            os.environ["HETU_PS_ID_BASE"] = "1000"
            table2 = ht.init.zeros((vocab, dim), name="emb_dense_kept",
                                   is_embed=True)
            idx2 = ht.Variable(name="idx2", dtype=np.int64,
                               trainable=False)
            vec2 = ht.Variable(name="vec2", trainable=False)
            look2 = ht.embedding_lookup_op(table2, idx2)
            loss2 = ht.reduce_mean_op(look2, [0, 1])
            g2 = ht.embedding_lookup_gradient_op(vec2, idx2, (vocab, dim))
            push2 = ht.parameterServerCommunicate_op(g2, ps_id=table2.name)
            ex2 = ht.Executor({"train": [loss2, g2, push2]}, ctx=ht.cpu(0),
                              comm_mode="PS", seed=0, prefetch=False)
            try:
                assert g2.rows_mode is False
                out2 = ex2.run("train", feed_dict={idx2: bi, vec2: bv})
                assert out2[1].asnumpy().shape == (vocab, dim)
            finally:
                ex2.close()
                os.environ.pop("HETU_PS_ID_BASE", None)
        finally:
            # finalize the process-singleton worker INSIDE the cluster
            # context — a live worker leaking past teardown poisons the
            # next test's cluster bootstrap (the test_elastic_executor
            # idiom)
            ex.close()
            from hetu_tpu import ps as ps_pkg
            ps_pkg.worker_finish()


# ---------------------------------------------------------------------------
# satellite: roofline families + hetutop kernels panel
# ---------------------------------------------------------------------------

def test_roofline_covers_kernel_families():
    from hetu_tpu.telemetry.profiler import roofline_rows
    x = ht.Variable(name="x", value=np.ones((16, 64), np.float32),
                    trainable=False)
    w = ht.Variable(name="w_r", value=np.ones((64, 8), np.float32))
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    opt = ht.optim.AdamOptimizer(0.01).minimize(loss)
    vec = ht.Variable(name="v_r", value=np.ones((16, 8), np.float32),
                      trainable=False)
    idx = ht.Variable(name="i_r", value=np.zeros(16, np.int64),
                      dtype=np.int64, trainable=False)
    eg = ht.embedding_lookup_gradient_op(vec, idx, (100, 8))
    rows = roofline_rows([loss, opt, eg])
    fams = {r.family: r for r in rows}
    # fused-adam family: one pass over grad+m+v+param (10 flops, 7 moves)
    adam = next((r for r in rows
                 if r.family.startswith("Optimizer_Adam")), None)
    assert adam is not None
    n = 64 * 8
    assert adam.flops == pytest.approx(10.0 * n)
    assert adam.bytes == pytest.approx(7.0 * 4.0 * n)
    # fused-embed-grad family: one add per input grad element, HBM-bound
    egr = fams.get("EmbeddingLookUpGradient")
    assert egr is not None and egr.bound == "memory"
    assert egr.flops == pytest.approx(2.0 * 16 * 8)   # training 2x mult


def test_hetutop_kernels_panel(tmp_path):
    from hetu_tpu.telemetry import hetutop
    d = tmp_path / "tel"
    d.mkdir()
    recs = [
        {"kind": "run_info", "ts": 1.0, "rank": 0, "device_kind": "cpu",
         "peak_tflops": None, "peak": "unknown"},
        {"kind": "step", "ts": 2.0, "rank": 0, "sub": "train", "step": 1,
         "step_ms": 5.0,
         "metrics": {
             'hetu_kernel_dispatch_total{kernel="fused_adam",path="pallas"}': 3.0,
             'hetu_kernel_dispatch_total{kernel="csr_spmm",path="fallback"}': 2.0,
         }},
    ]
    (d / "metrics-r0.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    frame = hetutop.render_frame(hetutop.gather(str(d)))
    assert "kernels:" in frame
    assert "fused_adam pallas:3" in frame
    assert "csr_spmm fallback:2" in frame


def test_spmd_scope_declines_kernels():
    """A GSPMD multi-device scope (the executor's spmd flag) makes every
    kernel ineligible — a bare pallas_call has no SPMD partitioning rule,
    so auto must fall back and force must refuse (docs/KERNELS.md)."""
    sv = jnp.ones((128, 128), jnp.float32)
    seg = jnp.zeros((128,), jnp.int32)
    with registry.active("auto", spmd=True):
        assert registry.in_spmd_scope()
        ok, why = registry.eligibility_of("fused_embed_grad", sv, seg)
        assert not ok and "GSPMD" in why
    with registry.active("force", spmd=True):
        with pytest.raises(registry.KernelEligibilityError):
            registry.dispatch("fused_embed_grad", sv, seg)
    # outside the scope the same call is eligible again
    with registry.active("force"):
        assert not registry.in_spmd_scope()
        registry.dispatch("fused_embed_grad", sv, seg)


def test_rows_mode_reset_across_executors():
    """Graph nodes are shared between executors: a second build over a
    graph whose embedding-grad op an earlier (hypothetical) executor
    flipped to rows mode must reset it to dense when its own conditions
    don't wire the rows route (no PS runtime here at all)."""
    vec = ht.Variable(name="v_reset", trainable=False)
    idx = ht.Variable(name="i_reset", dtype=np.int64, trainable=False)
    g = ht.embedding_lookup_gradient_op(vec, idx, (50, 8))
    g.to_rows()          # simulate a previous executor's flip
    assert g.rows_mode
    ex = ht.Executor({"d": [g]}, ctx=ht.cpu(0))
    assert g.rows_mode is False     # reset at build: dense again
    out = ex.run("d", feed_dict={vec: np.ones((4, 8), np.float32),
                                 idx: np.array([1, 2, 1, 3])})
    assert out[0].asnumpy().shape == (50, 8)
