"""kanana-2-30b-a3b's kernels, which need no trained system (cut from
test_kanana_model.py, ISSUE 42): the rotary columns against HF's and the
kernel that turns q's in one pass (ISSUE 40), the flash kernels at unequal
head widths, latent attention's tiles, and what `remat` may keep by kind."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_harness import to_heads, to_rows
from hetu_tpu.kernels import flash_attention as fa
from hetu_tpu.kernels import rope as rope_kernel
from hetu_tpu.models import hf_deepseek_v3 as hd, transformer as tfm
from hetu_tpu.telemetry import tracing
from model_harness import rope_kernel_taken  # noqa: F401
from test_kanana_model import ROTATED, SHARE, reference


# -- the rotary columns ------------------------------------------------------------

def test_rope_interleaved_is_hfs_up_to_one_permutation_of_the_pairs():
    """`_rope_interleaved` leaves a pair where it is; HF moves the even
    columns to the first half: the same numbers, and every q . k the same."""
    B, T, H, nope, rope = 2, 16, 3, 8, 6
    hdim = nope + rope
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, H * hdim))
    got = tfm._rope_interleaved(x, 0, 1e4, hdim, nope).reshape(B, T, H, hdim)
    x4 = x.reshape(B, T, H, hdim).transpose(0, 2, 1, 3)
    want = reference._rope_interleave(x4[..., nope:], 1e4).transpose(
        0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got[..., :nope]),
                               np.asarray(x.reshape(B, T, H, hdim)[..., :nope]))
    np.testing.assert_allclose(np.asarray(got[..., nope::2]),
                               np.asarray(want[..., :rope // 2]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[..., nope + 1::2]),
                               np.asarray(want[..., rope // 2:]), atol=1e-6)
    # the one rotary key of a token: a "head" that is all rotary
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, rope))
    got_k = tfm._rope_interleaved(k, 0, 1e4, rope, 0)
    want_k = reference._rope_interleave(k[:, None], 1e4)[:, 0]
    np.testing.assert_allclose(np.asarray(got_k[..., 0::2]),
                               np.asarray(want_k[..., :rope // 2]), atol=1e-6)


def _forward_and_cotangent(rotate, x, g, pos0, hdim, nope):
    out, vjp = jax.vjp(lambda x: rotate(x, pos0, 1e6, hdim, nope), x)
    return out, vjp(g)[0]


@pytest.mark.parametrize("pos0", [0, 7])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads,nope,rot", [
    pytest.param(2, 128, 64, id="kanana-widths"),
    pytest.param(4, 128, 64, id="two-column-blocks"),
    pytest.param(2, 0, 128, id="all-rotary")])
def test_rope_kernel_is_rope_interleaved_forward_and_transposed(
        monkeypatch, heads, nope, rot, dtype, pos0):
    """The one-pass kernel (interpreted) against the expression it replaces,
    both compiled: the forward result (the same float32 products and sum in
    the same order) and the cotangent of the same g (`jax.vjp` of the
    reference scatters two rolls, the kernel turns by the opposite angle)
    within one unit of the output dtype, the columns that pass EXACTLY the
    input; three row blocks, and one period of columns a block so that four
    heads are two column blocks. On the chip both are equal to the bit at
    (4, 8192, 6144) (PERF.md, PR 40); this host's compiler contracts a
    product and the sum into one rounding in one program and not the other
    on a few entries in 100,000."""
    hdim, B, T = nope + rot, 2, 96
    monkeypatch.setattr(rope_kernel, "_BLOCK_BYTES", 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, heads * hdim),
                          jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape,
                          jnp.float32).astype(dtype)
    period = rope_kernel._period(hdim)
    assert rope_kernel._blocks(x.shape, hdim, nope, x.dtype.itemsize) == (
        32, period)
    assert heads * hdim // period == (2 if heads == 4 or nope == 0 else 1)
    want, dwant = jax.jit(lambda x, g: _forward_and_cotangent(
        tfm._rope_interleaved, x, g, pos0, hdim, nope))(x, g)
    got, dgot = jax.jit(lambda x, g: _forward_and_cotangent(
        rope_kernel.rope_interleaved, x, g, pos0, hdim, nope))(x, g)
    assert got.dtype == dgot.dtype == dtype
    passes = np.arange(heads * hdim) % hdim < nope
    for a, b, of in ((got, want, x), (dgot, dwant, g)):
        a, b, of = (np.asarray(v, np.float64) for v in (a, b, of))
        np.testing.assert_array_equal(a[..., passes], of[..., passes])
        # one unit of the output dtype at the size of the two terms summed
        # (a sum that cancels keeps its terms' rounding)
        terms = np.abs(of) + np.abs(of).reshape(B, T, -1, 2)[
            ..., ::-1].reshape(of.shape)
        assert np.all(np.abs(a - b) <= float(jnp.finfo(dtype).eps) * terms)
    # forward the order of operations is the reference's
    assert np.mean(np.asarray(got != want)) < 1e-3
    assert rope_kernel.ROPE_PAIRS in str(jax.make_jaxpr(
        lambda x: rope_kernel.rope_interleaved(x, pos0, 1e6, hdim, nope))(x))


@pytest.mark.parametrize("heads,nope,rot,T,mesh_size,kernel", [
    pytest.param(2, 128, 64, 32, 1, True, id="served"),
    pytest.param(3, 128, 64, 32, 1, False, id="width-no-period-divides"),
    pytest.param(4, 32, 16, 32, 1, False, id="toy-widths"),
    pytest.param(2, 128, 64, 24, 1, False, id="rows-no-block-divides"),
    pytest.param(2, 128, 64, 32, 4, False, id="under-a-mesh")])
def test_rope_q_takes_the_kernel_by_backend_and_shape(
        rope_kernel_taken, heads, nope, rot, T, mesh_size, kernel):
    """`_rope_q` on a TPU (the fixture's patch): the kernel where its blocks
    divide the call, `_rope_interleaved` anywhere else, and the same array
    either way; off a TPU always the reference."""
    import types
    cfg = dataclasses.replace(
        hd.config_from_hf(SHARE),
        mla=tfm.MLAConfig(kv_rank=32, nope_dim=nope, rope_dim=rot, v_dim=24))
    mesh = None if mesh_size == 1 else types.SimpleNamespace(size=mesh_size)
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (2, T, heads * (nope + rot))).astype(jnp.bfloat16)
    want = tfm._rope_interleaved(q, 0, cfg.rope_theta, nope + rot, nope)
    got = tfm._rope_q(q, cfg, mesh)
    assert rope_kernel_taken == [q.shape] * kernel
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_rope_q_off_a_tpu_is_rope_interleaved(monkeypatch):
    monkeypatch.setattr(rope_kernel, "_rotate", None)   # never reached
    cfg = hd.config_from_hf(ROTATED)
    q = jnp.ones((2, 32, 2 * 192), jnp.bfloat16)
    assert not rope_kernel.takes(q, 192, 128)
    np.testing.assert_array_equal(
        np.asarray(tfm._rope_q(q, cfg, None), np.float32),
        np.asarray(tfm._rope_interleaved(q, 0, cfg.rope_theta, 192, 128),
                   np.float32))


# -- the same kernel on rotate-half columns (ISSUE 50) -----------------------------

LAGUNA_YARN = tfm.YarnConfig(factor=64.0, original_max_len=4096,
                             beta_fast=64.0, beta_slow=1.0,
                             attention_factor=1.4158883083359672)


@pytest.mark.parametrize("B,heads,hdim,rot,yarn,dtype,pos0,at", [
    pytest.param(2, 16, 128, 0, None, jnp.bfloat16, 0, None,
                 id="olmoe-16-heads-of-128"),
    pytest.param(1, 16, 128, 0, None, jnp.bfloat16, 0, None,
                 id="ouro-one-sequence"),
    pytest.param(1, 48, 128, 64, LAGUNA_YARN, jnp.bfloat16, 0, None,
                 id="laguna-full-half-a-head-under-yarn"),
    pytest.param(1, 64, 128, 0, None, jnp.bfloat16, 0, None,
                 id="laguna-window-64-heads"),
    pytest.param(2, 32, 64, 0, None, jnp.bfloat16, 0, None,
                 id="lfm2-heads-of-64"),
    pytest.param(2, 16, 64, 0, None, jnp.bfloat16, 0, None,
                 id="keye-index-query"),
    pytest.param(2, 4, 128, 0, None, jnp.float32, 0, None, id="f32"),
    pytest.param(2, 4, 128, 64, LAGUNA_YARN, jnp.bfloat16, 7, None,
                 id="pos0-7"),
    pytest.param(1, 2, 256, 128, None, jnp.bfloat16, 0, None,
                 id="two-lane-tiles-a-head-one-passes"),
    pytest.param(2, 8, 128, 0, None, jnp.bfloat16, 0, (0, 4 * 128),
                 id="q-in-the-fused-projection"),
    pytest.param(2, 8, 128, 64, LAGUNA_YARN, jnp.bfloat16, 0,
                 (4 * 128, 2 * 128), id="k-in-the-fused-projection")])
def test_rope_kernel_is_rope_forward_and_transposed(
        B, heads, hdim, rot, yarn, dtype, pos0, at):
    """The one-pass kernel (interpreted) against `_rope`, both compiled, at
    the rotary cells' head sizes: the forward result (the same float32
    products and sum in the same order, a passing column EXACTLY its input)
    and the cotangent of the same g (`jax.vjp` of the reference scatters two
    rolls, the kernel turns by the opposite angle) within one unit of the
    output dtype: this host's compiler contracts a product and the sum into
    one rounding in one program and not the other on a few entries in
    100,000; on the chip both are equal to the bit (`chip_smoke.py`'s
    kernels phase asserts it). Three row blocks. `at`: a column
    range of a wider array, read where it stands, its cotangent laid into
    the array's width."""
    T, theta = 96, 5e5
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, heads * hdim),
                          jnp.float32).astype(dtype)
    lo, width = at or (0, x.shape[-1])
    g = jax.random.normal(jax.random.PRNGKey(1), (B, T, width),
                          jnp.float32).astype(dtype)
    assert rope_kernel._blocks(x.shape, hdim, 0, x.dtype.itemsize, rot,
                               at)[0] == 32

    def both(rotate):
        out, vjp = jax.vjp(rotate, x)
        return out, vjp(g)[0]
    want, dwant = jax.jit(lambda: both(lambda x: tfm._rope(
        x[..., lo:lo + width], pos0, theta, hdim, rot, yarn)))()
    got, dgot = jax.jit(lambda: both(lambda x: rope_kernel.rope_halves(
        x, pos0, theta, hdim, rot, yarn, at)))()
    assert got.dtype == dgot.dtype == dtype and dgot.shape == x.shape
    dist = (rot or hdim) // 2
    x64 = np.asarray(x, np.float64)[..., lo:lo + width]
    for a, b, of in ((got, want, x64),
                     (dgot[..., lo:lo + width], dwant[..., lo:lo + width],
                      np.asarray(g, np.float64))):
        a, b = (np.asarray(v, np.float64) for v in (a, b))
        # one unit of the output dtype at the size of the two terms summed
        # (a sum that cancels keeps its terms' rounding; under YaRN the
        # terms carry its attention factor)
        terms = (np.abs(of) + np.maximum(np.abs(np.roll(of, dist, -1)),
                                         np.abs(np.roll(of, -dist, -1)))) * (
            yarn.attention_factor if yarn else 1.0)
        assert np.all(np.abs(a - b) <= float(jnp.finfo(dtype).eps) * terms)
    # forward the order of operations is the reference's
    assert np.mean(np.asarray(got != want)) < 1e-3
    outside = np.ones(x.shape[-1], bool)
    outside[lo:lo + width] = False
    assert not np.asarray(dgot, np.float32)[..., outside].any()
    passes = np.arange(width) % hdim >= (rot or hdim)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[..., passes],
        np.asarray(x, np.float32)[..., lo:lo + width][..., passes])
    text = str(jax.make_jaxpr(lambda x: rope_kernel.rope_halves(
        x, pos0, theta, hdim, rot, yarn, at))(x))
    assert rope_kernel.ROPE_HALVES in text
    assert rope_kernel.ROPE_PAIRS not in text


@pytest.mark.parametrize("shape,hdim,rot,kw,served", [
    pytest.param((2, 32, 16 * 128), 128, 0, {}, True, id="olmoe"),
    pytest.param((1, 32, 48 * 128), 128, 64, {}, True, id="laguna-full"),
    pytest.param((2, 32, 32 * 64), 64, 0, {}, True, id="heads-of-64"),
    pytest.param((2, 32, 2 * 256), 256, 128, {}, True,
                 id="a-head-of-two-lane-tiles"),
    pytest.param((1, 32, 64 * 128), 128, 0, {"at": (48 * 128, 8 * 128)},
                 True, id="k-in-the-projection"),
    pytest.param((2, 32, 16 * 128), 128, 0, {"mesh": 4}, False,
                 id="under-a-mesh"),
    pytest.param((2, 32, 64), 64, 0, {}, False,
                 id="keye-index-key-64-wide"),
    pytest.param((2, 32, 4 * 96), 96, 0, {}, False, id="a-head-of-96"),
    pytest.param((2, 32, 2 * 256), 256, 0, {}, False,
                 id="partner-in-another-lane-tile"),
    pytest.param((2, 32, 16 * 128), 128, 0, {"pos0": "traced"}, False,
                 id="traced-pos0"),
    pytest.param((2, 1, 16 * 128), 128, 0, {}, False, id="decode-T-1"),
    pytest.param((2, 24, 16 * 128), 128, 0, {}, False,
                 id="rows-no-block-divides"),
    pytest.param((1, 32, 64 * 128), 128, 0, {"at": (64, 8 * 128)}, False,
                 id="a-range-off-the-period"),
    pytest.param((32, 16 * 128), 128, 0, {}, False, id="no-batch-axis")])
def test_takes_serves_halves_by_backend_and_shape(
        rope_kernel_taken, monkeypatch, shape, hdim, rot, kw, served):
    """`rope.takes` for rotate-half columns: on a TPU (the fixture's patch)
    by the shape alone, the partner in the column's own lane tile; a mesh, a
    traced position, decode's one row and every shape the blocks do not
    divide take `_rope`; off a TPU everything does."""
    import types
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kw = dict(kw)
    if "mesh" in kw:
        kw["mesh"] = types.SimpleNamespace(size=kw["mesh"])
    seen = []
    if kw.get("pos0") == "traced":
        jax.make_jaxpr(lambda p: seen.append(rope_kernel.takes(
            x, hdim, rot=rot, **dict(kw, pos0=p))) or p)(0)
    else:
        seen.append(rope_kernel.takes(x, hdim, rot=rot, **kw))
    assert seen == [served]
    monkeypatch.setattr(rope_kernel, "_on_tpu", lambda: False)
    if kw.get("pos0") != "traced":
        assert not rope_kernel.takes(x, hdim, rot=rot, **kw)


def _heads_cfg(**kw):
    return tfm.TransformerConfig(**{**dict(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=1, d_ff=64, max_seq_len=64, rope=True, use_pos_emb=False,
        causal=True, norm="rmsnorm", dtype=jnp.bfloat16), **kw})


def _split_heads_as_it_was(qkv, p, cfg):
    """`_split_heads` of a rotary stack without the kernel: the rotation in
    `_rope`'s whole-width form (no mesh, no multiplier, small tables)."""
    nh, hd, nkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    B, T, _ = qkv.shape
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    if cfg.qk_norm == "head":
        q = tfm._rms_norm_heads(q, p["q_norm"], cfg.ln_eps)
        k = tfm._rms_norm_heads(k, p["k_norm"], cfg.ln_eps)
    rope = (0, cfg.rope_theta, hd, cfg.rope_dim, cfg.rope_yarn)
    q, k = tfm._rope(q, *rope), tfm._rope(k, *rope)
    k, v = (jnp.repeat(x.reshape(B, T, nkv, hd), nh // nkv,
                       axis=2).reshape(B, T, nh * hd) for x in (k, v))
    return q, k, v


@pytest.mark.parametrize("kw,T,calls", [
    pytest.param({}, 32, [(2, 32, 8 * 128)] * 2, id="in-the-projection"),
    pytest.param({"rope_dim": 64, "rope_yarn": LAGUNA_YARN}, 32,
                 [(2, 32, 8 * 128)] * 2, id="half-a-head-under-yarn"),
    pytest.param({"qk_norm": "head"}, 32,
                 [(2, 32, 4 * 128), (2, 32, 2 * 128)], id="after-qk-norm"),
    pytest.param({"d_head": 96}, 32, [], id="a-head-of-96"),
    pytest.param({}, 24, [], id="rows-no-block-divides")])
def test_split_heads_takes_the_kernel_for_q_and_k_or_for_neither(
        rope_kernel_taken, kw, T, calls):
    """`_split_heads` on a TPU (the fixture's patch): q and k in one pass
    each, read out of the fused projection where nothing has touched them
    (the array the kernel is handed is the projection itself) and after
    QK-norm as arrays of their own, under the rotation's scope; a shape the
    blocks do not divide keeps `_rope` and its scope. The same q, k, v to
    the bit either way, and their cotangents within a unit."""
    cfg = _heads_cfg(**kw)
    nh, hd, nkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    qkv = jax.random.normal(jax.random.PRNGKey(3), (2, T, (nh + 2 * nkv) * hd),
                            jnp.float32).astype(jnp.bfloat16)
    p = {"q_norm": 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (hd,)),
         "k_norm": 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (hd,))}
    new = lambda qkv: tfm._split_heads(qkv, p, cfg, None, "flash")
    old = lambda qkv: _split_heads_as_it_was(qkv, p, cfg)
    got, vjp = jax.vjp(new, qkv)
    want, vjp_was = jax.vjp(old, qkv)
    assert rope_kernel_taken == calls
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    ct = tuple(jax.random.normal(jax.random.PRNGKey(6 + i), a.shape,
                                 jnp.float32).astype(a.dtype)
               for i, a in enumerate(got))
    np.testing.assert_allclose(np.asarray(vjp(ct)[0], np.float32),
                               np.asarray(vjp_was(ct)[0], np.float32),
                               atol=0.07, rtol=0.01)
    scoped = tracing.SCOPE_ATTN_ROPE in jax.jit(new).lower(qkv).as_text(
        debug_info=True)
    assert scoped == bool(calls)


def test_the_indexers_query_takes_the_kernel_and_its_key_does_not(
        rope_kernel_taken, monkeypatch):
    """keye's indexer on a TPU (the fixture's patch): the 16 query heads of
    64 columns side by side turn in the kernel, the ONE key head of 64
    columns, half a lane tile, in `_rope`; both are `_rope`'s arrays."""
    cfg = _heads_cfg(dsa=tfm.DSAConfig(n_heads=16, head_dim=64, top_k=8))
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    h = jax.random.normal(ks[0], (2, 32, 64), jnp.float32).astype(cfg.dtype)
    p = {"wq_idx": jax.random.normal(ks[1], (64, 16 * 64)),
         "wk_idx": jax.random.normal(ks[2], (64, 64)),
         "ww_idx": jax.random.normal(ks[3], (64, 16)),
         "k_idx_norm_scale": jnp.ones((64,)),
         "k_idx_norm_bias": jnp.zeros((64,))}
    got = tfm._dsa_index(h, p, cfg)
    assert rope_kernel_taken == [(2, 32, 16 * 64)]
    rope_kernel_taken.clear()
    monkeypatch.setattr(rope_kernel, "_on_tpu", lambda: False)
    want = tfm._dsa_index(h, p, cfg)
    assert rope_kernel_taken == []
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="whole-heads"),
    pytest.param({"rope_dim": 64, "rope_yarn": LAGUNA_YARN}, id="yarn"),
    pytest.param({"qk_norm": "head"}, id="qk-norm")])
def test_split_heads_off_a_tpu_lowers_to_what_it_did(monkeypatch, kw):
    """Off a TPU the kernel is never asked for (`_rotate` is not reached) and
    `_split_heads` lowers to the text of the expression it was, to the
    character."""
    monkeypatch.setattr(rope_kernel, "_rotate", None)
    cfg = _heads_cfg(**kw)
    nh, hd, nkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    qkv = jax.ShapeDtypeStruct((2, 32, (nh + 2 * nkv) * hd), jnp.bfloat16)
    p = {"q_norm": jnp.ones((hd,)), "k_norm": jnp.ones((hd,))}
    text = lambda f: re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(f).lower(
        qkv).as_text()).replace("jit__lambda_", "jit_f")
    jax.clear_caches()
    was = text(lambda qkv: _split_heads_as_it_was(qkv, p, cfg))
    jax.clear_caches()
    now = text(lambda qkv: tfm._split_heads(qkv, p, cfg, None, "flash"))
    assert now == was and "rope" not in now


# -- the kernels at two widths -----------------------------------------------------

@pytest.mark.parametrize("blocks", [(None, None), (128, 128), (64, 128)],
                         ids=["one-tile", "tiles-128", "uneven"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_at_unequal_widths_matches_the_dot_path(blocks, causal):
    """q . k at 48 columns a head, p . v at 32: forward through the public
    entry, the three gradients through the Pallas backward kernels
    (interpreted; the public entry takes the XLA fallback off the chip) and
    through that fallback, against the unfused reference."""
    b, h, s, d, dv = 2, 4, 256, 48, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (b, s, h * d)) for i in (0, 1))
    v, do = (jax.random.normal(ks[i], (b, s, h * dv)) for i in (2, 3))
    bq, bk = blocks

    def ref(q, k, v):
        return to_rows(fa.mha_reference(
            *(to_heads(x, h) for x in (q, k, v)), causal))

    out = fa.flash_attention_btd((q, k, v), h, causal, block_q=bq,
                                 block_k=bk)
    assert out.shape == (b, s, h * dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=2e-6)
    want = jax.vjp(ref, q, k, v)[1](do)
    scale = 1.0 / d ** 0.5
    o, lse = fa._fwd_pallas((q, k, v), h, None, scale, causal, bq, bk,
                            interpret=True)
    got = fa._bwd_pallas(((q, k, v), o, lse, None), do, n_heads=h,
                         scale=scale, causal=causal, block_q=bq, block_k=bk,
                         interpret=True)
    kernels = fa._choose_tiles(s, d, q.dtype, causal, h, bq, bk, dv)[2]
    assert (fa.FLASH_BWD in kernels) == (blocks == (None, None))
    fallback = jax.vjp(lambda q, k, v: fa.flash_attention_btd(
        (q, k, v), h, causal, block_q=bq, block_k=bk), q, k, v)[1](do)
    for name, a, c, w in zip("qkv", got, fallback, want):
        assert a.shape == w.shape and c.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-5)
        np.testing.assert_allclose(np.asarray(c), np.asarray(w), atol=1e-5)


@pytest.mark.parametrize("s,d,heads", [(512, 64, 12), (128, 64, 12),
                                       (4096, 128, 16), (8192, 64, 32)])
def test_equal_widths_choose_what_they_chose(s, d, heads):
    """A v as wide as q changes nothing: the same blocks, groups and VMEM
    count, and no call asks Mosaic for more than it gave, but the one
    backward kernel of a many-tile sequence (PR 41), which has the sequence
    whole in VMEM and takes the lower of the two steps a call may ask for."""
    for causal in (True, False):
        base = fa._choose_tiles(s, d, jnp.bfloat16, causal, heads)
        assert base == fa._choose_tiles(s, d, jnp.bfloat16, causal, heads,
                                        dv=d)
        bq, bk, groups = base
        for kernel, g in groups.items():
            if kernel == fa.FLASH_BWD_DQKV:
                bq, bk = fa._bwd_blocks(s, d, jnp.bfloat16, g)
                assert (bq, bk) == fa._bwd_blocks(s, d, jnp.bfloat16, g,
                                                  dv=d) == (512, 512)
            assert fa._vmem_bytes(s, d, 2, bq, bk, g, kernel) == \
                fa._vmem_bytes(s, d, 2, bq, bk, g, kernel, d)
            assert fa._vmem_limit(kernel, s, d, d, jnp.bfloat16, bq, bk,
                                  g) == (fa._VMEM_LIMITS[0] if kernel
                                         == fa.FLASH_BWD_DQKV else None)
    assert fa._head_groups(heads, d) == fa._head_groups(heads, d, d)


def test_latent_attention_tiles_at_192_and_128_lanes():
    """The cell's call: 32 heads of 192 / 128 columns at 8,192 positions.
    Heads go in twos (384 and 256 lanes, whole tiles on both arrays); k and
    v whole in VMEM are over what Mosaic gives unasked, so the forward asks;
    q, dO, o, dq and dq's f32 sum whole are over what that budget holds, so
    the backward asks for the step above it."""
    assert fa._head_groups(32, 192, 128) == [2, 4, 8, 16]
    assert fa._head_groups(32, 192) == [2, 4, 8, 16]
    assert fa._head_groups(3, 192, 128) == [3]
    bq, bk, groups = fa._choose_tiles(8192, 192, jnp.bfloat16, True, 32,
                                      dv=128)
    assert (bq, bk) == (512, 512) and set(groups.values()) == {2}
    assert fa._bwd_blocks(8192, 192, jnp.bfloat16, 2, dv=128) == (512, 512)
    for kernel, budgets in ((fa.FLASH_FWD, (fa._VMEM_BUDGET,
                                            fa._VMEM_BUDGET_ASKED)),
                            (fa.FLASH_BWD_DQKV, fa._VMEM_BUDGETS)):
        count = fa._vmem_bytes(8192, 192, 2, bq, bk, 2, kernel, 128)
        assert budgets[0] < count <= budgets[1]
    asked = fa._asking(fa.FLASH_FWD, 8192, 192, 128, jnp.bfloat16, bq, bk, 2)
    assert asked["compiler_params"].vmem_limit_bytes == fa._VMEM_LIMIT
    assert fa._vmem_limit(fa.FLASH_BWD_DQKV, 8192, 192, 128, jnp.bfloat16,
                          bq, bk, 2) == fa._VMEM_LIMITS[1] > fa._VMEM_LIMIT
    # k and v of both widths: 2 heads x (192 + 128) columns x 2 bytes,
    # double-buffered, whole; a block of q and of o; lse; three tiles; acc
    assert fa._vmem_bytes(8192, 192, 2, 512, 512, 2, fa.FLASH_FWD, 128) == (
        2 * 2 * 320 * 2 * (8192 + 512) + 2 * 2 * 8 * 4 * 512
        + 3 * 512 * 512 * 4 + 512 * 128 * 4)


# -- what remat may keep, by kind ---------------------------------------------------

def test_remat_names_size_latent_attention_by_its_own_widths():
    cfg = dataclasses.replace(hd.config_from_hf(SHARE), dtype=jnp.bfloat16,
                              attn_impl="flash")
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    B, T, D = 2, 128, 64
    h = jax.ShapeDtypeStruct((B, T, D), jnp.bfloat16)
    act, lse = B * T * D * 2, B * T * 4 * 4
    every = tfm._remat_names(cfg, params, h, None, bytes_limit=1 << 40)
    assert every[0] == (
        tracing.REMAT_CANDIDATES[0] + tracing.REMAT_CANDIDATES[1]
        + (tracing.REMAT_MLA_LATENT,) + tracing.REMAT_CANDIDATES[2])
    o, latent = act * 4 * 24 // D, act * (32 + 16) // D
    qkv = act * 4 * (48 + 48 + 24) // D
    assert every[1] == 3 * act + 3 * (o + lse) + 3 * latent + 3 * qkv
    # as the limit falls the latent goes before o and lse do, q, k, v first
    state = tfm._state_bytes(cfg, params, None)
    fixed = state + 3 * act + max(
        tfm._block_residual_bytes(cfg, None, h, b, None, kind)
        for (kind, _), b in zip(tfm.layer_runs(cfg), params["blocks"]))
    for room, names in (
            (3 * act + 3 * (o + lse) + 3 * latent + 8,
             tracing.REMAT_CANDIDATES[0] + tracing.REMAT_CANDIDATES[1]
             + (tracing.REMAT_MLA_LATENT,)),
            (3 * act + 3 * (o + lse) + 8,
             tracing.REMAT_CANDIDATES[0] + tracing.REMAT_CANDIDATES[1]),
            (3 * act + 8, tracing.REMAT_CANDIDATES[0])):
        limit = int((fixed + room) * 32 / 31) + 64
        assert tfm._remat_names(cfg, params, h, None,
                                bytes_limit=limit)[0] == names
    # the latent's name is on both values the down projection makes
    jaxpr = str(jax.make_jaxpr(lambda x, p: tfm._mla_qkv(x, p, cfg))(
        jnp.zeros((B, T, D), jnp.bfloat16),
        jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype),
                     params["blocks"][0])))
    assert jaxpr.count(f"name={tracing.REMAT_MLA_LATENT}") == 2
