"""`transformer._gelu` under `cfg.gelu_exact`: HF's and torch's "gelu",
0.5 x (1 + erf(x / sqrt 2)), as ONE float32 `erf` rounded once to x's dtype
(PR 62). The oracle is float64; `jax.nn.gelu(x, approximate=False)`, the form
the program had (0.5 x erfc(-x / sqrt 2) in x's dtype), is kept here to be
compared with. Values are the CPU's: a TPU's `erf` is its own."""
import math
import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from hetu_tpu.models import transformer as tfm

EXACT = tfm.TransformerConfig(gelu_exact=True)
TANH = tfm.TransformerConfig(gelu_exact=False)
# float32's 1 + erf is a few times 2^-24 off where erf nears -1 (the CPU's
# erf stops 3 short of it), times |x| / 2: what the form may lose where gelu
# itself is smaller than that, below x = -4.5
CANCELLATION = 12 / 2 * 4 * 2.0 ** -24


def _phi64(x):
    """The normal CDF in float64, with no cancellation on either side."""
    return 0.5 * np.vectorize(math.erfc)(-x / math.sqrt(2.0))


def _gelu64(x):
    return x * _phi64(x)


def _dgelu64(x):
    """The CDF plus x times the density."""
    return _phi64(x) + x * np.exp(-x * x / 2) / math.sqrt(2 * math.pi)


def _every_bf16(lo, hi):
    """Every bfloat16 in [lo, hi] that float32 holds as a normal number, and
    zero: XLA's CPU flushes the subnormals' products."""
    x = np.arange(1 << 16, dtype=np.uint16).view(ml_dtypes.bfloat16)
    with np.errstate(invalid="ignore"):
        x64 = x.astype(np.float64)
        keep = (x64 >= lo) & (x64 <= hi) & ((np.abs(x64) >= 1e-30)
                                           | (x64 == 0))
    return x[keep]


def _bits(a):
    return np.asarray(a).view(np.uint16)


def test_bf16_is_the_float64_value_rounded_once():
    """Bit for bit above the tail: from -3.75 up, 26,222 values."""
    x = _every_bf16(-3.75, 12.0)
    assert x.size == 26222
    got = tfm._gelu(jnp.asarray(x), EXACT)
    assert got.dtype == jnp.bfloat16
    want = _gelu64(x.astype(np.float64)).astype(ml_dtypes.bfloat16)
    wrong = np.flatnonzero(_bits(got) != _bits(want))
    assert wrong.size == 0, (x[wrong][:5], np.asarray(got)[wrong][:5],
                             want[wrong][:5])


def test_bf16_error_is_nowhere_larger_than_the_erfc_forms():
    """Over every bfloat16 in [-12, 12]: the error against float64 is at no
    input above the old form's by more than float32's cancellation in
    1 + erf (1.1e-6, below x = -4.5, where gelu is under 1.5e-5), the largest
    is half a place at 2.0 where the old form's was 0.0098, and the two forms
    differ at a twenty-fifth of the inputs, the new one the closer."""
    x = _every_bf16(-12.0, 12.0)
    x64 = x.astype(np.float64)
    want = _gelu64(x64)
    new = np.asarray(tfm._gelu(jnp.asarray(x), EXACT))
    old = np.asarray(jax.nn.gelu(jnp.asarray(x), approximate=False))
    e_new = np.abs(new.astype(np.float64) - want)
    e_old = np.abs(old.astype(np.float64) - want)
    assert (e_new <= e_old + CANCELLATION).all()
    worse = e_new > e_old
    assert x64[worse].max() < -4.5 and (e_new - e_old)[worse].max() < 1.1e-6
    assert e_new.max() <= 2.0 ** -7 < 0.0097 < e_old.max()
    moved = _bits(new) != _bits(old)
    assert 1000 < moved.sum() < 1200
    assert (e_new[moved & (x64 >= -4.5)] < e_old[moved & (x64 >= -4.5)]).all()


@pytest.mark.parametrize("dtype,lo,atol,rtol", [
    pytest.param(jnp.float32, -10.0, 1e-6, 2e-7, id="float32"),
    # the CPU's erf stops 3 places short of -1: 1.07e-6 at x = -12
    pytest.param(jnp.float32, -12.0, CANCELLATION, 2e-7, id="float32-tail"),
    pytest.param(jnp.float16, -10.0, 1e-6, 2.0 ** -11, id="float16"),
])
def test_other_dtypes_come_back_as_they_went_in(dtype, lo, atol, rtol):
    x = jnp.linspace(lo, 12.0, 200001).astype(dtype)
    got = tfm._gelu(x, EXACT)
    assert got.dtype == dtype and got.shape == x.shape
    want = _gelu64(np.asarray(x, np.float64))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize("how", [
    "grad", "jvp", "grad-of-bf16", "grad-under-checkpoint",
    "grad-of-bf16-under-checkpoint"])
def test_the_derivative_is_the_cdf_plus_x_times_the_density(how):
    """Under `_gelu_erf`'s own rule (PR 65), which `jax.checkpoint` runs
    again in the backward pass as the trunk's `remat` does."""
    x = np.linspace(-12.0, 12.0, 20001).astype(np.float32)
    want = _dgelu64(x.astype(np.float64))
    f = lambda v: tfm._gelu(v, EXACT)
    if how.endswith("under-checkpoint"):
        f = jax.checkpoint(f)
    if how in ("grad", "grad-under-checkpoint"):
        got, tol = jax.vmap(jax.grad(f))(jnp.asarray(x)), 1e-5
    elif how == "jvp":
        got, tol = jax.jvp(f, (jnp.asarray(x),), (jnp.ones_like(x),))[1], 1e-5
    else:
        # GELU' is rounded once to x's dtype, and so is the cotangent
        xb = jnp.asarray(x).astype(jnp.bfloat16)
        want = _dgelu64(np.asarray(xb, np.float64))
        got = jax.grad(lambda v: jnp.sum(f(v).astype(jnp.float32)))(xb)
        assert got.dtype == jnp.bfloat16
        tol = 2.0 ** -8 * 1.2
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)


def test_the_value_beside_the_derivative_is_the_forwards_bit_for_bit():
    """The rule's `u`, what a differentiated step's forward and recomputed
    passes carry on, against the primal function, which is all that runs
    outside differentiation: every bfloat16 in [-12, 12]."""
    x = jnp.asarray(_every_bf16(-12.0, 12.0))
    f = lambda v: tfm._gelu(v, EXACT)
    u, _ = jax.jvp(f, (x,), (jnp.ones_like(x),))
    assert u.dtype == jnp.bfloat16
    assert (_bits(u) == _bits(f(x))).all()


@pytest.mark.parametrize("how", ["jvp-of-grad", "grad-of-grad"])
def test_a_second_derivative_goes_through_the_rule(how):
    """The rule's own lines are plain jax and a barrier differentiates as
    the identity, so a second order works (nothing here asks for one: it
    must not be a silent zero): GELU'' is the density times 2 - x^2."""
    x = np.linspace(-6.0, 6.0, 2001).astype(np.float32)
    x64 = x.astype(np.float64)
    want = np.exp(-x64 * x64 / 2) / math.sqrt(2 * math.pi) * (2 - x64 * x64)
    g = jax.grad(lambda v: tfm._gelu(v, EXACT))
    if how == "jvp-of-grad":
        got = jax.vmap(lambda v: jax.jvp(g, (v,), (jnp.float32(1.0),))[1])(
            jnp.asarray(x))
    else:
        got = jax.vmap(jax.grad(g))(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_tanh_form_is_jax_nn_gelus_bit_for_bit(dtype):
    x = (jnp.asarray(_every_bf16(-12.0, 12.0)).astype(dtype)
         if dtype == jnp.bfloat16
         else jnp.linspace(-12.0, 12.0, 200001).astype(dtype))
    got, want = tfm._gelu(x, TANH), jax.nn.gelu(x)
    assert got.dtype == want.dtype == dtype
    assert (np.asarray(got).view(np.uint8)
            == np.asarray(want).view(np.uint8)).all()
    # and it is not the exact form
    assert not np.array_equal(np.asarray(got), np.asarray(tfm._gelu(x, EXACT)))


@pytest.mark.parametrize("exact,erf,erfc,tanh", [
    pytest.param(True, 1, 0, 0, id="exact"),
    pytest.param(False, 0, 0, 1, id="tanh"),
])
def test_what_the_form_lowers_to(exact, erf, erfc, tanh):
    """ONE `erf` in float32 and no `erfc` (which has no HLO opcode and
    expands to two polynomials, an exponential and two divides)."""
    cfg = tfm.TransformerConfig(gelu_exact=exact)
    text = jax.jit(lambda v: tfm._gelu(v, cfg)).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.bfloat16)).as_text()
    assert len(re.findall(r"chlo\.erf\b", text)) == erf
    assert len(re.findall(r"chlo\.erfc\b", text)) == erfc
    assert len(re.findall(r"stablehlo\.tanh\b", text)) == tanh
    if exact:
        assert re.search(r"chlo\.erf .*tensor<8x128xf32>", text)


@pytest.mark.parametrize("exact,erf,tanh", [
    pytest.param(True, 1, 0, id="exact"),
    pytest.param(False, 0, 1, id="tanh"),
])
def test_the_backward_half_of_an_mlp_evaluates_the_gelu_once(exact, erf,
                                                              tanh):
    """`jax.grad(jax.checkpoint(...))` of w2' gelu(w1 x), the trunk's MLP
    under `remat`: ONE float32 `erf`, in the recomputed pass, whose rule ends
    in a barrier over the pair (u, GELU'), both in x's dtype, so that the
    `w2` weight gradient and dU read them and derive no `erf` of their own
    (PR 65; the compiled step's fusions on the chip are in PERF.md). The
    checkpoint's own barrier is the one over its three arguments. The tanh
    form is autodiff's, with no rule and no barrier of its own."""
    cfg = tfm.TransformerConfig(gelu_exact=exact)

    def loss(x, w1, w2):
        return jnp.sum((tfm._gelu(x @ w1, cfg) @ w2).astype(jnp.float32))

    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    text = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))).lower(
        bf16(8, 128), bf16(128, 256), bf16(256, 128)).as_text()
    assert len(re.findall(r"chlo\.erf\b", text)) == erf
    assert len(re.findall(r"chlo\.erfc\b", text)) == 0
    assert len(re.findall(r"stablehlo\.tanh\b", text)) == tanh
    barriers = [line for line in text.splitlines()
                if "stablehlo.optimization_barrier" in line]
    assert len(barriers) == 1 + erf
    assert sum(line.rstrip().endswith(
        ": tensor<8x256xbf16>, tensor<8x256xbf16>")
        for line in barriers) == erf
    if exact:
        assert re.search(r"chlo\.erf .*tensor<8x256xf32>", text)
