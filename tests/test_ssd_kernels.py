"""The chunked scan's Mosaic kernels (`hetu_tpu/kernels/ssd.py`) in interpret
mode on the CPU, at small shapes the kernels' tiles still divide, against
`transformer._ssd` (the einsums, called directly): the forward pass and all
five cotangents in float32 and in bfloat16, the one gating rule, and the path
`transformer._scan` takes by what `takes` says. What the chip's compiler makes
of them at Granite's shape is in `tests/test_flash_compile_v5e.py`."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import ssd
from hetu_tpu.models import transformer as tfm
from hetu_tpu.parallel import mesh as meshlib

P, N = 64, 128
LEAVES = ("x", "dt", "A_log", "B", "C")


@functools.lru_cache(maxsize=None)
def _inputs(B, T, H, G, dtype, seed=0):
    """Seeded operands of one scan: dt = softplus(.), A = -1..-16 a head as
    the initialiser spreads them, and a cotangent for y."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape, scale=1.0: (
        scale * jax.random.normal(k, shape, jnp.float32))
    return (normal(ks[0], (B, T, H, P)).astype(dtype),
            jax.nn.softplus(normal(ks[1], (B, T, H)) - 2.0),
            jnp.log(jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)),
            normal(ks[3], (B, T, G, N), 0.3).astype(dtype),
            normal(ks[4], (B, T, G, N), 0.3).astype(dtype),
            normal(ks[5], (B, T, H, P)))


# `transformer._scan`'s kernel branch without the rule: these head counts are
# below what a TPU's sublanes ask for, and interpret mode does not mind
_kernels = tfm._ssd_kernels


def _value_and_grads(fn, chunk, operands):
    """-> ((sum(y * g), y), the five cotangents), one program."""
    *ins, g = operands

    def loss(*a):
        y = fn(*a, chunk)
        return jnp.sum(y * g), y

    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)),
                                      has_aux=True))(*ins)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


# batch, chunks, positions a chunk, groups, heads a group
CASES = [
    pytest.param(1, 1, 128, 1, 2, id="one-chunk"),
    pytest.param(1, 2, 128, 1, 2, id="two-chunks"),
    pytest.param(1, 4, 128, 1, 4, id="four-chunks-four-heads"),
    pytest.param(2, 2, 128, 2, 2, id="two-sequences-two-groups"),
    pytest.param(1, 1, 256, 1, 2, id="one-chunk-of-256"),
    pytest.param(1, 2, 256, 2, 4, id="two-chunks-of-256-two-groups"),
    pytest.param(2, 4, 128, 2, 4, id="everything-at-once"),
]


@pytest.mark.parametrize("B,chunks,Q,G,R", CASES)
def test_kernels_are_the_einsums_in_float32(B, chunks, Q, G, R):
    """y and the cotangents of x, dt, A_log, B and C within 1e-4 of their
    RMS (measured <= 3e-5: dt rides in the decay's exponent here, exp(a_l -
    a_s + log dt_s), where the einsums multiply by it)."""
    operands = _inputs(B, chunks * Q, G * R, G, jnp.float32)
    ((want_loss, want_y), want), ((loss, got_y), got) = (
        _value_and_grads(fn, Q, operands) for fn in (tfm._ssd, _kernels))
    assert got_y.dtype == jnp.float32 and got_y.shape == want_y.shape
    assert _rel(got_y, want_y) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    for leaf, g, w in zip(LEAVES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, leaf
        assert _rel(g, w) < 1e-4, (leaf, _rel(g, w))


@pytest.mark.parametrize("B,chunks,Q,G,R", [CASES[1], CASES[5]])
def test_kernels_are_as_close_as_the_einsums_in_bfloat16(B, chunks, Q, G, R):
    """bfloat16 operands: against the einsums in FLOAT32 on the same values,
    the kernels' y and cotangents are within 2e-2 of their RMS (the bound
    `tests/test_granite_model.py` holds the bfloat16 stack's loss to) and no
    further off than twice what the bfloat16 einsums are: the operands are
    rounded elsewhere, not more."""
    operands = _inputs(B, chunks * Q, G * R, G, jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in operands)
    ((_, true_y), truth), (_, want), ((_, got_y), got) = (
        _value_and_grads(fn, Q, ops) for fn, ops in (
            (tfm._ssd, exact), (tfm._ssd, operands), (_kernels, operands)))
    assert _rel(got_y, true_y) < 2e-2
    for leaf, t, w, g in zip(LEAVES, truth, want, got):
        assert g.dtype == w.dtype, leaf
        assert _rel(g, t) < 2e-2, (leaf, _rel(g, t))
        assert _rel(g, t) < 2 * _rel(w, t) + 1e-3, (leaf, _rel(g, t),
                                                    _rel(w, t))


def test_a_step_size_of_zero_gets_a_finite_gradient():
    """dt = 0 at some positions (softplus underflowed): log dt is -inf in
    the exponent, the position's column of M is 0, and every cotangent stays
    finite (d dt divides by dt: the guard)."""
    x, dt, A_log, Bm, Cm, g = _inputs(1, 256, 2, 1, jnp.float32)
    dt = dt.at[0, 5:9, 0].set(0.0).at[0, 255, 1].set(0.0)
    operands = (x, dt, A_log, Bm, Cm, g)
    ((want_loss, _), want), ((loss, _), got) = (
        _value_and_grads(fn, 128, operands) for fn in (tfm._ssd, _kernels))
    assert abs(float(loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    assert all(np.all(np.isfinite(np.asarray(leaf))) for leaf in got)
    for leaf, g_, w in zip(LEAVES, got, want):
        if leaf != "dt":        # at dt = 0 the guard gives 0, not the limit
            assert _rel(g_, w) < 1e-4, leaf


def _shapes(T=512, H=16, P_=P, G=1, N_=N, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((1, T, H, P_), dtype),
            jax.ShapeDtypeStruct((1, T, G, N_), dtype))


@pytest.mark.parametrize("case,on_tpu,mesh_of,chunk,taken", [
    ("granite-like", True, None, 256, True),
    ("off the tpu", False, None, 256, False),
    ("one device's mesh", True, 1, 256, True),
    ("a mesh of two", True, 2, 256, False),
    ("a ragged last chunk", True, None, 384, False),
    ("a chunk below a lane tile", True, None, 64, False),
    ("heads of 32 columns", True, None, 256, False),
    ("a state of 64", True, None, 256, False),
    ("float16", True, None, 256, False),
    ("four heads a group", True, None, 256, False),
    ("heads of 128 columns", True, None, 256, True),
    ("float32", True, None, 256, True),
])
def test_takes_is_the_one_rule(monkeypatch, case, on_tpu, mesh_of, chunk,
                               taken):
    """The kernels' truth table: a TPU, one program, whole chunks of whole
    lane tiles, heads of 64 or whole tiles of columns, a state of whole
    tiles, bfloat16 or float32, and eight heads a grid step or more."""
    monkeypatch.setattr(ssd, "_on_tpu", lambda: on_tpu)
    x, Bm = _shapes(**{"heads of 32 columns": dict(P_=32),
                       "a state of 64": dict(N_=64),
                       "float16": dict(dtype=jnp.float16),
                       "four heads a group": dict(G=4),
                       "heads of 128 columns": dict(P_=128),
                       "float32": dict(dtype=jnp.float32)}.get(case, {}))
    mesh = mesh_of and meshlib.make_mesh(dp=mesh_of,
                                         devices=jax.devices()[:mesh_of])
    assert ssd.takes(x, Bm, chunk, mesh) is taken


@pytest.mark.parametrize("on_tpu,P_,wanted", [
    (False, 64, "_ssd"), (True, 32, "_ssd"), (True, 64, "ssd")])
def test_scan_takes_the_path_the_rule_names(monkeypatch, on_tpu, P_, wanted):
    """`transformer._scan` calls the einsums off a TPU and at a width the
    kernels do not slice, the kernels where the rule admits the call: by
    name, and with `_ssm_log_decay`'s cumulative sum handed to the kernels."""
    seen = []
    monkeypatch.setattr(ssd, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(tfm, "_ssd", lambda *a: seen.append("_ssd"))
    monkeypatch.setattr(
        ssd, "ssd", lambda x, dt, acs, *rest: seen.append(
            "ssd" if acs.shape == dt.shape else "ssd without the log-decay"))
    x, Bm = _shapes(T=256, H=8, P_=P_)
    dt = jnp.ones((1, 256, 8), jnp.float32)
    tfm._scan(jnp.zeros(x.shape, x.dtype), dt, jnp.zeros((8,)),
              jnp.zeros(Bm.shape, Bm.dtype), jnp.zeros(Bm.shape, Bm.dtype),
              128)
    assert seen == [wanted]


def test_heads_a_step_follow_the_vmem_count():
    """Granite's call takes 16 heads a step unasked (the unrolled loop's
    cap), both kernels' counts under the 12 MiB budget; float32 operands of
    the same call fit at 8; a state of 1,024 columns fits nowhere unasked
    and asks at the fewest heads; 24 heads go by eights (12 would leave the
    rows' sublane tiles ragged), a group of 2 whole."""
    count = lambda h, size, n=128: max(ssd._vmem_bytes(
        256, 64, n, 64, h, size, k) for k in (ssd.SSD_FWD, ssd.SSD_BWD))
    assert ssd._heads(64, 1, 64, 128, 256, 2) == (16, False)
    assert count(16, 2) <= ssd._VMEM_BUDGET < count(16, 4)
    assert ssd._heads(64, 1, 64, 128, 256, 4) == (8, False)
    assert ssd._VMEM_BUDGET < count(8, 2, 1024) <= ssd._VMEM_BUDGET_ASKED
    assert ssd._heads(64, 1, 64, 1024, 256, 2) == (8, True)
    assert ssd._heads(24, 1, 64, 128, 256, 2) == (8, False)
    assert ssd._heads(4, 2, 64, 128, 128, 4) == (2, False)
    # an odd number of 64-column heads a group shares no lane tile
    assert ssd._heads(3, 1, 64, 128, 256, 2) is None
