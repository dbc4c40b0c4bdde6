"""Kimi Delta Attention's scan kernel (`hetu_tpu/kernels/kda.py`) in interpret
mode on the CPU, small (T 256, 2 and 4 heads of 128 columns, chunks of 64):
against `models/kda.scan`'s XLA form and against the float64 recurrence over
POSITIONS at the limits the kimi cell's check holds its part (C) to, with
easy and with hard decays (a chunk's cumulated log-decay past -100: 1 /
exp(G) is inf there), the parts `terms=True` writes, every gradient against
`jax.grad` of the XLA form, a state that crosses more chunks than a segment
of the backward pass holds, the one gating rule as a table with the counter
that names the reason, and the kernel's name. What the chip's compiler makes
of it at the cell's shapes is in `tests/test_flash_compile_v5e.py`."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import kda as kda_kernel
from hetu_tpu.models import kda
from hetu_tpu.parallel import mesh as meshlib
from hetu_tpu.telemetry import tracing

K, CHUNK = 128, 64
LEAVES = ("q", "k", "v", "g", "beta")
# the kimi adapter's part (C): G, U, the entering states, o against float64
LIMITS = {"G": 1e-5, "U": 1e-5, "entering": 3e-5, "o": 3e-5}

# name -> (T, heads, dtype of q / k / v, log-decay a position about)
CASES = {
    "two-heads": (256, 2, jnp.float32, 0.1),
    "four-heads": (256, 4, jnp.float32, 0.1),
    "two-heads-bf16": (256, 2, jnp.bfloat16, 0.1),
    "hard-decays": (256, 2, jnp.float32, 2.5),
    "hard-decays-bf16": (256, 4, jnp.bfloat16, 2.5),
    # 17 chunks: one more than a segment of the backward pass holds
    "seventeen-chunks": (1088, 2, jnp.float32, 0.1),
}
FORWARD = [c for c in CASES if c != "seventeen-chunks"]


@functools.lru_cache(maxsize=None)
def _inputs(case):
    """Seeded operands as `transformer._kda_inputs` makes them: q, k
    L2-normalised a head (q times K^-0.5), g = -scale softplus(.), beta a
    sigmoid; and a cotangent for o."""
    T, H, dtype, scale = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    return ((l2(normal(ks[0], 1, T, H, K)) * K ** -0.5).astype(dtype),
            l2(normal(ks[1], 1, T, H, K)).astype(dtype),
            normal(ks[2], 1, T, H, K).astype(dtype),
            -scale * jax.nn.softplus(normal(ks[3], 1, T, H, K) + 1.0),
            jax.nn.sigmoid(normal(ks[4], 1, T, H)),
            normal(ks[5], 1, T, H, K))


@functools.lru_cache(maxsize=None)
def _kernel_terms(case):
    o, terms = jax.jit(lambda *a: kda_kernel.terms(*a, CHUNK))(
        *_inputs(case)[:5])
    return {"o": o, **terms}


@functools.lru_cache(maxsize=None)
def _xla_terms(case):
    o, terms = jax.jit(lambda *a: kda.scan(*a, CHUNK, terms=True))(
        *_inputs(case)[:5])
    return {"o": o, **terms}


@functools.lru_cache(maxsize=None)
def _float64_terms(case):
    """The recurrence over positions in numpy float64 on the operands as the
    scan reads them (`benchmark/configs/kimi-linear-48b-a3b/adapter.py`'s
    part (C)): S' = Diag(exp g) S; u = beta (v - S'^T k); S = S' + k u^T;
    o = S^T q."""
    q, k, v, g, beta = (np.asarray(x.astype(jnp.float32), np.float64)[0]
                        for x in _inputs(case)[:5])
    T, H, _ = q.shape
    G = np.concatenate([np.cumsum(g[i:i + CHUNK], 0)
                        for i in range(0, T, CHUNK)])
    S = np.zeros((H, K, K))
    U, o, entering = np.empty_like(v), np.empty_like(v), []
    for i in range(T):
        if i % CHUNK == 0:
            entering.append(S.copy())
        S *= np.exp(g[i])[..., None]
        U[i] = beta[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", S, k[i]))
        S += k[i][..., None] * U[i][:, None, :]
        o[i] = np.einsum("hkv,hk->hv", S, q[i])
    return {"G": G, "U": U, "entering": np.stack(entering), "o": o}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


@pytest.mark.parametrize("part", sorted(LIMITS))
@pytest.mark.parametrize("case", FORWARD)
def test_kernel_against_the_float64_recurrence(case, part):
    """Each part the kernel writes, at the limit the cell's check has for
    it, hard decays too; nothing inf, nothing nan."""
    got = np.asarray(_kernel_terms(case)[part])[0]
    assert np.isfinite(got).all()
    assert _rel(got, _float64_terms(case)[part]) <= LIMITS[part], (case, part)


@pytest.mark.parametrize("part", sorted(LIMITS))
@pytest.mark.parametrize("case", FORWARD)
def test_kernel_against_the_xla_form(case, part):
    """The same parts against `models/kda.scan(..., terms=True)` off the
    kernel's path: one algorithm, one result."""
    assert _rel(_kernel_terms(case)[part], _xla_terms(case)[part]) <= 3e-6, (
        case, part)


def test_hard_decays_are_past_float32s_reciprocal():
    """The hard cases are worth their name: 1 / exp(G) is inf in them."""
    for case in ("hard-decays", "hard-decays-bf16"):
        low = float(kda.chunk_log_decay_min(_inputs(case)[3], CHUNK))
        assert low < -100.0, (case, low)
        with np.errstate(divide="ignore"):
            assert np.isinf(np.float32(1.0) / np.exp(np.float32(low)))


@functools.lru_cache(maxsize=None)
def _grads(case, kernel):
    *ins, do = _inputs(case)
    fn = ((lambda *a: kda_kernel.kda(*a, CHUNK)) if kernel
          else (lambda *a: kda.scan(*a, CHUNK)))
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * do),
                            argnums=tuple(range(5))))(*ins)


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("case", ["two-heads", "two-heads-bf16",
                                  "hard-decays", "seventeen-chunks"])
def test_gradients_against_the_xla_form(case, leaf):
    """d q, k, v, g, beta through `kda_bwd` (each chunk made again from the
    state `kda_fwd` saw enter it, the state's cotangent carried over the
    chunks in reverse) against `jax.grad` of the XLA form; at 17 chunks the
    cotangent crosses more chunks than a segment of the XLA form holds."""
    i = LEAVES.index(leaf)
    got, want = _grads(case, True)[i], _grads(case, False)[i]
    assert got.dtype == want.dtype == _inputs(case)[i].dtype
    limit = 1e-4 if got.dtype == jnp.float32 else 1e-2
    assert _rel(got.astype(jnp.float32), want.astype(jnp.float32)) <= limit


def test_the_kept_states_are_the_backward_passes_residual():
    """Differentiated, `kda_fwd` writes the state entering EVERY chunk (the
    one residual beside the inputs); called for o alone, the first only."""
    ins = _inputs("seventeen-chunks")[:5]
    kept = kda_kernel._kda_fwd(*ins, CHUNK)[1][-1]
    assert kept.shape == (1, 17, 2, K, K)
    want = _float64_terms("seventeen-chunks")["entering"]
    assert _rel(np.swapaxes(np.asarray(kept[0]), -1, -2), want) <= 3e-5
    alone = str(jax.make_jaxpr(lambda *a: kda_kernel.kda(*a, CHUNK))(*ins))
    assert "f32[1,1,2,128,128]" in alone and "f32[1,17,2," not in alone


def _shaped(T=256, H=2, width=K, dtype=jnp.bfloat16):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    x = jax.ShapeDtypeStruct((1, T, H, width), dtype)
    return x, x, x, f32(1, T, H, width), f32(1, T, H)


# what is asked -> the reason's words (None: taken); the backend patched to
# a TPU in all but the first
RULE = {
    "off-a-tpu": (dict(), CHUNK, None, "not a tpu"),
    "taken": (dict(), CHUNK, None, None),
    "taken-two-heads-f32": (dict(dtype=jnp.float32), CHUNK, None, None),
    "taken-one-device-mesh": (dict(), CHUNK, 1, None),
    "a-mesh": (dict(), CHUNK, 2, "a mesh of 2 devices"),
    "not-whole-chunks": (dict(T=250), CHUNK, None, "not whole chunks"),
    "chunk-32": (dict(), 32, None, "a chunk of 32"),
    "narrow-heads": (dict(width=64), CHUNK, None, "whole lane tiles"),
    "float16": (dict(dtype=jnp.float16), CHUNK, None, "float16"),
}


@pytest.mark.parametrize("row", sorted(RULE))
def test_the_rule_is_a_table(row, monkeypatch):
    """`takes` by platform, mesh, shapes and dtype; where it refuses,
    `kda.scan` runs the XLA form and the counter names the first reason."""
    shapes, chunk, devices, words = RULE[row]
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: row != "off-a-tpu")
    mesh = devices and meshlib.make_mesh(dp=devices,
                                         devices=jax.devices()[:devices])
    args = _shaped(**shapes)
    assert kda_kernel.takes(*args, chunk, mesh) == (words is None)
    noted = len(tracing.forms("kda.scan"))
    traced = jax.make_jaxpr(
        lambda *a: kda.scan(*a, chunk, mesh=mesh))(*args)
    (note,) = tracing.forms("kda.scan")[noted:]
    assert ("pallas_call" in str(traced)) == (words is None)
    if words is None:
        assert note["form"] == "kernel" and note["reason"] is None
    else:
        assert note["form"] == "xla" and words in note["reason"]


def test_scan_hands_terms_to_the_kernel_where_the_rule_admits(monkeypatch):
    """`terms=True` of TWO heads goes through the same kernel (the kimi
    cell's part (C) asks `kda_terms(..., heads=(0, 17))`), and gives what
    the XLA form gives."""
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: True)
    ins = _inputs("two-heads")[:5]
    traced = jax.make_jaxpr(lambda *a: kda.scan(*a, CHUNK, terms=True))(*ins)
    assert str(traced).count("pallas_call") == 1
    o, terms = kda.scan(*ins, CHUNK, terms=True)
    assert sorted(terms) == ["G", "U", "entering"]
    for name, got in {"o": o, **terms}.items():
        want = _xla_terms("two-heads")[name]
        assert got.shape == want.shape and _rel(got, want) <= 3e-6, name


def test_a_heads_decay_goes_through_the_kernel_broadcast(monkeypatch):
    """g (B, T, H), a decay a HEAD (PR 68, the kind "gdn"): off a TPU the XLA
    form serves it with g broadcast over the head's columns; where the rule
    admits the call `scan` hands it to the HEAD kernels (PR 69,
    `kernels/gdn.py`, `tests/test_gdn_kernels.py`), `terms` too (G comes back
    a head's), which give what the XLA form gives; the channel kernel run by
    hand on g broadcast gives it too."""
    q, k, v, g, beta = _inputs("two-heads")[:5]
    g = g[..., 0]
    want_o, want = kda.scan(q, k, v, g, beta, CHUNK, terms=True)
    assert tracing.forms("kda.scan")[-1]["form"] == "xla"
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: True)
    traced = jax.make_jaxpr(lambda *a: kda.scan(*a, CHUNK, terms=True))(
        q, k, v, g, beta)
    assert str(traced).count("pallas_call") == 1
    o, terms = kda.scan(q, k, v, g, beta, CHUNK, terms=True)
    assert tracing.forms("kda.scan")[-1]["form"] == "head-kernel"
    assert terms["G"].shape == g.shape
    wide = jnp.broadcast_to(g[..., None], k.shape)
    for name, got in {"o": o, **terms, "channel": kda_kernel.kda(
            q, k, v, wide, beta, CHUNK)}.items():
        like = {"o": want_o, **want, "channel": want_o}[name]
        assert got.shape == like.shape, name
        assert _rel(got, like) <= 3e-6, name
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: False)
    np.testing.assert_array_equal(
        np.asarray(kda.scan(q, k, v, g, beta, CHUNK)),
        np.asarray(kda.scan(q, k, v, wide, beta, CHUNK)))


@pytest.mark.parametrize("name", [kda_kernel.KDA_FWD, kda_kernel.KDA_BWD])
def test_the_kernels_names_are_no_other_readers(name):
    """`benchmark/reduce` finds the attention, selection, rotary and Mamba
    kernels by substring: the names hold none of them, do not end in what a
    trace reader strips as numbering, and both calls sit under the scan's
    scope, which `kda_scan_ms_per_step` reads."""
    assert not any(s in name for s in ("flash", "dsa_", "rope", "ssd"))
    assert not name[-1].isdigit()
    text = jax.jit(jax.grad(lambda *a: kda_kernel.kda(*a, CHUNK).sum())).lower(
        *_shaped()).as_text(debug_info=True)
    assert re.search(
        rf'"[^"]*{tracing.SCOPE_KDA_SCAN}[^"]*\b{name}\b[^"]*/pallas_call"',
        text)
