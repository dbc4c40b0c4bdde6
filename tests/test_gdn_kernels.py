"""Gated DeltaNet's scan kernels (`hetu_tpu/kernels/gdn.py`) in interpret
mode on the CPU, small (T 256, heads of 128 columns, chunks of 64): against
`models/kda.scan`'s XLA form with g broadcast and the key heads repeated, and
against the float64 recurrence over POSITIONS at the limits the qwen3-next
cell's check holds its part (C) to, with r = 1, 2 and 4 value heads a key
head, in float32 and bfloat16, and with g = -20 a position (G to -1,280 a
chunk: exp(G) = 0); the parts `terms=True` writes; every gradient (dq and dk
a KEY head, dv, dg a head, d beta) against `jax.grad` of both; the rule as a
table, each refusal falling back to the XLA form with the same answer; the
kernels' names. What the chip's compiler makes of them at the cell's shapes
is in `tests/test_flash_compile_v5e.py`."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import gdn as gdn_kernel, kda as kda_kernel
from hetu_tpu.models import kda
from hetu_tpu.parallel import mesh as meshlib
from hetu_tpu.telemetry import tracing

K, CHUNK = 128, 64
LEAVES = ("q", "k", "v", "g", "beta")
# the qwen3-next adapter's part (C): G, U, the entering states, o
LIMITS = {"G": 1e-5, "U": 1e-5, "entering": 3e-5, "o": 5e-6}

# name -> (T, key heads, value heads, dtype of q / k / v, hard decays)
CASES = {
    "one-a-key-head": (256, 2, 2, jnp.float32, False),
    "two-a-key-head-bf16": (256, 2, 4, jnp.bfloat16, False),
    "four-a-key-head": (128, 1, 4, jnp.float32, False),
    "decay-20-a-position": (256, 1, 2, jnp.float32, True),
    "decay-20-a-position-bf16": (256, 2, 4, jnp.bfloat16, True),
}


@functools.lru_cache(maxsize=None)
def _inputs(case):
    """Seeded operands as `transformer._gdn_inputs` makes them: q, k a KEY
    head, L2-normalised (q times K^-0.5), g = -scale softplus(.) a value
    head (hard: -20 a position on head 0), beta a sigmoid; and a cotangent
    for o."""
    T, Hk, Hv, dtype, hard = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    g = -0.3 * jax.nn.softplus(normal(ks[3], 1, T, Hv) + 1.0)
    if hard:
        g = g.at[..., 0].set(-20.0)
    return ((l2(normal(ks[0], 1, T, Hk, K)) * K ** -0.5).astype(dtype),
            l2(normal(ks[1], 1, T, Hk, K)).astype(dtype),
            normal(ks[2], 1, T, Hv, K).astype(dtype), g,
            jax.nn.sigmoid(normal(ks[4], 1, T, Hv)),
            normal(ks[5], 1, T, Hv, K))


@functools.lru_cache(maxsize=None)
def _kernel_terms(case):
    o, terms = jax.jit(lambda *a: gdn_kernel.terms(*a, CHUNK))(
        *_inputs(case)[:5])
    return {"o": o, **terms}


@functools.lru_cache(maxsize=None)
def _xla_terms(case):
    """`kda.scan` off a TPU: the XLA channel form, g broadcast and the key
    heads repeated inside it."""
    o, terms = jax.jit(lambda *a: kda.scan(*a, CHUNK, terms=True))(
        *_inputs(case)[:5])
    assert tracing.forms("kda.scan")[-1]["form"] == "xla"
    return {"o": o, **terms}


def _recurrence(q, k, v, g, beta):
    """The rule over positions, whatever the dtype: S' = exp(g) S; u = beta
    (v - S'^T k); S = S' + k u^T; o = S^T q -> (o, U, every state S) a
    sequence; key head j serves value heads r j .. r j + r - 1."""
    r = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(x, r, axis=2)[0] for x in (q, k))
    v, g, beta = v[0], g[0], beta[0]

    def step(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[..., None] * u[:, None, :]
        return S, (jnp.einsum("hkv,hk->hv", S, q), u, S)

    S0 = jnp.zeros((v.shape[1], k.shape[-1], v.shape[-1]), v.dtype)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


@functools.lru_cache(maxsize=None)
def _float64(case):
    """The recurrence in float64 on the operands as the scan reads them ->
    (the parts by name, the gradients of sum(o * do) by leaf)."""
    with jax.enable_x64(True):
        *ins, do = (jnp.asarray(np.asarray(x.astype(jnp.float32)),
                                jnp.float64) for x in _inputs(case))
        o, U, S = _recurrence(*ins)
        grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a)[0] * do[0]),
                         argnums=tuple(range(5)))(*ins)
        T = o.shape[0]
        G = jnp.cumsum(ins[3][0].reshape(T // CHUNK, CHUNK, -1), 1).reshape(
            T, -1)
        entering = jnp.concatenate([jnp.zeros_like(S[:1]),
                                    S[CHUNK - 1:-1:CHUNK]])
        parts = {"G": G, "U": U, "entering": entering, "o": o}
        return ({n: np.asarray(x) for n, x in parts.items()},
                [np.asarray(x) for x in grads])


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


@pytest.mark.parametrize("part", sorted(LIMITS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_against_the_float64_recurrence(case, part):
    """Each part the forward kernel writes, at the limit the cell's check has
    for it, exp(G) = 0 too; nothing inf, nothing nan."""
    got = np.asarray(_kernel_terms(case)[part])[0]
    assert np.isfinite(got).all()
    assert _rel(got, _float64(case)[0][part]) <= LIMITS[part], (case, part)


@pytest.mark.parametrize("part", sorted(LIMITS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_against_the_xla_form(case, part):
    """The same parts against `models/kda.scan(..., terms=True)` off the
    kernels' path, g broadcast there: one rule, one result, and the shapes
    the cell's adapter reads."""
    got, want = _kernel_terms(case)[part], _xla_terms(case)[part]
    assert got.shape == want.shape
    assert _rel(got, want) <= 3e-6, (case, part)


def test_hard_decays_are_past_exps_underflow():
    """-20 a position is -1,280 a chunk: exp(G) is 0 there, and 1 / exp(G)
    inf from -88 on."""
    for case in ("decay-20-a-position", "decay-20-a-position-bf16"):
        low = float(kda.chunk_log_decay_min(_inputs(case)[3], CHUNK))
        assert low == -1280.0 and np.exp(np.float32(low)) == 0.0


@functools.lru_cache(maxsize=None)
def _grads(case, kernel):
    *ins, do = _inputs(case)
    fn = ((lambda *a: gdn_kernel.gdn(*a, CHUNK)) if kernel
          else (lambda *a: kda.scan(*a, CHUNK)))
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * do),
                            argnums=tuple(range(5))))(*ins)


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_against_the_xla_form_and_float64(case, leaf):
    """d q and d k a KEY head (the sum over its value heads made inside the
    kernel), d v, d g a head and d beta through `gdn_bwd` against `jax.grad`
    of the XLA form and of the float64 recurrence, in the inputs' shapes and
    dtypes. Where a head forgets everything a position its decay's gradient
    is e^-20 of the others': absolute, of the largest."""
    n = LEAVES.index(leaf)
    got, xla = _grads(case, True)[n], _grads(case, False)[n]
    want = _float64(case)[1][n]
    assert got.shape == xla.shape == want.shape == _inputs(case)[n].shape
    assert got.dtype == xla.dtype == _inputs(case)[n].dtype
    assert bool(jnp.all(jnp.isfinite(got)))
    limit = 2e-5 if got.dtype == jnp.float32 else 1e-2
    for other in (xla, want):
        got32, other = (np.asarray(x.astype(jnp.float32) if hasattr(
            x, "astype") else x, np.float64) for x in (got, other))
        if leaf == "g" and CASES[case][4]:
            assert np.max(np.abs(got32 - other)) <= limit * np.max(
                np.abs(other))
        else:
            assert _rel(got32, other) <= limit, (case, leaf)


def test_the_kept_states_are_the_backward_passes_residual():
    """Differentiated, `gdn_fwd` writes the state entering EVERY chunk (the
    one residual beside the inputs, q and k a key head among them); called
    for o alone, the first only."""
    case = "two-a-key-head-bf16"
    ins = _inputs(case)[:5]
    *kept, entering = gdn_kernel._gdn_fwd(*ins, CHUNK)[1]
    assert [x.shape for x in kept] == [x.shape for x in ins]
    assert entering.shape == (1, 4, 4, K, K)
    assert _rel(np.swapaxes(np.asarray(entering[0]), -1, -2),
                _float64(case)[0]["entering"]) <= 3e-5
    alone = str(jax.make_jaxpr(lambda *a: gdn_kernel.gdn(*a, CHUNK))(*ins))
    assert "f32[1,1,4,128,128]" in alone and "f32[1,4,4," not in alone
    both = str(jax.make_jaxpr(jax.grad(
        lambda *a: gdn_kernel.gdn(*a, CHUNK).sum(), argnums=(0, 3)))(*ins))
    # q, k and their cotangents a KEY head
    assert "bf16[1,256,256]" in both


def _shaped(T=256, Hk=2, Hv=4, width=K, dtype=jnp.bfloat16):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    key = jax.ShapeDtypeStruct((1, T, Hk, width), dtype)
    return (key, key, jax.ShapeDtypeStruct((1, T, Hv, width), dtype),
            f32(1, T, Hv), f32(1, T, Hv))


# what is asked -> the reason's words (None: taken); the backend patched to
# a TPU in all but the first
RULE = {
    "off-a-tpu": (dict(), CHUNK, None, "not a tpu"),
    "taken": (dict(), CHUNK, None, None),
    "taken-one-a-key-head-f32": (dict(Hk=4, dtype=jnp.float32), CHUNK, None,
                                 None),
    "taken-the-cells-heads": (dict(Hk=16, Hv=32), CHUNK, None, None),
    "taken-one-device-mesh": (dict(), CHUNK, 1, None),
    "a-mesh": (dict(), CHUNK, 2, "a mesh of 2 devices"),
    "not-whole-chunks": (dict(T=250), CHUNK, None, "not whole chunks"),
    "chunk-16": (dict(), 16, None, "a chunk of 16"),
    "narrow-heads": (dict(width=64), CHUNK, None, "whole lane tiles"),
    "float16": (dict(dtype=jnp.float16), CHUNK, None, "float16"),
    "eight-a-key-head": (dict(Hk=1, Hv=8), CHUNK, None,
                         "do not divide the 4 heads of a grid step"),
    "no-whole-groups": (dict(Hk=3), CHUNK, None, "not whole groups"),
}


@pytest.mark.parametrize("row", sorted(RULE))
def test_the_rule_is_a_table(row, monkeypatch):
    """`refusal` by platform, mesh, shapes, dtype and the heads' grouping;
    where it refuses, `kda.scan` runs the XLA form and the counter names the
    first reason; where it admits, the head kernels and their name."""
    shapes, chunk, devices, words = RULE[row]
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: row != "off-a-tpu")
    mesh = devices and meshlib.make_mesh(dp=devices,
                                         devices=jax.devices()[:devices])
    args = _shaped(**shapes)
    reason = gdn_kernel.refusal(*args, chunk, mesh)
    assert (reason is None) if words is None else (words in reason)
    noted = len(tracing.forms("kda.scan"))
    trace = lambda: jax.make_jaxpr(
        lambda *a: kda.scan(*a, chunk, mesh=mesh))(*args)
    if row == "no-whole-groups":    # no key head for value head 3
        with pytest.raises(ValueError, match="whole groups"):
            trace()
        return
    traced = trace()
    (note,) = tracing.forms("kda.scan")[noted:]
    assert ("pallas_call" in str(traced)) == (words is None)
    if words is None:
        assert note == {"site": "kda.scan", "form": "head-kernel",
                        "reason": None}
    else:
        assert note["form"] == "xla" and words in note["reason"]


REFUSED = {"chunk-16": (dict(), 16), "narrow-heads": (dict(width=64), CHUNK),
           "a-mesh": (dict(), CHUNK),
           "eight-a-key-head": (dict(Hk=1, Hv=8, T=128), CHUNK)}


@pytest.mark.parametrize("row", sorted(REFUSED))
def test_a_refused_call_gets_the_same_answer_from_the_xla_form(
        row, monkeypatch):
    """A clause of the rule at a time, on a "TPU": the XLA form serves the
    call (the key heads repeated, g broadcast, inside `scan`) and gives the
    recurrence's answer."""
    shapes, chunk = REFUSED[row]
    shaped = _shaped(**{"T": 128, "dtype": jnp.float32, **shapes})
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q, k, v, g, beta = (jax.random.normal(key, x.shape, x.dtype)
                        for key, x in zip(keys, shaped))
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True))
    g, beta = -jax.nn.softplus(g), jax.nn.sigmoid(beta)
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: True)
    mesh = meshlib.make_mesh(dp=2, devices=jax.devices()[:2]) if (
        row == "a-mesh") else None
    got = kda.scan(q, k, v, g, beta, chunk, mesh=mesh)
    assert tracing.forms("kda.scan")[-1]["form"] == "xla"
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta)[0]
    assert _rel(got[0], want) <= 2e-6


def test_scan_hands_a_heads_decay_to_the_head_kernels(monkeypatch):
    """Where the rule admits the call `scan` runs the head kernels on q and k
    a KEY head, forward, backward and `terms` (G comes back a head's), ONE
    Mosaic call each, and a channel's decay still goes to `kda_fwd`."""
    case = "two-a-key-head-bf16"
    ins, want = _inputs(case)[:5], _xla_terms(case)
    monkeypatch.setattr(kda_kernel, "_on_tpu", lambda: True)
    for fn, calls in ((lambda *a: kda.scan(*a, CHUNK, terms=True), 1),
                      (jax.grad(lambda *a: kda.scan(*a, CHUNK).sum()), 2)):
        text = jax.jit(fn).lower(*ins).as_text(debug_info=True)
        assert len(re.findall(r"gdn_(?:fwd|bwd)\b[^\"]*/pallas_call\"",
                              text)) >= calls
        assert "kda_fwd" not in text and "kda_bwd" not in text
    assert tracing.forms("kda.scan")[-1]["form"] == "head-kernel"
    o, terms = kda.scan(*ins, CHUNK, terms=True)
    assert sorted(terms) == ["G", "U", "entering"]
    for name, got in {"o": o, **terms}.items():
        assert got.shape == want[name].shape, name
        assert _rel(got, want[name]) <= 3e-6, name
    q, k, v, g, beta = ins
    wide = jnp.broadcast_to(g[..., None], v.shape)
    text = jax.jit(lambda *a: kda.scan(*a, CHUNK)).lower(
        v, v, v, wide, beta).as_text(debug_info=True)
    assert "kda_fwd" in text and "gdn_fwd" not in text
    assert tracing.forms("kda.scan")[-1]["form"] == "kernel"


@pytest.mark.parametrize("name", [gdn_kernel.GDN_FWD, gdn_kernel.GDN_BWD])
def test_the_kernels_names_are_no_other_readers(name):
    """`benchmark/reduce` finds the attention, selection, rotary and Mamba
    kernels by substring: the names hold none of them, nor the channel
    kernels', do not end in what a trace reader strips as numbering, and both
    calls sit under the scan's own scope."""
    assert not any(s in name for s in ("flash", "dsa_", "rope", "ssd", "kda"))
    assert not name[-1].isdigit()
    text = jax.jit(jax.grad(lambda *a: gdn_kernel.gdn(*a, CHUNK).sum())).lower(
        *_shaped()).as_text(debug_info=True)
    assert re.search(
        rf'"[^"]*{tracing.SCOPE_KDA_SCAN}[^"]*\b{name}\b[^"]*/pallas_call"',
        text)
