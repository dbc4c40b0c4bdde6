"""One harness for the flash kernels' tests (interpret mode): a case is made
from a hashable key once a process (seeded inputs, key-padding bias, the
float32 reference's output and gradients), a forward and a blockwise oracle
once a (case, form of qkv, tiles), and ONE check holds a backward kernel to
them. A new kernel's cases are one more `parametrize` table over `check`,
not a copy of its body. Not collected: the tables are in
`test_flash_chosen_tiles.py`, `test_flash_btd_layout.py`,
`test_flash_backward_one_tile.py`, `test_flash_backward.py`,
`test_flash_row_selection.py` and `test_flash_window.py`."""
import contextlib
import functools
from typing import NamedTuple, Optional
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import flash_attention as fa
from hetu_tpu.kernels.flash_attention import mha_reference

DTYPES = pytest.mark.parametrize("dtype,tol_fwd,tol_bwd", [
    (jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 2e-2, 2e-2)],
    ids=["f32", "bf16"])


def rand_qkv(rng, b=2, h=2, s=256, d=64):
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    return q, k, v


def to_rows(x):
    """(b, h, s, d) -> (b, s, h*d): the layout the kernels index."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def to_heads(x, h):
    """(b, s, h*d) -> (b, h, s, d): the layout `mha_reference` takes."""
    b, s, w = x.shape
    return x.reshape(b, s, h, w // h).transpose(0, 2, 1, 3)


def padding_bias(rng, b, s, min_valid=8):
    """(b, s) key-padding bias: 0 for valid keys, -1e9 for a padded tail."""
    lens = rng.randint(min_valid, s + 1, b)
    pos = np.arange(s)[None, :]
    return jnp.asarray(np.where(pos < lens[:, None], 0.0, -1e9), jnp.float32)


def pallas_calls(jaxpr):
    """The pallas_calls at the top of a jaxpr, in order."""
    return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]


class Case(NamedTuple):
    """The key of a case. `bias`: None, "tail" (every batch row's tail
    padded) or "row" (and the second batch row padded entirely: its forward
    is the reference's uniform softmax, its backward never was, since lse =
    -1e9 + log(l) rounds to -1e9 in f32 and the rebuilt p is 1 and not 1/l;
    such a row's dO is zero in a real loss, here its gradients must be
    finite and are compared nowhere). `qk_std`: the deviation q and k are
    drawn with (v and dO with 1)."""
    heads: int
    d: int              # head width of q and k
    dv: int             # head width of v and o
    s: int
    batch: int
    causal: bool
    bias: Optional[str]
    dtype: type
    seed: int
    qk_std: float = 0.3
    rows: Optional[int] = None  # a selection by query row: every query keeps
                                # itself and up to `rows` - 1 seeded others
                                # of the keys before it (causal cases)
    window: Optional[int] = None    # a sliding window: query t keeps the
                                    # keys t - window < s <= t (causal cases)


def chosen_case(s, d, causal, bias, dtype, b=2, h=3):
    """The key the tables at chosen tiles and the one-tile table share: h =
    3 is a multiple of no power-of-two head group (three heads of 64 go as
    one block of 192 lanes); with a bias and two batch rows the second is
    padded entirely, one batch row has its tail padded, not all of it."""
    return Case(h, d, d, s, b, causal,
                ("row" if b > 1 else "tail") if bias else None, dtype,
                seed=s + d + causal + 2 * bias)


class Inputs(NamedTuple):
    q: jax.Array        # (b, s, h*d) in the case's dtype, as are k, v, do
    k: jax.Array
    v: jax.Array
    do: jax.Array
    k_bias: Optional[jax.Array]
    ref: jax.Array      # (b, h, s, dv) float32: the reference's output
    ref_grads: tuple    # its vjp at dO: dq, dk, dv in (b, h, s, .) float32
    row_mask: Optional[tuple] = None    # the `pack_row_mask` pair


# LLVM at -O0 for what the harness compiles: each program runs once at a toy
# size, so its compile is the cost (a third less on the many-tile table, 167
# -> 111 s on one core). Asked for a compile at a time and not in XLA_FLAGS:
# -O0 sums a reduction in order where -O2 vectorises it, and elsewhere in the
# suite that moves comparisons the seeds here do not come near (ISSUE 42).
_O0 = {"xla_backend_optimization_level": 0}


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile(compiler_options=_O0)


@functools.lru_cache(maxsize=None)
def _reference(causal, has_bias, b, h, s, d, dv, has_rows=False):
    """The float32 reference's output and its vjp at dO, compiled once a
    shape: a case in bfloat16 runs the program its float32 twin built."""
    def out_and_grads(q, k, v, do, k_bias, keep):
        out, vjp = jax.vjp(lambda q, k, v: mha_reference(
            q, k, v, causal, k_bias=k_bias if has_bias else None,
            keep=keep if has_rows else None), q, k, v)
        return out, vjp(do)

    qk, v = (jax.ShapeDtypeStruct((b, h, s, w), jnp.float32) for w in (d, dv))
    return _compiled(out_and_grads, qk, qk, v, v,
                     jax.ShapeDtypeStruct((b, s), jnp.float32),
                     jax.ShapeDtypeStruct((b, s, s), jnp.bool_))


def kept_rows(rng, b, s, rows):
    """(b, s, s) bool inside the causal triangle: query t keeps itself and
    the `rows` - 1 keys before it of largest seeded score (all while t <
    `rows`), so no two queries' sets need be alike."""
    score = rng.rand(b, s, s)
    score[:, np.arange(s), np.arange(s)] = 2.0
    score = np.where(np.tril(np.ones((s, s), bool)), score, -1.0)
    kth = -np.sort(-score, axis=-1)[..., min(rows, s) - 1:min(rows, s)]
    return jnp.asarray((score >= kth) & (score >= 0))


@functools.lru_cache(maxsize=None)
def make(case: Case) -> Inputs:
    """Seeded q, k, v, dO in the case's dtype and the reference on the SAME
    values in float32 (so the inputs' own rounding is not counted)."""
    b, h, s = case.batch, case.heads, case.s
    rng = np.random.RandomState(case.seed)
    q, k = (jnp.asarray(rng.randn(b, h, s, case.d), jnp.float32) * case.qk_std
            for _ in range(2))
    v, do = (jnp.asarray(rng.randn(b, h, s, case.dv), jnp.float32)
             for _ in range(2))
    k_bias = None
    if case.bias:
        k_bias = padding_bias(rng, b, s)
        if case.bias == "row":
            k_bias = k_bias.at[1].set(-1e9)
    keep = kept_rows(rng, b, s, case.rows) if case.rows else None
    if case.window:     # to the reference a window is a boolean mask too
        pos = np.arange(s)
        keep = jnp.broadcast_to(jnp.asarray(
            (pos[None, :] <= pos[:, None])
            & (pos[None, :] > pos[:, None] - case.window)), (b, s, s))
        if k_bias is not None:
            # a row whose whole window is padding is a fully padded row (see
            # `Case.bias`): its dO is zero, as in a real loss
            seen = jnp.any(keep & (k_bias == 0)[:, None, :], -1)
            do = do * seen[:, None, :, None]
    given = tuple(x.astype(case.dtype) for x in (q, k, v, do))
    ref, ref_grads = _reference(case.causal, k_bias is not None, b, h, s,
                                case.d, case.dv, keep is not None)(
        *(x.astype(jnp.float32) for x in given),
        jnp.zeros((b, s)) if k_bias is None else k_bias,
        jnp.ones((b, s, s), bool) if keep is None else keep)
    return Inputs(*(to_rows(x) for x in given), k_bias, ref, ref_grads,
                  fa.pack_row_mask(keep) if case.rows else None)


def _scale(case):
    return 1.0 / np.sqrt(case.d)


def _qkv(case, fused):
    """The kernels get (b, s, h*d) arrays: three, or with `fused` the one
    [q|k|v] array a fused projection writes."""
    x = make(case)
    qkv = (x.q, x.k, x.v)
    return jnp.concatenate(qkv, axis=-1) if fused else qkv


def _forcing(case, tiles):
    """`tiles` = (block_q, block_k, heads a step): where the heads are
    given, `_choose_tiles` answers with exactly these for both kernels of
    the call; where they are None (or `tiles` is), it chooses."""
    if tiles is None or tiles[2] is None:
        return contextlib.nullcontext()
    block_q, block_k, group = tiles
    kernels = fa._kernels_of(case.s, block_q, block_k)
    return mock.patch.object(
        fa, "_choose_tiles",
        lambda *a, **kw: (block_q, block_k, dict.fromkeys(kernels, group)))


@functools.lru_cache(maxsize=None)
def forward(case, fused, tiles=None):
    """-> (qkv, out, lse, k_bias) and, under a selection by row, its packed
    pair: what the backward is given, of `_fwd_pallas` at the blocks of
    `tiles` (None: the chooser's)."""
    block_q, block_k = (None, None) if tiles is None else tiles[:2]
    qkv = _qkv(case, fused)
    k_bias, row_mask = make(case).k_bias, make(case).row_mask
    with _forcing(case, tiles):
        out, lse = _compiled(lambda qkv, k_bias, row_mask: fa._fwd_pallas(
            qkv, case.heads, k_bias, _scale(case), case.causal, block_q,
            block_k, interpret=True, row_mask=row_mask, window=case.window),
            qkv, k_bias, row_mask)(qkv, k_bias, row_mask)
    return (qkv, out, lse, k_bias) + ((row_mask,) if case.rows else ())


@functools.lru_cache(maxsize=None)
def oracle(case, fused, tiles=None):
    """`_bwd_blockwise` on the same forward, at the forward's key blocks."""
    block_k = tiles and tiles[1]
    if block_k is None:
        block_k = fa._choose_tiles(case.s, case.d, case.dtype, case.causal,
                                   case.heads, dv=case.dv)[1]
    args = forward(case, fused, tiles), make(case).do
    return _compiled(functools.partial(
        fa._bwd_blockwise, n_heads=case.heads, scale=_scale(case),
        causal=case.causal, block_k=min(block_k, case.s),
        window=case.window), *args)(*args)


def check(case, fused, tol_fwd, tol_bwd, *, tiles=None, kernel=None,
          against_oracle=True):
    """Forward and ONE backward kernel (interpret mode) of `case`, on three
    arrays or one fused, at `tiles` (`_forcing`): the forward's dtypes and
    its output against the reference; the backward is one pallas_call, named
    `kernel` (None: whichever `_kernels_of` the forward's blocks is), whose
    first result is dq, on the grid the forced tiles give; the gradient
    comes in the form and dtype qkv came in, is finite everywhere (a fully
    padded row too), and equals the blockwise oracle (`against_oracle`) and
    the reference's gradient, at `tol_bwd`."""
    x = make(case)
    b, h, s = case.batch, case.heads, case.s
    res = forward(case, fused, tiles)
    qkv, out, lse = res[:3]
    assert out.dtype == case.dtype and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(to_heads(out, h), np.float32),
                               np.asarray(x.ref), rtol=tol_fwd, atol=tol_fwd)
    compared = slice(0, 1) if case.bias == "row" else slice(None)

    block_q, block_k, group = tiles or (None, None, None)
    with _forcing(case, tiles):
        traced = jax.jit(functools.partial(
            fa._bwd_pallas, n_heads=h, scale=_scale(case), causal=case.causal,
            block_q=block_q, block_k=block_k, interpret=True,
            window=case.window)).trace(res, x.do)
    calls = pallas_calls(traced.jaxpr)
    if kernel is None:
        kernel = fa._kernels_of(s, *fa._choose_tiles(
            s, case.d, case.dtype, case.causal, h, block_q, block_k,
            case.dv)[:2])[1]
    assert [e.params["name"] for e in calls] == [kernel]
    assert calls[0].outvars[0].aval.shape == (b, s, h * case.d)    # dq first
    if group is not None:
        assert calls[0].params["grid_mapping"].grid == (
            b, h // group, s // block_k if kernel == fa.FLASH_BWD_DQKV else 1)
    got = traced.lower().compile(compiler_options=_O0)(res, x.do)
    wants = [x.ref_grads]
    if against_oracle:
        want = oracle(case, fused, tiles)
        wants.append([to_heads(w, h) for w in (
            jnp.split(want, 3, axis=-1) if fused else want)])
    if fused:
        assert got.shape == qkv.shape
        got = jnp.split(got, 3, axis=-1)
    for i, a in enumerate(got):
        assert a.dtype == case.dtype
        a = np.asarray(to_heads(a, h), np.float32)
        assert np.isfinite(a).all()
        for want in wants:
            np.testing.assert_allclose(
                a[compared], np.asarray(want[i], np.float32)[compared],
                rtol=tol_bwd, atol=tol_bwd)
