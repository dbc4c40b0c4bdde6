"""Gated DeltaNet's chunked scan through VMEM: the gated delta rule with a
decay a HEAD.

`kernels/kda.py` runs the rule with a decay a CHANNEL, and pays for it: the
pairs' decays exp(G_r[c] - G_i[c]) stand inside the sum over the key columns,
so a chunk is cut into six LEVELS, each an exp over (C, K), two float32 arrays
k F and q F split into bfloat16 pieces and a six-pass product, walked a second
time backward. With ONE log-decay a value head and position the exponential
leaves the sum (`models/kda.py`, "A DECAY A HEAD"):

  A[r, i] = beta_r (k_r . k_i) exp(G_r - G_i)    i < r
  P[r, i] =        (q_r . k_i) exp(G_r - G_i)    i <= r

so the pairs are ONE product of operands that ARE bfloat16, the Gram matrix
of [k; q] (2 C x 2 C: k k^T, q k^T and its transpose side by side, nothing
to split and nothing to turn), made once a KEY head and shared by its value
heads, times ONE (C, C) matrix of decays a value head, D = exp(G_r - G_i)
with the difference taken FIRST (trap (i) of `models/kda.py`: the cell's G
reaches -1,564 inside a chunk). No levels. Every other decay multiplies the
side of a product that is float32 anyway, so that q and k go into every
product as the one bfloat16 piece they are:

  [k; q] S            then  exp G . (k S), exp G . (q S)      (rows of the result)
  U = X (beta (v - exp G . k S))                            (`_inverse`, unchanged)
  o = exp G . q S + (QK . D) U
  S' = exp(G_last) S + k^T (exp(G_last - G) . U)            (rows of U)

exp(G) underflows to 0 where G < -103: that is its value, the reference's
too. A grid step is one chunk of `heads` VALUE heads and the `heads / r` key
heads that serve them (r = Hv / Hk: q and k arrive un-repeated, (B, T, Hk *
K), and a block of value heads maps to its block of key heads); G and beta
come as small column and row blocks, (C, heads) and (heads, 128): nothing (T,
Hv * K) float32 is written, read or returned. Grid, scratch, `_lockstep`,
`_inverse`, the pieces and the passes are `kernels/kda.py`'s, shared.

The backward kernel makes a chunk again from the state that entered it, as
`kda_bwd` does; dKK and dQK are pulled back through `. D` by plain products
(dk = (M_A + M_A^T) k + M_P^T q, dq = M_P k with M_A = beta dA . D, M_P =
dP . D, ONE product of [(M_A + M_A^T | M_P^T); (M_P | 0)] against [k; q]);
dG_r is the row sum less the column sum of dA . A + dP . P plus the state
terms' scalars, ONE float32 a position and value head, written as columns
like d beta; dq and dk are summed over a key head's r value heads inside the
grid step and written once a key head. The reverse cumulated sum of dG stays
XLA's, over (T, Hv).

``gdn(q, k, v, g, beta, chunk)`` is `models/kda.scan` of a head's decay that
`refusal` admits, behind a custom_vjp (`gdn_fwd`, `gdn_bwd`); ``terms(...)``
the forward kernel writing U and every entering state. Called directly off a
TPU the kernels are interpreted, which is how tests drive them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.tracing import SCOPE_KDA_SCAN
from . import kda
from .flash_attention import _dot_nt, _dot_tn
from .kda import (CHUNK, _LANES, _beta_blocks, _heads, _interpreted,
                  _inverse, _lockstep, _mm, _mm_narrow, _pieces, _pieces32,
                  _stacked, _turned)

# the kernels' names in the device trace (`mosaic:<name>`): none of the
# substrings other kernels are found by ("flash", "dsa_", "rope", "ssd")
GDN_FWD = "gdn_fwd"
GDN_BWD = "gdn_bwd"


def refusal(q, k, v, g, beta, chunk, mesh=None):
    """Why the head kernels do not take the scan of q, k (B, T, Hk, K), v
    (B, T, Hv, V), g and beta (B, T, Hv), the first reason; None where they
    take it: `kda.refusal`'s clauses (one program on a TPU, chunks of
    `CHUNK`, whole chunks, heads of whole lane tiles, the dtypes), and whole
    groups of r = Hv / Hk value heads a key head inside a grid step."""
    reason = kda.refusal(q, k, v, g, beta, chunk, mesh)
    if reason is not None:
        return reason
    Hk, Hv = k.shape[2], v.shape[2]
    if Hv % Hk:
        return f"{Hv} value heads are not whole groups on {Hk} key heads"
    if _heads(Hv) % (Hv // Hk):
        return (f"{Hv // Hk} value heads a key head do not divide the "
                f"{_heads(Hv)} heads of a grid step")
    return None


def _exp_below(x):
    """exp(min(x, 0)): a masked-away entry's difference is positive and
    would overflow before the mask drops it."""
    return jnp.exp(jnp.minimum(x, 0.0))


def _gram(k, q):
    """The pieces of [k; q] (2 C, K) and their Gram matrix (2 C, 2 C)
    float32: k k^T and its neighbour k q^T = (q k^T)^T in the upper rows, q
    k^T in the lower. ONE bfloat16 pass where q and k are bfloat16."""
    kq = _stacked(_pieces(k), _pieces(q))
    return kq, _mm(kq, kq, _dot_nt)


def _fwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref, o_ref,
                s_ref, *rest, heads, group, K, V, every, terms):
    # grid: (batch, blocks of value heads, chunks); a chunk of `heads` value
    # heads on `heads / group` key heads. state: (heads, V, K) f32, S^T of
    # every value head ENTERING this chunk
    u_ref, state = rest if terms else (None,) + rest
    chunk = pl.program_id(2)
    C = v_ref.shape[0]

    @pl.when(chunk == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    @pl.when(chunk % every == 0)
    def _():
        s_ref[...] = state[...]

    r = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    grams = [_gram(k_ref[:, j * K:(j + 1) * K], q_ref[:, j * K:(j + 1) * K])
             for j in range(heads // group)]

    def head(h):
        vl = slice(h * V, (h + 1) * V)
        kq, gram = grams[h // group]
        gcol, bcol = gcol_ref[:, h:h + 1], bcol_ref[:, h:h + 1]
        # D = exp(G_r - G_i), i <= r: the difference first
        D = jnp.where(i <= r, _exp_below(gcol - grow_ref[h:h + 1, :]), 0.0)
        ST = state[h]                                        # (V, K)
        kqS = _mm(kq, _pieces(ST), _dot_nt)                  # [k; q] S
        yield
        X = yield from _inverse(bcol * jnp.where(i < r, gram[:C] * D, 0.0))
        expG = jnp.exp(gcol)                                 # (C, 1)
        U = _mm_narrow(_pieces32(X), _pieces(bcol * (
            v_ref[:, vl].astype(jnp.float32) - expG * kqS[:C])))
        yield
        pu = _pieces(U)
        o_ref[:, vl] = expG * kqS[C:] + _mm_narrow(
            _pieces32(gram[C:] * D), pu)
        if terms:
            u_ref[:, vl] = U
        yield
        last = gcol[C - 1:, :]                               # (1, 1)
        # the state's decay a scalar, along the lanes first (Mosaic
        # broadcasts along lanes or sublanes, not both at once: the exp
        # keeps the two apart, `ssd._along`)
        decay = jnp.exp(jnp.broadcast_to(last, (1, K)))
        state[h] = decay * ST + _mm(_pieces(jnp.exp(last - gcol) * U),
                                    [piece[:C] for piece in kq], _dot_tn)

    _lockstep(head(h) for h in range(heads))


def _bwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, bcol_ref, brow_ref,
                s_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate,
                *, heads, group, K, V):
    # grid: (batch, blocks of value heads, chunks from the LAST). s_ref: S^T
    # of every value head ENTERING this chunk; dstate: (heads, V, K) f32,
    # the cotangent of every head's S^T LEAVING it
    C = v_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, jnp.float32)

    r = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    # the transposed forms, (C, 2 C): row i against r in the lanes, the
    # system's half (strictly below) beside the output's (the diagonal too)
    turned_pair = ((i < C) & (r < i)) | ((i >= C) & (r <= i - C))
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    grams = [_gram(k_ref[:, j * K:(j + 1) * K], q_ref[:, j * K:(j + 1) * K])
             for j in range(heads // group)]
    dkq_of = [[] for _ in grams]

    def head(h):
        vl = slice(h * V, (h + 1) * V)
        kq, gram = grams[h // group]
        pk = [piece[:C] for piece in kq]
        gcol, grow = gcol_ref[:, h:h + 1], grow_ref[h:h + 1, :]
        bcol, brow = bcol_ref[:, h:h + 1], brow_ref[h:h + 1, :]
        ST, dST, do = s_ref[h], dstate[h], do_ref[:, vl]
        # the chunk again, as `_fwd_kernel` made it
        D = jnp.where(i <= r, _exp_below(gcol - grow), 0.0)
        KK = jnp.where(i < r, gram[:C] * D, 0.0)
        ps, pdst = _pieces(ST), _pieces(dST)
        kqS = _mm(kq, ps, _dot_nt)                           # [k; q] S
        kdS = _mm(pk, pdst, _dot_nt)                         # k dS'
        yield
        X = yield from _inverse(bcol * KK)
        expG, last = jnp.exp(gcol), gcol[C - 1:, :]
        to_last = jnp.exp(last - gcol)
        decay = jnp.exp(jnp.broadcast_to(last, (1, K)))
        rest = v_ref[:, vl].astype(jnp.float32) - expG * kqS[:C]
        U = _mm_narrow(_pieces32(X), _pieces(bcol * rest))
        pu = _pieces(U)
        yield
        # o = exp G . q S + P U and S' = decay S + k^T (to_last . U), pulled
        # back to U: [KK^T | P^T] is the Gram matrix's upper rows times the
        # decays the other way round, (C, 2 C)
        Dt = jnp.where(turned_pair, _exp_below(grow - gcol), 0.0)
        turned = gram[:C] * Dt
        pdo = _pieces(do)
        Pt = jnp.where(i < C, pltpu.roll(turned, C, 1), 0.0)
        dU = _mm_narrow(_pieces32(Pt), pdo) + to_last * kdS
        yield
        # U = X R, R = beta (v - exp G . k S)
        dR = _mm_narrow(_pieces32(_turned(X)[:C]), _pieces(dU))
        yield
        dv = bcol * dR
        dv_ref[:, vl] = dv.astype(dv_ref.dtype)
        # dA = -dR U^T below the diagonal (d X = -X dA X), dP = do U^T: made
        # transposed (U's C rows streamed), then turned
        UdRdo = _mm(pu, _stacked(_pieces(dR), pdo), _dot_nt)  # (C, 2 C)
        yield
        M = _turned(UdRdo)                                   # (2 C, 128)
        dA = jnp.where(i < r, -M[:C], 0.0)
        db_ref[:, h:h + 1] = (jnp.sum(dR * rest, axis=1, keepdims=True)
                              + jnp.sum(dA * KK, axis=1, keepdims=True))
        # M_A = beta dA . D, M_P = dP . D, and both transposed side by side
        MA, MP = bcol * dA * D, M[C:] * D
        Mt = jnp.where(i < C, -brow, 1.0) * UdRdo * Dt
        pairs = _mm(_stacked(_pieces(Mt + MA), _pieces(MP)), kq)
        # the state's operands: [d kS; d qS] = exp G . [-dv; do], and U dS'
        left = _stacked(_pieces(-expG * dv), _pieces(expG * do))
        through = _mm(left, ps)                              # (2 C, K)
        dkq_of[h // group].append(
            (pairs[:C] + through[:C] + to_last * _mm(pu, pdst),
             pairs[C:] + through[C:]))
        at_last = to_last * jnp.sum(U * kdS, axis=1, keepdims=True)
        dlast = jnp.sum(at_last, axis=0, keepdims=True) + jnp.sum(
            decay * jnp.sum(ST * dST, axis=0, keepdims=True), axis=1,
            keepdims=True)
        dg_ref[:, h:h + 1] = (
            expG * jnp.sum(do * kqS[C:] - dv * kqS[:C], axis=1, keepdims=True)
            - at_last
            + jnp.sum(MA * gram[:C] + MP * gram[C:], axis=1, keepdims=True)
            - jnp.sum(Mt * gram[:C], axis=1, keepdims=True)
            + jnp.where(row == C - 1, dlast, 0.0))
        dstate[h] = decay * dST + _mm(left, kq, _dot_tn)

    _lockstep(head(h) for h in range(heads))
    # a key head's q and k served `group` value heads: their sum, once
    for j, parts in enumerate(dkq_of):
        for ref, of_heads in zip((dk_ref, dq_ref), zip(*parts)):
            ref[:, j * K:(j + 1) * K] = sum(
                of_heads[1:], of_heads[0]).astype(ref.dtype)


def _cumulated(g, chunk):
    """g (B, T, H) cumulated over each chunk."""
    B, T, H = g.shape
    return jnp.cumsum(g.reshape(B, T // chunk, chunk, H), axis=2).reshape(
        B, T, H)


def _decay_blocks(G, chunk, heads):
    """`_beta_blocks` of the cumulated log-decay, the rows with G in BOTH
    halves of the lanes (the transposed forms read the upper one)."""
    cols, rows = _beta_blocks(G, chunk, heads)
    return cols, rows + jnp.roll(rows, chunk, axis=-1)


def _sizes(k, v, chunk):
    """-> (B, T, Hv, K, V, chunks, the value heads a grid step, the value
    heads a key head)."""
    B, T, Hk, K = k.shape
    Hv, V = v.shape[2:]
    return B, T, Hv, K, V, T // chunk, _heads(Hv), Hv // Hk


def _forward(q, k, v, g, beta, chunk, every, terms=False):
    """-> (o (B, T, Hv, V) f32, the states entering every `every`-th chunk,
    transposed: (B, ceil(n / every), Hv, V, K) f32[, U (B, T, Hv, V), G (B,
    T, Hv)])."""
    B, T, H, K, V, n, heads, group = _sizes(k, v, chunk)
    with jax.named_scope(SCOPE_KDA_SCAN):
        G = _cumulated(g, chunk)
        wide = lambda W: pl.BlockSpec((None, chunk, W),
                                      lambda b, h, c: (b, c, h))
        small = lambda rows, cols: pl.BlockSpec(
            (None, None, None, rows, cols), lambda b, h, c: (b, c, h, 0, 0))
        flat = lambda x: x.reshape(B, T, -1)
        blocks = [small(chunk, heads), small(heads, _LANES)]
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, heads=heads, group=group, K=K, V=V,
                              every=every, terms=terms),
            grid=(B, H // heads, n),
            in_specs=[wide(heads // group * K)] * 2 + [wide(heads * V)]
            + blocks + blocks[:1],
            out_specs=[wide(heads * V), pl.BlockSpec(
                (None, None, heads, V, K),
                lambda b, h, c: (b, c // every, h, 0, 0))]
            + [wide(heads * V)] * terms,
            out_shape=[jax.ShapeDtypeStruct((B, T, H * V), jnp.float32),
                       jax.ShapeDtypeStruct((B, -(-n // every), H, V, K),
                                            jnp.float32)]
            + [jax.ShapeDtypeStruct((B, T, H * V), jnp.float32)] * terms,
            scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpreted(),
            name=GDN_FWD,
        )(flat(q), flat(k), flat(v), *_decay_blocks(G, chunk, heads),
          _beta_blocks(beta, chunk, heads)[0])
    o, entering = out[0].reshape(B, T, H, V), out[1]
    if terms:
        return o, entering, out[2].reshape(B, T, H, V), G
    return o, entering


def _backward(q, k, v, g, beta, entering, do, chunk):
    """-> (dq, dk, dv, dg, dbeta) in the inputs' shapes and dtypes;
    `entering`: `_forward`'s states at `every` 1, (B, n, Hv, V, K)."""
    B, T, H, K, V, n, heads, group = _sizes(k, v, chunk)
    with jax.named_scope(SCOPE_KDA_SCAN):
        G = _cumulated(g, chunk)
        wide = lambda W: pl.BlockSpec((None, chunk, W),
                                      lambda b, h, c: (b, n - 1 - c, h))
        small = lambda *block: pl.BlockSpec(
            (None, None) + block,
            lambda b, h, c: (b, n - 1 - c, h) + (0,) * (len(block) - 1))
        flat = lambda x: x.reshape(B, T, -1)
        like = lambda x: jax.ShapeDtypeStruct(flat(x).shape, x.dtype)
        cols = jax.ShapeDtypeStruct((B, n, H // heads, chunk, heads),
                                    jnp.float32)
        key, blocks = wide(heads // group * K), [
            small(None, chunk, heads), small(None, heads, _LANES)]
        dq, dk, dv, dG, dbeta = pl.pallas_call(
            functools.partial(_bwd_kernel, heads=heads, group=group, K=K,
                              V=V),
            grid=(B, H // heads, n),
            in_specs=[key, key, wide(heads * V)] + blocks * 2
            + [small(heads, V, K), wide(heads * V)],
            out_specs=[key, key, wide(heads * V), blocks[0], blocks[0]],
            out_shape=[like(q), like(k), like(v), cols, cols],
            scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpreted(),
            name=GDN_BWD,
        )(flat(q), flat(k), flat(v), *_decay_blocks(G, chunk, heads),
          *_beta_blocks(beta, chunk, heads), entering,
          flat(do.astype(jnp.float32)))
        positions = lambda x: x.swapaxes(2, 3).reshape(B, n, chunk, H)
        # G is g cumulated down a chunk: dg_t sums dG from t to its end
        dg = jax.lax.cumsum(positions(dG), axis=2, reverse=True)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), positions(dbeta).reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gdn(q, k, v, g, beta, chunk):
    """`models/kda.scan` of a head's decay that `refusal` admits: q, k (B,
    T, Hk, K), v (B, T, Hv, V), g and beta (B, T, Hv) float32 -> o (B, T, Hv,
    V) float32."""
    return _forward(q, k, v, g, beta, chunk, k.shape[1] // chunk)[0]


def _gdn_fwd(q, k, v, g, beta, chunk):
    o, entering = _forward(q, k, v, g, beta, chunk, 1)
    return o, (q, k, v, g, beta, entering)


def _gdn_bwd(chunk, residuals, do):
    return _backward(*residuals, do, chunk)


gdn.defvjp(_gdn_fwd, _gdn_bwd)


def terms(q, k, v, g, beta, chunk):
    """The kernel with its parts written out -> (o, {U (B, T, Hv, V),
    entering (B, n, Hv, K, V), G (B, T, Hv)}), `models/kda.scan(...,
    terms=True)`'s with a head's decay."""
    o, entering, U, G = _forward(q, k, v, g, beta, chunk, 1, terms=True)
    return o, {"U": U, "entering": entering.swapaxes(-1, -2), "G": G}
