"""Kimi Delta Attention's chunked scan through VMEM.

`models/kda.py` is the gated delta rule's chunked form in XLA: the pairwise
decays of the diagonal sub-blocks, KK / QK, the inverse's small ops and the
`[U~ | W]` product go through HBM as float32 a segment at a time, and the
state walks the chunks through `lax.scan` (docs/KERNELS.md, "The gated delta
rule in VMEM"). Here a grid step holds ONE chunk of C = 64 positions of
`heads` heads as the projections wrote them (q, k, v, G (C, heads * K)) and
takes each head from its q, k, v, G, beta to its o without leaving VMEM; the
chunks are the sequential axis of the grid and S^T, (V, K) float32 a head,
lies in VMEM scratch over all of them.

A pair's decay, trap (i) of `models/kda.py`: exp(G_r - G_i) is never
exp(G_r) / exp(G_i). The pairs (r, i), i < r, of a chunk are cut by LEVELS: at
level s (32, 16, ..., 1) the chunk is blocks of 2 s positions, and a pair
belongs to the one level at which r lies in the later half and i in the
earlier half of the same block. Through the later half's first position m
the decay is two factors, exp(G_r - G_m) and exp(G_m - G_i), both <= 1 (the
difference is taken FIRST, a position against m), so a level is one
elementwise pass F = exp(-(+)(G - G_m)) over (C, K) and ONE product (k F) [k
F; q F]^T (k F's C rows streamed against the 128 stationary ones: [KK^T |
QK^T] at full lanes) whose other blocks are masked away: no (C, C, K) tensor
exists, and the sum over the channels is the MXU's. The levels' sum is turned
once into KK and QK as WIDE arrays, (C, 128) with zeros from lane C on, the
form of every C-wide matrix here.

The system (I + A) is inverted exactly by the same levels: with X_s the
inverse of the diagonal blocks of s positions and Off_s the blocks of A that
level s holds, X_2s = X_s - X_s Off_s X_s, from X_1 = I: the merge
`models/kda.py` makes above 16 positions, made from one position up. No
series is cut short.

Precision as `models/kda.py` states it: G, the factors, the system, its
inverse, U, the state and o float32. A float32 product is run as bfloat16
passes of the operands' exact pieces (`_pieces`: a float32 is three
bfloat16 terms; the six pairs whose error is above float32's own are kept,
what `Precision.HIGHEST` runs), so an operand that IS bfloat16 (q, k, v as
they arrive) is one piece and its product three passes or one, with nothing
dropped that was not zero. The passes of a product are ONE dot, the pieces
side by side along the contraction (a dot costs the rows it pushes and the
results it pops; `_mm`, `_mm_narrow`), and the heads of a grid step are
traced in lockstep (`_lockstep`), a stage of each in turn, so that one
head's split stands between another's product and its use: a chunk is a
chain of ~20 dependent products, and head after head each ran at its full
latency (docs/KERNELS.md has the measurements).

The backward kernel walks the chunks from the last with the cotangent of
every head's LEAVING state in scratch and makes a chunk again from the state
that ENTERED it (the one residual beside the inputs: `kda_fwd` writes it for
every chunk when it is differentiated, 64 KiB a chunk and head): the pairs'
products, the inverse and U as the forward kernel made them, then o = QG S +
QK U and S' = decay S + Kd^T U pulled back to U, U = X R to R = beta (v - KG
S) and to A (d X = -X dA X gives dA = -dR U^T: the inverse's cotangent is
never formed), and dKK, dQK back through the levels. A level's term moves
G_r up and G_i down by the same amount and the position m between them not
at all, so no sum of near equal terms is taken. Nothing (C, C, K) and
nothing a segment wide exists in either direction.

``kda(q, k, v, g, beta, chunk)`` is `models/kda.scan` of a call `takes`
admits, differentiable through a custom_vjp (`kda_fwd`, `kda_bwd`); the
log-decay is cumulated over each chunk by XLA and handed in, and d g is the
reverse cumulated sum of what the kernel returns for G. ``terms(...)`` is
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
from .flash_attention import _dot, _dot_nt, _dot_tn

# the kernels' names in the device trace (`mosaic:<name>`). Readers find the
# attention, selection, rotary and Mamba kernels by the substrings "flash",
# "dsa_", "rope" and "ssd": these names must hold none of them
KDA_FWD = "kda_fwd"
KDA_BWD = "kda_bwd"

CHUNK = 64          # positions a chunk: the levels below are written for it
_LANES = 128
# the heads of a grid step are traced side by side (`_lockstep`): 30.0 /
# 19.6 / 14.4 ms a layer's forward pass at 1 / 2 / 4 on the chip; 8 read
# 14.1, compile twice as long and overrun the VMEM Mosaic gives unasked
_MAX_HEADS = 4


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpreted() -> bool:
    # not `_on_tpu()`: a test that patches the rule to take the kernel off
    # the chip still needs it interpreted there
    return jax.default_backend() != "tpu"


def _heads(H):
    """Heads a grid step carries: the most of 1, 2, 4, ... up to
    `_MAX_HEADS` that divide H."""
    heads = 1
    while heads * 2 <= _MAX_HEADS and H % (heads * 2) == 0:
        heads *= 2
    return heads


def refusal(q, k, v, g, beta, chunk, mesh=None):
    """Why the kernel does not take the scan of q, k (B, T, H, K), v (B, T,
    H, V), g (B, T, H, K), beta (B, T, H), the first reason; None where it
    takes it: one program on a TPU (under a mesh the XLA form stays: GSPMD
    cannot partition the custom kernel; off a TPU interpret mode would be
    slower than it), whole chunks of `CHUNK` positions, heads of whole lane
    tiles, q, k, v in one of bfloat16 / float32 and g, beta float32."""
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices"
    if not _on_tpu():
        return "backend is not a tpu"
    if chunk != CHUNK:
        return f"a chunk of {chunk} positions, not {CHUNK}"
    if k.ndim != 4 or k.shape[1] % chunk:
        return f"{k.shape[1]} positions are not whole chunks of {chunk}"
    if k.shape[-1] % _LANES or v.shape[-1] % _LANES:
        return (f"heads of {k.shape[-1]} / {v.shape[-1]} columns are not "
                f"whole lane tiles of {_LANES}")
    dtypes = {jnp.dtype(x.dtype) for x in (q, k, v)}
    if len(dtypes) != 1 or not dtypes <= {jnp.dtype(jnp.bfloat16),
                                          jnp.dtype(jnp.float32)}:
        return f"q, k, v in {sorted(str(d) for d in dtypes)}"
    if g.dtype != jnp.float32 or beta.dtype != jnp.float32:
        return f"g, beta in {g.dtype}, {beta.dtype}"
    return None


def takes(q, k, v, g, beta, chunk, mesh=None) -> bool:
    """The ONE gating rule `models/kda.scan` asks."""
    return refusal(q, k, v, g, beta, chunk, mesh) is None


def _split(x, dtype):
    """Three terms that add up to float32 x, each exactly a bfloat16 (a
    float32's 24 bits, 8 a term), as `dtype`."""
    out = []
    for n in range(3):
        piece = x.astype(jnp.bfloat16)
        exact = piece.astype(jnp.float32)
        out.append(piece if dtype == jnp.bfloat16 else exact)
        if n < 2:
            x = x - exact
    return out


def _pieces(x):
    """bfloat16 terms that add up to x: x itself where it is bfloat16
    already, else three."""
    return [x] if x.dtype == jnp.bfloat16 else _split(x, jnp.bfloat16)


def _pieces32(x):
    """`_pieces` of a float32 array as float32 arrays: what `_mm_narrow`
    rolls before it rounds."""
    return _split(x, jnp.float32)


def _passes(a, b):
    """The passes (i, j) of a float32 product of `_pieces`: i + j <= 2 (what
    is left out is below float32's own rounding of the result)."""
    return [(i, j) for i in range(len(a)) for j in range(len(b))
            if i + j <= 2]


_CONTRACTED = {_dot: (1, 0), _dot_nt: (1, 1), _dot_tn: (0, 0)}


def _cat(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis)


def _stacked(a, b):
    """The pieces of [a; b] from the pieces of a and of b."""
    return [jnp.concatenate(pair, axis=0) for pair in zip(a, b)]


def _mm(a, b, dot=_dot):
    """The float32 product of two operands given as `_pieces`, as ONE dot:
    the passes lie side by side along the contraction, so the MXU sums them
    and the result is read once (six dots of 64 rows cost 2.7 to 3.9 x what
    the one does: the rows pushed and the results popped set the pace)."""
    passes, (at_a, at_b) = _passes(a, b), _CONTRACTED[dot]
    return dot(_cat([a[i] for i, _ in passes], at_a),
               _cat([b[j] for _, j in passes], at_b))


def _mm_narrow(a, b):
    """`_mm` of a WIDE left operand, (M, 128) float32 with zeros from lane
    C = 64 on, given as `_pieces32`, with b (C, N) given as `_pieces`: two
    passes share a 128-wide tile of the contraction, the second's piece
    rolled into the free lanes (a tile of its own a pass costs the MXU 24
    more pushes a product: measured, 27.8 ms a layer against 30.8)."""
    passes = _passes(a, b)
    rolled = {i: pltpu.roll(a[i], CHUNK, 1) for i, _ in passes[1::2]}
    left, right = [], []
    for (i, j), (i2, j2) in zip(passes[::2], passes[1::2]):
        left.append((a[i] + rolled[i2]).astype(jnp.bfloat16))
        right.append(jnp.concatenate([b[j], b[j2]], axis=0))
    if len(passes) % 2:
        i, j = passes[-1]
        left.append(a[i].astype(jnp.bfloat16))
        right.append(jnp.concatenate([b[j], b[j]], axis=0))
    return _dot(_cat(left, 1), _cat(right, 0))


_DONE = object()


def _lockstep(bodies):
    """Run generators in lockstep, a step of each in turn: the heads of a
    grid step are traced stage by stage side by side, so that one head's
    split and product stand between another's product and its use (the
    compiler keeps the program's order by and large: a head after a head
    ran the chain of 20 dependent products a chunk at its full latency,
    and one head a grid step cost what eight did)."""
    live = list(bodies)
    while live:
        live = [body for body in live if next(body, _DONE) is not _DONE]


def _levels(C):
    return [C >> n for n in range(1, C.bit_length())]     # 32, 16, ..., 1


def _level_mask(shape, s, later=0):
    """bool of `shape`: the index along axis `later` (a position r, modulo
    C where that axis is longer) in the later half and the index along the
    other axis (i) in the earlier half of the same block of 2 s positions.
    Lanes from C on of a wide array are in no block. In shifts and masks:
    s is a power of two, and the VPU has no integer division."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, later) & (CHUNK - 1)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - later)
    return (((r ^ i) >> s.bit_length()) == 0) & ((r & s) != 0) & (
        (i & s) == 0)


def _to_reference(G, s):
    """G_r - G_m down the chunk, m the first position of the later half of
    r's block of 2 s positions, with the sign turned in the earlier half:
    <= 0 everywhere where G falls down the chunk. G (C, K) float32."""
    C, K = G.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (C, K), 0)
    if s >= 8:      # whole sublane tiles: the row m, repeated down its block
        ref = jnp.concatenate(
            [jnp.broadcast_to(G[m:m + 1, :], (2 * s, K))
             for m in range(s, C, 2 * s)], axis=0)
    else:           # inside a tile: a sublane of each tile, down the tile
        tiles = G.reshape(C // 8, 8, K)
        sub = jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
        ref = None
        for m in range(8 - s, 0, -2 * s):   # the last block's m first
            at = jnp.broadcast_to(tiles[:, m:m + 1, :], tiles.shape)
            ref = at if ref is None else jnp.where(sub < m + s, at, ref)
        ref = ref.reshape(C, K)
    return jnp.where((row & s) != 0, G - ref, ref - G)


def _turned(x):
    """x (C, 2 C) -> its transpose as a WIDE array (2 C, 128): lanes from C
    on are zeros."""
    return jnp.concatenate([x, jnp.zeros_like(x)], axis=0).T


def _pair_products(q, k, G):
    """`models/kda.pair_products` -> (KK, QK, [KK^T | QK^T], the levels'
    (F, pieces of k F, pieces of q F)): KK (strictly below the diagonal) and
    QK (the diagonal too) as WIDE (C, 128) arrays, and both transposed side
    by side, (C, 2 C), as the MXU makes them: a level streams k F's C rows
    against [k F; q F]."""
    C = G.shape[0]
    total, levels = jnp.zeros((C, 2 * C), jnp.float32), []
    for s in _levels(C):
        F = jnp.exp(_to_reference(G, s))
        pk, pq = _pieces(k * F), _pieces(q * F)
        levels.append((F, pk, pq))
        total = total + jnp.where(_level_mask((C, 2 * C), s, 1),
                                  _mm(pk, _stacked(pk, pq), _dot_nt), 0.0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    total = jnp.where(r == i + C, jnp.sum(q * k, axis=1, keepdims=True),
                      total)
    wide = _turned(total)
    return wide[:C], wide[C:], total, levels


def _inverse(A):
    """(I + A)^-1 of strictly lower triangular A, WIDE (C, 128), exactly,
    by the levels from one position up: X_2s = X_s - X_s Off_s X_s. A
    generator (`_lockstep`): yields after each product, returns X."""
    r = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0)
    i = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    X = (r == i).astype(jnp.float32)
    for s in _levels(A.shape[0])[::-1]:
        off = jnp.where(_level_mask(A.shape, s), A, 0.0)
        if s == 1:
            X = X - off
        else:
            x32 = _pieces32(X)
            px = [piece.astype(jnp.bfloat16) for piece in x32]
            T = _mm_narrow(_pieces32(off), px)
            yield
            X = X - _mm_narrow(x32, _pieces(T))
            yield
    return X


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref, o_ref, s_ref,
                *rest, heads, K, V, every, terms):
    # grid: (batch, head blocks, chunks); a chunk of `heads` heads. state:
    # (heads, V, K) f32, S^T of every head ENTERING this chunk
    u_ref, state = rest if terms else (None,) + rest
    chunk = pl.program_id(2)
    C = q_ref.shape[0]

    @pl.when(chunk == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    @pl.when(chunk % every == 0)
    def _():
        s_ref[...] = state[...]

    def head(h):
        kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        q, k = (x[:, kl].astype(jnp.float32) for x in (q_ref, k_ref))
        v, G = v_ref[:, vl], g_ref[:, kl]
        KK, QK, _, _ = _pair_products(q, k, G)
        yield
        X = yield from _inverse(bcol_ref[:, h:h + 1] * KK)
        # [U~ | W] = X Diag(beta) [v | k exp G]: beta rides on X's columns,
        # so that v goes in as it arrived
        xb = _pieces32(X * brow_ref[h:h + 1, :])
        expG = jnp.exp(G)
        Ut, W = _mm_narrow(xb, _pieces(v)), _mm_narrow(xb, _pieces(k * expG))
        yield
        # the state's three products: [W; q exp G] S, QK U, U^T (k decayed
        # to the chunk's end)
        ST = state[h]                                        # (V, K)
        WQ = _mm(_pieces(jnp.concatenate([W, q * expG], axis=0)),
                 _pieces(ST), _dot_nt)
        yield
        U = Ut - WQ[:C]
        pu = _pieces(U)
        o_ref[:, vl] = WQ[C:] + _mm_narrow(_pieces32(QK), pu)
        if terms:
            u_ref[:, vl] = U
        yield
        last = G[C - 1:, :]                                  # (1, K)
        state[h] = jnp.exp(last) * ST + _mm(
            pu, _pieces(k * jnp.exp(last - G)), _dot_tn)

    _lockstep(head(h) for h in range(heads))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, heads, K,
                V):
    # grid: (batch, head blocks, chunks from the LAST). s_ref: S^T of every
    # head ENTERING this chunk; dstate: (heads, V, K) f32, the cotangent of
    # every head's S^T LEAVING it
    C = q_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, jnp.float32)

    r = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    it = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    rt = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    def head(h):
        kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        q, k = (x[:, kl].astype(jnp.float32) for x in (q_ref, k_ref))
        G, bcol, brow = g_ref[:, kl], bcol_ref[:, h:h + 1], brow_ref[h:h + 1]
        ST, dST, do = s_ref[h], dstate[h], do_ref[:, vl]
        # the chunk again, as `_fwd_kernel` made it
        KK, _, turned, levels = _pair_products(q, k, G)
        yield
        X = yield from _inverse(bcol * KK)
        expG, last = jnp.exp(G), G[C - 1:, :]
        QG, KG, decay = q * expG, k * expG, jnp.exp(last)
        to_last = jnp.exp(last - G)
        Kd = k * to_last
        ps, pdst = _pieces(ST), _pieces(dST)
        rest = v_ref[:, vl].astype(jnp.float32) - _mm(_pieces(KG), ps,
                                                      _dot_nt)
        yield
        pu = _pieces(_mm_narrow(_pieces32(X), _pieces(bcol * rest)))
        yield
        # o = QG S + QK U and S' = decay S + Kd^T U, pulled back to U (QK^T
        # is the right half of what the levels made), then U = X R to R =
        # beta (v - KG S)
        pdo = _pieces(do)
        QKt = jnp.where(i < C, pltpu.roll(turned, C, 1), 0.0)
        dU = _mm_narrow(_pieces32(QKt), pdo) + _mm(_pieces(Kd), pdst,
                                                  _dot_nt)
        yield
        dR = _mm_narrow(_pieces32(_turned(X)[:C]), _pieces(dU))
        yield
        dv = bcol * dR
        dv_ref[:, vl] = dv.astype(dv_ref.dtype)
        # dA = -dR U^T below the diagonal (d X = -X dA X), dQK = do U^T:
        # made transposed (U's C rows streamed), then turned; both forms
        # go back through the levels
        Dt = _mm(pu, _stacked(_pieces(dR), pdo), _dot_nt)    # (C, 2 C)
        yield
        D = _turned(Dt)                                      # (2 C, 128)
        dA = jnp.where(i < r, -D[:C], 0.0)
        db_ref[:, h:h + 1] = (jnp.sum(dR * rest, axis=1, keepdims=True)
                              + jnp.sum(dA * KK, axis=1, keepdims=True))
        D = jnp.concatenate([bcol * dA, jnp.where(i <= r, D[C:], 0.0)],
                            axis=0)
        Dt = jnp.where(((rt < C) & (it < rt)) | ((rt >= C) & (it <= rt - C)),
                       jnp.where(rt < C, -brow, 1.0) * Dt, 0.0)
        # the state's operands: [dQG; dKG] = [do; -dv] S, dKd = U dS'
        left = _stacked(pdo, _pieces(-dv))
        dQKG = _mm(left, ps)                                 # (2 C, K)
        dKd = _mm(pu, pdst)
        dq = expG * dQKG[:C]
        dk = expG * dQKG[C:] + to_last * dKd
        at_last = Kd * dKd
        dG = QG * dQKG[:C] + KG * dQKG[C:] - at_last
        dlast = (jnp.sum(at_last, axis=0, keepdims=True)
                 + decay * jnp.sum(ST * dST, axis=0, keepdims=True))
        dstate[h] = decay * dST + _mm(
            left, _stacked(_pieces(QG), _pieces(KG)), _dot_tn)
        yield
        # the pairs' products, level by level: a term of level s moves G_r
        # up and G_i down by the same amount, and the position between them
        # not at all
        for s, (F, pk, pq) in zip(_levels(C), levels):
            rows = _mm_narrow(_pieces32(jnp.where(
                _level_mask(D.shape, s), D, 0.0)), pk)       # (2 C, K)
            cols = _mm(_pieces(jnp.where(
                _level_mask(Dt.shape, s, 1), Dt, 0.0)), _stacked(pk, pq))
            gk, gq = F * (rows[:C] + cols), F * rows[C:]
            dk, dq = dk + gk, dq + gq
            later = (jax.lax.broadcasted_iota(jnp.int32, G.shape, 0) & s) != 0
            moved = k * gk + q * gq
            dG = dG + jnp.where(later, moved, -moved)
        own = jnp.sum(jnp.where(i == r, D[C:], 0.0), axis=1, keepdims=True)
        dq_ref[:, kl] = (dq + own * k).astype(dq_ref.dtype)
        dk_ref[:, kl] = (dk + own * q).astype(dk_ref.dtype)
        dg_ref[:, kl] = dG + jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, G.shape, 0) == C - 1, dlast,
            0.0)

    _lockstep(head(h) for h in range(heads))


def _cumulated(g, chunk):
    """g (B, T, H, K) cumulated over each chunk -> G (B, T, H * K)."""
    B, T, H, K = g.shape
    return jnp.cumsum(g.reshape(B, T // chunk, chunk, H, K), axis=2).reshape(
        B, T, H * K)


def _beta_blocks(beta, chunk, heads):
    """beta (B, T, H) -> its two block forms, (B, n, H / heads, chunk,
    heads) columns and (B, n, H / heads, heads, 128) rows, zeros from lane
    `chunk` on."""
    B, T, H = beta.shape
    cols = beta.reshape(B, T // chunk, chunk, H // heads, heads).swapaxes(2, 3)
    return cols, jnp.pad(cols.swapaxes(3, 4),
                         ((0, 0),) * 4 + ((0, _LANES - chunk),))


def _forward(q, k, v, g, beta, chunk, every, terms=False):
    """-> (o (B, T, H, V) f32, the states entering every `every`-th chunk,
    transposed: (B, ceil(n / every), H, V, K) f32[, U (B, T, H, V), G (B, T,
    H, K)])."""
    B, T, H, K = k.shape
    V, n, heads = v.shape[-1], T // chunk, _heads(H)
    with jax.named_scope(SCOPE_KDA_SCAN):
        G = _cumulated(g, chunk)
        wide = lambda W: pl.BlockSpec((None, chunk, heads * W),
                                      lambda b, h, c: (b, c, h))
        small = lambda rows, cols: pl.BlockSpec(
            (None, None, None, rows, cols), lambda b, h, c: (b, c, h, 0, 0))
        flat = lambda x: x.reshape(B, T, -1)
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, heads=heads, K=K, V=V, every=every,
                              terms=terms),
            grid=(B, H // heads, n),
            in_specs=[wide(K), wide(K), wide(V), wide(K),
                      small(chunk, heads), small(heads, _LANES)],
            out_specs=[wide(V), pl.BlockSpec(
                (None, None, heads, V, K),
                lambda b, h, c: (b, c // every, h, 0, 0))]
            + [wide(V)] * terms,
            out_shape=[jax.ShapeDtypeStruct((B, T, H * V), jnp.float32),
                       jax.ShapeDtypeStruct((B, -(-n // every), H, V, K),
                                            jnp.float32)]
            + [jax.ShapeDtypeStruct((B, T, H * V), jnp.float32)] * terms,
            scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpreted(),
            name=KDA_FWD,
        )(flat(q), flat(k), flat(v), G, *_beta_blocks(beta, chunk, heads))
    o, entering = out[0].reshape(B, T, H, V), out[1]
    if terms:
        return o, entering, out[2].reshape(B, T, H, V), G.reshape(g.shape)
    return o, entering


def _backward(q, k, v, g, beta, entering, do, chunk):
    """-> (dq, dk, dv, dg, dbeta) in the inputs' shapes and dtypes;
    `entering`: `_forward`'s states at `every` 1, (B, n, H, V, K)."""
    B, T, H, K = k.shape
    V, n, heads = v.shape[-1], T // chunk, _heads(H)
    with jax.named_scope(SCOPE_KDA_SCAN):
        G = _cumulated(g, chunk)
        wide = lambda W: pl.BlockSpec((None, chunk, heads * W),
                                      lambda b, h, c: (b, n - 1 - c, h))
        small = lambda *block: pl.BlockSpec(
            (None, None) + block,
            lambda b, h, c: (b, n - 1 - c, h) + (0,) * (len(block) - 1))
        flat = lambda x: x.reshape(B, T, -1)
        like = lambda x, W: jax.ShapeDtypeStruct((B, T, H * W), x.dtype)
        cols = jax.ShapeDtypeStruct((B, n, H // heads, chunk, heads),
                                    jnp.float32)
        dq, dk, dv, dG, dbeta = pl.pallas_call(
            functools.partial(_bwd_kernel, heads=heads, K=K, V=V),
            grid=(B, H // heads, n),
            in_specs=[wide(K), wide(K), wide(V), wide(K),
                      small(None, chunk, heads), small(None, heads, _LANES),
                      small(heads, V, K), wide(V)],
            out_specs=[wide(K), wide(K), wide(V), wide(K),
                       small(None, chunk, heads)],
            out_shape=[like(q, K), like(k, K), like(v, V), like(g, K), cols],
            scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpreted(),
            name=KDA_BWD,
        )(flat(q), flat(k), flat(v), G, *_beta_blocks(beta, chunk, heads),
          entering, flat(do.astype(jnp.float32)))
        # G is g cumulated down a chunk: dg_t sums dG from t to its end
        dg = jax.lax.cumsum(dG.reshape(B, n, chunk, H, K), axis=2,
                            reverse=True)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta.swapaxes(2, 3).reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda(q, k, v, g, beta, chunk):
    """`models/kda.scan` of a call `takes` admits: q, k (B, T, H, K), v (B,
    T, H, V), g (B, T, H, K) and beta (B, T, H) float32 -> o (B, T, H, V)
    float32."""
    return _forward(q, k, v, g, beta, chunk, k.shape[1] // chunk)[0]


def _kda_fwd(q, k, v, g, beta, chunk):
    o, entering = _forward(q, k, v, g, beta, chunk, 1)
    return o, (q, k, v, g, beta, entering)


def _kda_bwd(chunk, residuals, do):
    return _backward(*residuals, do, chunk)


kda.defvjp(_kda_fwd, _kda_bwd)


def terms(q, k, v, g, beta, chunk):
    """The kernel with its parts written out -> (o, {U (B, T, H, V),
    entering (B, n, H, K, V), G (B, T, H, K)}), `models/kda.scan(...,
    terms=True)`'s."""
    o, entering, U, G = _forward(q, k, v, g, beta, chunk, 1, terms=True)
    return o, {"U": U, "entering": entering.swapaxes(-1, -2), "G": G}
