"""The chunked Mamba-2 scan through VMEM, forward and backward.

`transformer._ssd` is four einsums the compiler schedules: the float32 decay
matrix of every head and chunk, the masked `C B^T . L` and the chunk states go
through HBM, and the backward pass is the compiler's transpose of all of it
(docs/KERNELS.md, "The chunked scan in VMEM"). Here a grid step holds one
chunk of Q positions of `heads` heads as the projections wrote them (x (Q,
heads * P), B and C (Q, N)) and, a head at a time, builds

    M[l, s] = (C B^T)[l, s] exp(a_l - a_s) dt_s        for s <= l, else 0

(a the log-decay cumulated over the chunk), multiplies it with x, adds
`(C exp(a_l)) S` of the state S that ENTERS the chunk and moves S on:
`S <- exp(a_last) S + (B^T . dt exp(a_last - a)) x`. The chunks are a
sequential axis of the grid and S, (N, H * P) float32, lies in VMEM scratch
over all of them: no chunk state is written but the entering ones, which the
backward pass reads. `C B^T`, `B^T` and C in float32 are made once a group
and kept in scratch for its heads.

Three things shape the code. (1) A per-position factor is cheap along the
lanes (a (1, Q) row against (., Q): a sublane broadcast) and dear down the
sublanes (a (Q, 1) column: a lane broadcast a row tile), so dt rides in
the exponent of M, the entering part scales C's rows with the one column a
head needs anyway (a_l), and the state's operand is B^T with its COLUMNS
scaled: one column form a head and pass. (2) Heads of 64 columns come two a
lane tile: a product is made against the whole tile and the head's half
selected, so nothing is shifted along the lanes and no store is masked. (3)
Above the diagonal M is 0: of a chunk's 128-row blocks each meets only the
columns up to its own.

The backward kernel walks the chunks in reverse with the state's cotangent in
scratch, rebuilds M a chunk at a time TRANSPOSED (rows s, columns l), so that
every product is a plain or an NT one, and returns dx, d dt, d(log-decay) and
dB, dC summed over a group's heads in VMEM. Nothing (H, Q, Q) is saved: the
residuals are the inputs and the entering states. The sums that cancel in
d(log-decay) (a row's and a column's of dM . M) are taken of the SAME
float32 products.

Precision as `_ssd` states it: dt, the cumulative log-decay, the decay
matrix, the states and every sum float32; matmul operands in x's dtype; y
float32. Against `_ssd` the operands are ROUNDED elsewhere (M with dt in it
and x bare, where `_ssd` rounds `C B^T . L` and `x dt`): the same class of
result, not the same bits.

``ssd(x, dt, acs, Bm, Cm, chunk)`` is `_ssd` with the cumulative log-decay
`acs` (`transformer._ssm_log_decay`, (B, T, H)) handed in, differentiable
through a custom_vjp; ``takes(x, Bm, chunk, mesh)`` is the one rule a caller
asks. Called directly off a TPU the kernels are interpreted, which is how
tests drive them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, _dot_nt, _dot_tn

# the kernels' names in the device trace (`mosaic:<name>`). Readers find the
# attention, selection and rotary kernels by the substrings "flash", "dsa_"
# and "rope": these names must hold none of them
SSD_FWD = "ssd_fwd"
SSD_BWD = "ssd_bwd"

_LANES = 128
# What a grid step may hold by `_vmem_bytes`' count without asking (three
# quarters of the 16 MiB Mosaic gives a kernel on a v5e unasked), what it
# may hold when it asks, and what it then asks for (the chip has 128 MiB)
_VMEM_BUDGET = 12 * 1024 * 1024
_VMEM_BUDGET_ASKED = 48 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_MAX_HEADS = 16     # the head loop of a grid step is unrolled
# exp's argument above the diagonal, where a_l - a_s > 0 may overflow and the
# masked C B^T is 0: capped where exp is still finite
_CAP = 80.0
# d dt = (...) / dt: a step size that underflowed to 0 has M = 0 and gets 0
_TINY = 1e-37


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpreted() -> bool:
    # not `_on_tpu()`: a test that patches the rule to take the kernels off
    # the chip still needs them interpreted there
    return jax.default_backend() != "tpu"


def _vmem_bytes(Q, P, N, H, heads, itemsize, kernel):
    """Bytes a grid step of `kernel` holds at `heads` heads: blocks in and
    out twice (the pipeline's two buffers), the scratch once, and the
    float32 temporaries of one head ((Q, Q) of the decay matrix and its
    cotangent, (N, Q) of the state's and the entering part's operands)."""
    x = Q * heads * P * itemsize
    bc = 2 * Q * N * itemsize
    rows = 2 * max(heads, 8) * Q * 4      # the (heads, Q) float32 blocks
    wide = Q * heads * P * 4              # y, dy: float32 like x
    entering = N * heads * P * 4
    state = N * H * P * 4
    square, flat = Q * Q * 4, N * Q * 4
    if kernel == SSD_FWD:
        blocks = x + rows + bc + wide + entering
        return 2 * blocks + state + square + 2 * flat + 3 * square + 2 * flat
    blocks = (x + rows + bc + entering + wide        # in
              + x + rows + 2 * Q * N * 4)            # out
    return (2 * blocks + state + 2 * square + 4 * flat + Q * _LANES * 4
            + 5 * square + 4 * flat)


def _heads(H, G, P, N, Q, itemsize):
    """Heads a grid step takes -> (heads, asks), or None where no group
    serves: the most of `_MAX_HEADS` that divide a group's heads in whole
    lane tiles (two heads of 64 columns a tile) and in whole sublane tiles
    of the (heads, Q) rows (or are the group), and whose count in BOTH
    kernels fits `_VMEM_BUDGET`; where none fits, the fewest that fit
    `_VMEM_BUDGET_ASKED`, and the call asks Mosaic for `_VMEM_LIMIT`."""
    R, tile = H // G, max(1, _LANES // P)
    groups = [h for h in range(min(R, _MAX_HEADS), 0, -1)
              if R % h == 0 and h % tile == 0 and (h % 8 == 0 or h == R)]
    count = lambda h: max(_vmem_bytes(Q, P, N, H, h, itemsize, k)
                          for k in (SSD_FWD, SSD_BWD))
    for budget, order in ((_VMEM_BUDGET, groups),
                          (_VMEM_BUDGET_ASKED, groups[::-1])):
        fit = next((h for h in order if count(h) <= budget), None)
        if fit is not None:
            return fit, budget != _VMEM_BUDGET
    return None


def takes(x, Bm, chunk, mesh=None) -> bool:
    """The ONE gating rule: the kernels run the scan of x (B, T, H, P) with
    Bm / Cm (B, T, G, N) on the single-program TPU path, where whole chunks
    of whole lane tiles divide the sequence, the widths fill the tiles the
    kernels slice and a grid step's heads fill the sublanes of the (heads,
    Q) blocks it transposes. Under a mesh `_ssd` stays (GSPMD cannot
    partition the custom kernel); off-TPU interpret mode would be slower
    than `_ssd`."""
    if (mesh is not None and mesh.size > 1) or not _on_tpu():
        return False
    if x.ndim != 4 or Bm.ndim != 4:
        return False
    (_, T, H, P), (G, N) = x.shape, Bm.shape[2:]
    if x.dtype not in (jnp.bfloat16, jnp.float32) or Bm.dtype != x.dtype:
        return False
    if (T % chunk or chunk % _LANES or N % _LANES or H % G
            or (P != 64 and P % _LANES)):
        return False
    heads = _heads(H, G, P, N, chunk, x.dtype.itemsize)
    return heads is not None and heads[0] % 8 == 0


def _triangle(Q, lower):
    """(Q, Q) bool: row >= column (`lower`), or row <= column."""
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return row >= col if lower else row <= col


def _halves(shape, P):
    """The lane masks of the heads that share a lane tile of `shape`'s
    columns: [None] for a head of whole tiles, else one bool array a head."""
    if shape[1] == P:
        return [None]
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // P
    return [head == t for t in range(shape[1] // P)]


def _pick(masks, parts):
    """The heads' results side by side: part t where mask t holds."""
    out = parts[-1]
    for mask, part in zip(masks[-2::-1], parts[-2::-1]):
        out = jnp.where(mask, part, out)
    return out


def _own(mask, a):
    """`a` with the lanes of the tile's other heads zeroed: an operand whose
    lanes are contracted."""
    return a if mask is None else jnp.where(mask, a, jnp.zeros_like(a))


def _rows_of(a_ref, dt_ref):
    """The per-position float32 rows of a grid step's heads, (heads, Q) each
    -> a, dt, b = a - log dt (so that exp(a_l - b_s) = exp(a_l - a_s) dt_s),
    w = dt exp(a_last - a), and (heads, 1) a_last."""
    a, dt = a_ref[...], dt_ref[...]
    last = a[:, a.shape[1] - 1:]
    b = a - jnp.log(dt)
    return a, dt, b, jnp.exp(last - b), last


def _along(scalar, width):
    """exp of a (1, 1) value along `width` lanes, (1, width): for a product
    with (rows, width). Mosaic broadcasts along lanes or along sublanes, not
    both at once, and folds two broadcasts in a row into one: the exp keeps
    them apart."""
    return jnp.exp(jnp.broadcast_to(scalar, (1, width)))


def _fwd_kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, y_ref, s_ref,
                state, cb, bt, cf, *, heads, P, per_group):
    # grid: (batch, chunks, head blocks); a chunk of `heads` heads. state:
    # (head blocks, N, heads * P) f32, S^T of every head ENTERING this
    # chunk; cb: masked C B^T; bt: B^T, cf: C, float32
    chunk, hk = pl.program_id(1), pl.program_id(2)
    Q, N = x_ref.shape[0], b_ref.shape[1]
    dtype = x_ref.dtype
    W = max(P, _LANES)

    @pl.when(chunk == 0)
    def _():
        state[hk] = jnp.zeros(state.shape[1:], jnp.float32)

    @pl.when(hk % per_group == 0)
    def _():
        cb[...] = jnp.where(_triangle(Q, True),
                            _dot_nt(c_ref[...], b_ref[...]), 0.0)
        bt[...] = b_ref[...].astype(jnp.float32).T
        cf[...] = c_ref[...].astype(jnp.float32)

    a, _, b, w, last = _rows_of(a_ref, dt_ref)
    a_col = a.T                                      # (Q, heads)
    ea_col = jnp.exp(a_col)
    masks, masks_n, masks_1 = (_halves((rows, W), P) for rows in (Q, N, 1))
    for g in range(heads * P // W):
        lanes = slice(g * W, (g + 1) * W)
        X = x_ref[:, lanes]
        S = state[hk, :, lanes]                      # (N, W) f32
        Sb = S.astype(dtype)
        ys, news, decays = [], [], []
        for t in range(len(masks)):
            j = g * len(masks) + t
            col = row = slice(j, j + 1)
            blocks = []
            for r in range(Q // _LANES):
                upto = (r + 1) * _LANES
                rows = slice(r * _LANES, upto)
                E = jnp.exp(jnp.minimum(a_col[rows, col] - b[row, :upto],
                                        _CAP))
                blocks.append(
                    _dot((cb[rows, :upto] * E).astype(dtype), X[:upto])
                    + _dot((cf[rows, :] * ea_col[rows, col]).astype(dtype),
                           Sb))
            ys.append(jnp.concatenate(blocks, axis=0))
            news.append(_dot((bt[...] * w[row, :]).astype(dtype), X))
            decays.append(_along(last[row, :], W))
        y_ref[:, lanes] = _pick(masks, ys)
        s_ref[:, lanes] = S
        state[hk, :, lanes] = (_pick(masks_1, decays) * S
                               + _pick(masks_n, news))


def _bwd_kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, s_ref, dy_ref,
                dx_ref, da_ref, ddt_ref, db_ref, dc_ref,
                dstate, cbt, bt, ct, dcbt, dbt, dct, gcol, *,
                heads, P, per_group):
    # grid: (batch, chunks from the LAST, head blocks). dstate: (head
    # blocks, N, heads * P) f32, the cotangent of every head's S^T LEAVING
    # this chunk; cbt: masked (C B^T)^T, rows s and columns l; bt, ct: B^T,
    # C^T float32; dcbt, dbt, dct: their cotangents summed over the group's
    # heads; gcol (Q, heads): the row sums of (dM . M)^T a head
    step, hk = pl.program_id(1), pl.program_id(2)
    Q, N = x_ref.shape[0], b_ref.shape[1]
    dtype = x_ref.dtype
    W = max(P, _LANES)

    @pl.when(step == 0)
    def _():
        dstate[hk] = jnp.zeros(dstate.shape[1:], jnp.float32)

    @pl.when(hk % per_group == 0)
    def _():
        cbt[...] = jnp.where(_triangle(Q, False),
                             _dot_nt(b_ref[...], c_ref[...]), 0.0)
        bt[...] = b_ref[...].astype(jnp.float32).T
        ct[...] = c_ref[...].astype(jnp.float32).T
        dcbt[...] = jnp.zeros_like(dcbt)
        dbt[...] = jnp.zeros_like(dbt)
        dct[...] = jnp.zeros_like(dct)

    a, dt, b, w, last = _rows_of(a_ref, dt_ref)
    ea, e_last = jnp.exp(a), jnp.exp(last)
    b_col, w_col = b.T, w.T                          # (Q, heads)
    Bm = b_ref[...]
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    masks, masks_n, masks_1 = (_halves((rows, W), P) for rows in (Q, N, 1))
    for g in range(heads * P // W):
        lanes = slice(g * W, (g + 1) * W)
        X, dY = x_ref[:, lanes], dy_ref[:, lanes].astype(dtype)
        S, dS = s_ref[:, lanes], dstate[hk, :, lanes]        # (N, W) f32
        Sb, dSb = S.astype(dtype), dS.astype(dtype)
        B_dS = _dot(Bm, dSb)          # (Q, W): every head of the tile's
        S_dS = S * dS
        dxs, dSs, decays = [], [], []
        for t, (mask, mask_n) in enumerate(zip(masks, masks_n)):
            j = g * len(masks) + t
            col = row = slice(j, j + 1)
            Xh, Sh, dSh = _own(mask, X), _own(mask_n, Sb), _own(mask_n, dSb)
            # 1. inside the chunk, transposed: rows s, columns l >= s
            blocks, da = [], jnp.zeros((1, Q), jnp.float32)
            for r in range(Q // _LANES):
                since = r * _LANES
                rows = slice(since, since + _LANES)
                ET = jnp.exp(jnp.minimum(
                    a[row, since:] - b_col[rows, col], _CAP))
                cbt_r = cbt[rows, since:]
                blocks.append(_dot((cbt_r * ET).astype(dtype), dY[since:]))
                dMT = _dot_nt(Xh[rows], dY[since:]) * ET
                dcbt[rows, since:] += dMT
                # d a_l gets the column sums of (dM . M)^T and d b_s loses
                # its row sums: of the SAME products, so that what cancels
                # between them does
                G = dMT * cbt_r
                sums = jnp.sum(G, axis=0, keepdims=True)
                da = da + (jnp.concatenate(
                    [jnp.zeros((1, since), jnp.float32), sums], axis=1)
                    if since else sums)
                gcol[rows, col] = jnp.sum(G, axis=1, keepdims=True)
            # 4. the entering state's part, transposed: (N, Q)
            dSs.append(_dot((ct[...] * ea[row, :]).astype(dtype), dY))
            dCT = _dot_nt(Sh, dY) * ea[row, :]
            dct[...] += dCT
            da = da + jnp.sum(dCT * ct[...], axis=0, keepdims=True)
            # 2. and 3. the chunk's own state and the recurrence
            dBT = _dot_nt(dSh, Xh) * w[row, :]
            dbt[...] += dBT
            v = jnp.sum(dBT * bt[...], axis=0, keepdims=True)   # dw . w
            dxs.append(jnp.concatenate(blocks, axis=0)
                       + w_col[:, col] * B_dS)
            decays.append(_along(last[row, :], W))
            s_ds = jnp.sum(jnp.sum(_own(mask_n, S_dS), axis=1, keepdims=True),
                           axis=0, keepdims=True)               # (1, 1)
            d_last = (jnp.sum(v, axis=1, keepdims=True)
                      + e_last[row, :] * s_ds)
            da_ref[row, :] = da - v + jnp.where(at_last, d_last, 0.0)
            ddt_ref[row, :] = v
        dx_ref[:, lanes] = _pick(masks, dxs).astype(dx_ref.dtype)
        dstate[hk, :, lanes] = (_pick(masks_1, decays) * dS
                                + _pick(masks_n, dSs))

    # b = a - log dt: d a_s gets d b_s, d dt_s gets -d b_s / dt_s, and
    # d b_s = -(v + g)
    g_row = gcol[...].T                              # (heads, Q)
    da_ref[...] = da_ref[...] - g_row
    ddt_ref[...] = (ddt_ref[...] + g_row) / jnp.maximum(dt, _TINY)

    @pl.when(hk % per_group == per_group - 1)
    def _():
        dcb = jnp.where(_triangle(Q, False), dcbt[...], 0.0).astype(dtype)
        db_ref[...] = dbt[...].T + _dot(dcb, c_ref[...])
        dc_ref[...] = dct[...].T + _dot_tn(dcb, b_ref[...])


def _rows(a, heads):
    """(B, T, H) -> the (heads, Q) blocks' array (B, H / heads, heads, T)."""
    B, T, H = a.shape
    return a.swapaxes(1, 2).reshape(B, H // heads, heads, T)


def _from_rows(a):
    """`_rows`' inverse."""
    B, blocks, heads, T = a.shape
    return a.reshape(B, blocks * heads, T).swapaxes(1, 2)


def _specs(H, P, G, N, Q, heads, chunk_of):
    """Block specs by name for both kernels; `chunk_of(step)` is the chunk
    a grid step of the chunk axis works on."""
    per_group = H // G // heads
    at = lambda f: (lambda b, c, h: f(b, chunk_of(c), h))
    return {
        "x": pl.BlockSpec((None, Q, heads * P), at(lambda b, c, h: (b, c, h))),
        "row": pl.BlockSpec((None, None, heads, Q),
                            at(lambda b, c, h: (b, h, 0, c))),
        "bc": pl.BlockSpec((None, Q, N),
                           at(lambda b, c, h: (b, c, h // per_group))),
        "state": pl.BlockSpec((None, None, None, N, heads * P),
                              at(lambda b, c, h: (b, c, h, 0, 0))),
    }


def _params(asks):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        **({"vmem_limit_bytes": _VMEM_LIMIT} if asks else {}))


def _shapes(x, Bm, chunk):
    (B, T, H, P), (G, N) = x.shape, Bm.shape[2:]
    heads, asks = _heads(H, G, P, N, chunk, x.dtype.itemsize)
    return B, T, H, P, G, N, heads, asks


def _forward(x, dt, acs, Bm, Cm, chunk):
    """-> (y (B, T, H, P) f32, the states entering the chunks, transposed:
    (B, T / chunk, H / heads, N, heads * P) f32)."""
    B, T, H, P, G, N, heads, asks = _shapes(x, Bm, chunk)
    n, blocks = T // chunk, H // heads
    spec = _specs(H, P, G, N, chunk, heads, lambda c: c)
    flat = pltpu.VMEM((N, chunk), jnp.float32)
    y, entering = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, P=P,
                          per_group=H // G // heads),
        grid=(B, n, blocks),
        in_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                  spec["bc"]],
        out_specs=[spec["x"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((B, n, blocks, N, heads * P),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blocks, N, heads * P), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32), flat,
                        pltpu.VMEM((chunk, N), jnp.float32)],
        compiler_params=_params(asks),
        interpret=_interpreted(),
        name=SSD_FWD,
    )(x.reshape(B, T, H * P), _rows(acs, heads), _rows(dt, heads),
      Bm.reshape(B, T, G * N), Cm.reshape(B, T, G * N))
    return y.reshape(x.shape), entering


def _backward(x, dt, acs, Bm, Cm, entering, dy, chunk):
    """-> (dx, d dt, d acs, dBm, dCm) in the inputs' shapes and dtypes."""
    B, T, H, P, G, N, heads, asks = _shapes(x, Bm, chunk)
    n, blocks = T // chunk, H // heads
    spec = _specs(H, P, G, N, chunk, heads, lambda c: n - 1 - c)
    row = jax.ShapeDtypeStruct((B, blocks, heads, T), jnp.float32)
    bc = jax.ShapeDtypeStruct((B, T, G * N), jnp.float32)
    square = pltpu.VMEM((chunk, chunk), jnp.float32)
    flat = pltpu.VMEM((N, chunk), jnp.float32)
    dx, da, ddt, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, P=P,
                          per_group=H // G // heads),
        grid=(B, n, blocks),
        in_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                  spec["bc"], spec["state"], spec["x"]],
        out_specs=[spec["x"], spec["row"], spec["row"], spec["bc"],
                   spec["bc"]],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * P), x.dtype), row, row,
                   bc, bc],
        scratch_shapes=[pltpu.VMEM((blocks, N, heads * P), jnp.float32),
                        square, flat, flat, square, flat, flat,
                        pltpu.VMEM((chunk, heads), jnp.float32)],
        compiler_params=_params(asks),
        interpret=_interpreted(),
        name=SSD_BWD,
    )(x.reshape(B, T, H * P), _rows(acs, heads), _rows(dt, heads),
      Bm.reshape(B, T, G * N), Cm.reshape(B, T, G * N), entering,
      dy.reshape(B, T, H * P))
    return (dx.reshape(x.shape), _from_rows(ddt), _from_rows(da),
            dB.reshape(Bm.shape).astype(Bm.dtype),
            dC.reshape(Cm.shape).astype(Cm.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd(x, dt, acs, Bm, Cm, chunk):
    """`transformer._ssd` of a call `takes` admits, with the cumulative
    log-decay handed in: x (B, T, H, P), dt and acs (B, T, H) float32 (acs =
    `_ssm_log_decay`: dt A cumulated over each chunk's positions), Bm / Cm
    (B, T, G, N) -> y (B, T, H, P) float32, without the D skip."""
    return _forward(x, dt, acs, Bm, Cm, chunk)[0]


def _ssd_fwd(x, dt, acs, Bm, Cm, chunk):
    y, entering = _forward(x, dt, acs, Bm, Cm, chunk)
    return y, (x, dt, acs, Bm, Cm, entering)


def _ssd_bwd(chunk, residuals, dy):
    return _backward(*residuals, dy, chunk)


ssd.defvjp(_ssd_fwd, _ssd_bwd)
