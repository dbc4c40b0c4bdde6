"""Fused optimizer step kernels: one VMEM pass over (grad, m, v, param)
(docs/KERNELS.md).

The reference applies sparse/dense updates with hand-fused CUDA kernels
(``src/ops/Optimizers.cu`` / ``OptimizersSparse.cu``); under XLA the
update rule is a chain of elementwise HLOs that the fusion pass USUALLY
melts into the gradient epilogue — but for the large-parameter ZeRO-ish
step the measured behavior (hetuprof roofline: optimizer families sit on
the HBM roof) is several full passes over param-sized tensors. The Adam
kernel here reads grad + m + v + param once each and writes the three
outputs in the same pass — arithmetic intensity goes from ~1 flop/byte
per HLO to the full rule per element loaded.

Numerical contract: the kernel body is the SAME expression sequence as
``Optimizer.apply_dense`` (bias-corrected Adam, SGD with fused l2), so
off/auto/force agree to f32 rounding; the equality tests pin it.

Layout: parameters arrive in their natural shapes; the kernel views them
as lane-shaped ``(rows, 128)`` arrays, zero-padded up to the 8x128 f32
tile and sliced back — elementwise kernels can always be tiled by
padding, so only dtype (f32 master precision) disqualifies a call, and
the whole parameter set of a real model (odd biases included) rides the
fused pass. The call is a 1-D grid over ``_BLOCK_ROWS``-row blocks, so a
tensor of any size streams through a bounded VMEM footprint (the last
block may be partial: Pallas pads its reads and drops its out-of-range
writes, which an elementwise rule tolerates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

# the kernels' names in the device trace (the pallas_call's `name=`) and in
# the registry: one constant a call site (docs/KERNELS.md)
FUSED_ADAM = "fused_adam"
FUSED_SGD = "fused_sgd"

_LANE = registry.LANE
_SUBLANE = registry.SUBLANE
_TILE = _LANE * _SUBLANE
# rows per grid step: one (1024, 128) f32 block is 512 KiB, and Adam's
# seven operands (p, g, m, v in; p, m, v out), double-buffered by the
# pipeline, hold 7 MiB — inside the registry's shared VMEM budget
_BLOCK_ROWS = 1024
assert 7 * 2 * _BLOCK_ROWS * _LANE * 4 <= registry.VMEM_BUDGET_BYTES


def _row_grid(rows):
    """(grid, vector BlockSpec) of the row-block sweep over a
    ``(rows, 128)`` lane view."""
    block = min(rows, _BLOCK_ROWS)
    return ((pl.cdiv(rows, block),),
            pl.BlockSpec((block, _LANE), lambda i: (i, 0)))


_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)
_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _lane_view(x):
    """Flat lane-shaped view, zero-padded up to the 8x128 f32 tile —
    elementwise kernels can always be tiled by padding (the pad rows are
    computed and sliced away; XLA fuses the pad/slice into the call's
    edges), unlike the gather/matmul kernels whose alignment is load-
    bearing. Returns (view, n_elements)."""
    n = x.size
    pad = (-n) % _TILE
    flat = x.reshape(-1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANE), n


def _unview(view, n, shape):
    return view.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# Adam (bias-corrected; optional decoupled weight decay)
# ---------------------------------------------------------------------------

def _adam_xla(param, grad, m, v, t, lr, *, beta1, beta2, eps, weight_decay):
    """The Optimizer.apply_dense expression sequence, verbatim."""
    t = t + 1.0
    m = beta1 * m + (1.0 - beta1) * grad
    v = beta2 * v + (1.0 - beta2) * grad * grad
    m_hat = m / (1.0 - beta1 ** t)
    v_hat = v / (1.0 - beta2 ** t)
    new_param = param - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    if weight_decay > 0:
        new_param = new_param - lr * weight_decay * param
    return new_param, m, v, t


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                 po_ref, mo_ref, vo_ref, *, beta1, beta2, eps, weight_decay):
    # sc_ref: (lr, 1 - beta1**t, 1 - beta2**t). The bias corrections are
    # formed outside: Mosaic cannot legalize a scalar float power
    # (math.powf), and XLA computes the same two scalars either way.
    lr, c1, c2 = sc_ref[0, 0], sc_ref[0, 1], sc_ref[0, 2]
    g = g_ref[:]
    p = p_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    m_hat = m / c1
    v_hat = v / c2
    new_p = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    if weight_decay > 0:
        new_p = new_p - lr * weight_decay * p
    po_ref[:] = new_p
    mo_ref[:] = m
    vo_ref[:] = v


def _adam_pallas(param, grad, m, v, t, lr, *, beta1, beta2, eps,
                 weight_decay):
    shape = param.shape
    (pv, n), (gv, _), (mv, _), (vv, _) = (
        _lane_view(x) for x in (param, grad, m, v))
    t1 = jnp.asarray(t, jnp.float32) + 1.0
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32), 1.0 - beta1 ** t1,
                         1.0 - beta2 ** t1]).reshape(1, 3)
    grid, vec = _row_grid(pv.shape[0])
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          weight_decay=weight_decay),
        grid=grid,
        in_specs=[vec, vec, vec, vec, _SCALAR],
        out_specs=[vec, vec, vec],
        out_shape=[jax.ShapeDtypeStruct(pv.shape, jnp.float32)] * 3,
        compiler_params=_PARALLEL,
        interpret=not registry._on_tpu(),
        name=FUSED_ADAM,
    )(pv, gv, mv, vv, scalars)
    return (_unview(new_p, n, shape), _unview(new_m, n, shape),
            _unview(new_v, n, shape), t1)


def _sized_f32(name, x):
    """Elementwise kernels pad to the tile internally, so alignment is
    never disqualifying — only dtype (f32 master precision) and emptiness
    are."""
    if jnp.dtype(x.dtype) != jnp.dtype(jnp.float32):
        return False, f"{name} must be f32 (master precision), got {x.dtype}"
    n = 1
    for s in x.shape:
        n *= int(s)
    if n == 0:
        return False, f"{name} is empty"
    return True, None


def _adam_eligible(param, grad, m, v, t, lr, **_kw):
    for name, x in (("param", param), ("grad", grad), ("m", m), ("v", v)):
        ok, why = _sized_f32(name, x)
        if not ok:
            return ok, why
    return True, None


registry.register_kernel(
    FUSED_ADAM,
    pallas_fn=_adam_pallas,
    xla_fallback=_adam_xla,
    eligibility=_adam_eligible,
)


# ---------------------------------------------------------------------------
# SGD (l2 folded into the same pass)
# ---------------------------------------------------------------------------

def _sgd_xla(param, grad, lr, *, l2reg):
    if l2reg > 0:
        grad = grad + l2reg * param
    return param - lr * grad


def _sgd_kernel(p_ref, g_ref, lr_ref, o_ref, *, l2reg):
    g = g_ref[:]
    p = p_ref[:]
    if l2reg > 0:
        g = g + l2reg * p
    o_ref[:] = p - lr_ref[0, 0] * g


def _sgd_pallas(param, grad, lr, *, l2reg):
    shape = param.shape
    pv, n = _lane_view(param)
    gv, _ = _lane_view(grad)
    lr_in = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    grid, vec = _row_grid(pv.shape[0])
    out = pl.pallas_call(
        functools.partial(_sgd_kernel, l2reg=l2reg),
        grid=grid,
        in_specs=[vec, vec, _SCALAR],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct(pv.shape, jnp.float32),
        compiler_params=_PARALLEL,
        interpret=not registry._on_tpu(),
        name=FUSED_SGD,
    )(pv, gv, lr_in)
    return _unview(out, n, shape)


def _sgd_eligible(param, grad, lr, **_kw):
    for name, x in (("param", param), ("grad", grad)):
        ok, why = _sized_f32(name, x)
        if not ok:
            return ok, why
    return True, None


registry.register_kernel(
    FUSED_SGD,
    pallas_fn=_sgd_pallas,
    xla_fallback=_sgd_xla,
    eligibility=_sgd_eligible,
)


# ---------------------------------------------------------------------------
# optimizer.py entry points
# ---------------------------------------------------------------------------

def adam_step(opt, param, grad, slot, lr):
    """Registry-dispatched Adam apply for one parameter. ``opt`` is the
    AdamOptimizer (hyperparameters are trace-time constants)."""
    new_p, m, v, t = registry.dispatch(
        "fused_adam", param, grad, slot["m"], slot["v"], slot["t"], lr,
        beta1=opt.beta1, beta2=opt.beta2, eps=opt.epsilon,
        weight_decay=opt.weight_decay)
    return new_p, {"m": m, "v": v, "t": t}


def sgd_step(opt, param, grad, lr):
    return registry.dispatch("fused_sgd", param, grad, lr, l2reg=opt.l2reg)
