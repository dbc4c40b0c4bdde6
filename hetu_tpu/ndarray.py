"""Device contexts and array handles for the TPU-native framework.

Capability parity with the reference's ``python/hetu/ndarray.py`` (DLContext
:10, NDArray :132, IndexedSlices :482), redesigned for JAX: an ``NDArray`` is a
thin, duck-typed wrapper over a ``jax.Array`` — allocation, layout, strides,
copies and streams are all owned by XLA, so none of the reference's manual
memory machinery (lazy strided views, memory planning) is reimplemented here.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


class DLContext:
    """A device placement tag: ``cpu(0)``, ``tpu(3)``, ``rtpu('host2', 1)``.

    Mirrors the reference DLContext (ndarray.py:10) including the remote
    (hostname-qualified) form used by DeviceGroup strings. ``gpu`` is accepted
    as an alias for ``tpu`` so reference scripts run unchanged.
    """

    __slots__ = ("device_type", "device_id", "hostname")

    def __init__(self, device_type: str, device_id: int = 0, hostname: str = "localhost"):
        if device_type == "gpu":  # compat alias: reference scripts say gpu
            device_type = "tpu"
        assert device_type in ("cpu", "tpu"), device_type
        self.device_type = device_type
        self.device_id = int(device_id)
        self.hostname = hostname

    # -- resolution to a physical jax device -------------------------------
    def jax_device(self):
        """Resolve to a local jax.Device; an id the backend does not have
        raises instead of wrapping onto another chip."""
        devs = (jax.devices("cpu") if self.device_type == "cpu"
                else tpu_devices())
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r} requested but only {len(devs)} "
                f"{devs[0].platform} device(s) exist")
        return devs[self.device_id]

    @property
    def local(self) -> bool:
        return self.hostname in ("localhost", "127.0.0.1")

    def relocalize(self):
        self.hostname = "localhost"

    def __eq__(self, other):
        return (
            isinstance(other, DLContext)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
            and self.hostname == other.hostname
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id, self.hostname))

    def __repr__(self):
        if self.local:
            return f"{self.device_type}({self.device_id})"
        return f"{self.hostname}:{self.device_type}({self.device_id})"


def tpu_devices():
    """The devices a ``tpu`` context resolves onto: the chips of the TPU
    backend. With no TPU this raises (the reference hard-fails without
    CUDA the same way) — except in a process pinned to the CPU on purpose
    (``utils.cpu_pinned``: the test suite), where the
    virtual CPU devices stand in so the same script runs unchanged."""
    from .utils import cpu_pinned
    if cpu_pinned():
        return jax.devices("cpu")
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"a tpu device was requested but the jax backend is "
            f"{jax.default_backend()!r}; set JAX_PLATFORMS=cpu to run on "
            "the CPU on purpose")
    return jax.devices()


def cpu(dev_id: int = 0) -> DLContext:
    return DLContext("cpu", dev_id)


def tpu(dev_id: int = 0) -> DLContext:
    return DLContext("tpu", dev_id)


# The reference exposes gpu()/rgpu(); on the TPU build these are aliases.
def gpu(dev_id: int = 0) -> DLContext:
    return DLContext("tpu", dev_id)


def rcpu(hostname: str, dev_id: int = 0) -> DLContext:
    return DLContext("cpu", dev_id, hostname=hostname)


def rtpu(hostname: str, dev_id: int = 0) -> DLContext:
    return DLContext("tpu", dev_id, hostname=hostname)


rgpu = rtpu


def is_gpu_ctx(ctx) -> bool:
    """Compat shim (reference ndarray.py:106): true when ctx is an accelerator."""
    return isinstance(ctx, DLContext) and ctx.device_type == "tpu"


def is_tpu_ctx(ctx) -> bool:
    return is_gpu_ctx(ctx)


class NDArray:
    """Thin handle over a ``jax.Array`` with the reference's surface.

    Reference parity: ndarray.py:132 (asnumpy :2xx, copyto, shape/dtype).
    There is no manual alloc/free — XLA owns memory.
    """

    __slots__ = ("handle", "ctx")

    def __init__(self, handle, ctx: DLContext | None = None):
        self.handle = handle
        self.ctx = ctx

    @property
    def shape(self):
        return tuple(self.handle.shape)

    @property
    def dtype(self):
        return np.dtype(self.handle.dtype)

    def asnumpy(self) -> np.ndarray:
        return np.asarray(self.handle)

    def copyto(self, target):
        if isinstance(target, DLContext):
            return array(self.handle, ctx=target)
        if isinstance(target, NDArray):
            target.handle = jax.device_put(self.handle, target.handle.sharding)
            return target
        raise ValueError(f"Unsupported target {target!r}")

    def __array__(self, dtype=None):
        arr = np.asarray(self.handle)
        return arr.astype(dtype) if dtype is not None else arr

    def __repr__(self):
        return f"NDArray(shape={self.shape}, dtype={self.dtype}, ctx={self.ctx})"


def array(arr, ctx: DLContext | None = None, dtype=None) -> NDArray:
    """Create an NDArray on ``ctx`` (reference ndarray.py:419 ``array``)."""
    if isinstance(arr, NDArray):
        arr = arr.handle
    if dtype is None and not hasattr(arr, "dtype"):
        dtype = np.float32
    if dtype is None and np.issubdtype(np.asarray(arr).dtype, np.floating):
        dtype = np.float32
    np_arr = np.asarray(arr, dtype=dtype)
    dev = ctx.jax_device() if ctx is not None else None
    handle = jax.device_put(np_arr, dev)
    return NDArray(handle, ctx)


def empty(shape, ctx: DLContext | None = None, dtype=np.float32) -> NDArray:
    """Allocate an uninitialized-contents array (zeros under XLA)."""
    dev = ctx.jax_device() if ctx is not None else None
    handle = jax.device_put(jnp.zeros(shape, dtype=dtype), dev)
    return NDArray(handle, ctx)


class ND_Sparse_Array:
    """CSR sparse matrix handle (reference ndarray.py:411 ``ND_Sparse_Array``).

    Stored as (data, indices, indptr) jax arrays; consumed by csrmv/csrmm ops.
    """

    __slots__ = ("data", "row", "col", "nrow", "ncol", "ctx")

    def __init__(self, data, row, col, nrow, ncol, ctx=None):
        self.data = data
        self.row = row
        self.col = col
        self.nrow = nrow
        self.ncol = ncol
        self.ctx = ctx

    @property
    def shape(self):
        return (self.nrow, self.ncol)


def sparse_array(values, indices, shape, ctx=None) -> ND_Sparse_Array:
    """Build a CSR array from COO-style (values, (row, col)) like the reference
    (ndarray.py:452)."""
    row, col = indices
    dev = ctx.jax_device() if ctx is not None else None
    put = lambda a, dt: jax.device_put(np.asarray(a, dtype=dt), dev)
    return ND_Sparse_Array(
        put(values, np.float32), put(row, np.int32), put(col, np.int32),
        int(shape[0]), int(shape[1]), ctx,
    )


@jax.tree_util.register_pytree_node_class
class SparseValue:
    """Traced CSR/COO value: (data, row, col) arrays + static (nrow, ncol).

    Registered as a pytree so it can cross the jit boundary with the matrix
    dims as static aux data (segment_sum needs a static segment count).
    Iterable as a 5-tuple for ergonomic unpacking in op bodies.
    """

    def __init__(self, data, row, col, nrow, ncol):
        self.data, self.row, self.col = data, row, col
        self.nrow, self.ncol = int(nrow), int(ncol)

    def tree_flatten(self):
        return (self.data, self.row, self.col), (self.nrow, self.ncol)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __iter__(self):
        return iter((self.data, self.row, self.col, self.nrow, self.ncol))


class IndexedSlices:
    """Sparse gradient as (indices, values) pair (reference ndarray.py:482).

    ``deduplicate`` sums duplicate rows — on TPU this is a segment-sum, which
    XLA lowers to an efficient sorted scatter.
    """

    __slots__ = ("indices", "values", "dense_shape")

    def __init__(self, indices=None, values=None, dense_shape=None):
        self.indices = indices
        self.values = values
        self.dense_shape = dense_shape

    def to_dense(self):
        out = jnp.zeros(self.dense_shape, dtype=self.values.dtype)
        flat_idx = self.indices.reshape(-1)
        flat_val = self.values.reshape((-1,) + tuple(self.dense_shape[1:]))
        return out.at[flat_idx].add(flat_val)

    def deduplicate(self):
        flat_idx = np.asarray(self.indices).reshape(-1)
        flat_val = np.asarray(self.values).reshape((flat_idx.shape[0], -1))
        uniq, inverse = np.unique(flat_idx, return_inverse=True)
        summed = np.zeros((uniq.shape[0], flat_val.shape[1]), dtype=flat_val.dtype)
        np.add.at(summed, inverse, flat_val)
        return IndexedSlices(jnp.asarray(uniq), jnp.asarray(summed), self.dense_shape)

    cpu_deduplicate = deduplicate
