"""Multi-host distributed backend — scale-out across processes/hosts.

The reference bootstraps its multi-node world with MPI (rank discovery by
hostname hashing, ``communicator/mpi_nccl_comm.py:114-134``), builds NCCL
communicators over it, and launches ranks with ``mpirun``
(``python/runner.py:204``). The TPU-native equivalent is JAX's coordination
service: one process per host joins via ``jax.distributed`` (gRPC over DCN),
after which ``jax.devices()`` is the GLOBAL device list and one
``jax.sharding.Mesh`` spans every chip in the job — GSPMD collectives ride
ICI inside a slice and DCN across slices, no hand-written communicator layer.

``heturun`` (hetu_tpu/runner.py) exports ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` to each remote worker;
``initialize()`` consumes them. On real TPU pods the three values are
auto-detected from the pod metadata and may all be omitted.

Off-TPU (CI, the virtual-mesh tests), the same path runs with multiple CPU
processes: each process provisions ``local_device_count`` virtual CPU
devices and cross-process collectives go through Gloo. This mirrors the
reference's local-process-cluster test strategy (SURVEY.md §4) at the
multi-HOST level.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_initialized = False


def is_initialized() -> bool:
    return _initialized


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_count: Optional[int] = None,
               auto_detect: bool = False) -> bool:
    """Join (or create) the multi-process JAX world. Idempotent.

    Args fall back to the env vars exported by ``heturun``
    (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``);
    with none present and no args this is a single-process no-op (returns
    False) so scripts can call it unconditionally. On a real TPU pod slice
    pass ``auto_detect=True`` (or set ``HETU_MULTIHOST=auto``): the three
    values then come from the pod metadata via no-arg
    ``jax.distributed.initialize()``.

    ``local_device_count``: CI/testing mode — FORCES a virtual-CPU Gloo
    world with this many devices per process (the multi-host analogue of the
    test suite's virtual 8-device mesh). Never pass it on real TPUs; it is
    mutually exclusive with ``auto_detect``.
    """
    global _initialized
    if _initialized:
        return True
    auto_detect = auto_detect or os.environ.get("HETU_MULTIHOST") == "auto"
    if auto_detect and local_device_count is not None:
        raise ValueError(
            "local_device_count forces a virtual-CPU world and cannot be "
            "combined with auto_detect (TPU pod metadata)")
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None and not auto_detect:
        if local_device_count is not None:
            raise ValueError(
                "local_device_count was given but no coordinator/world was "
                "specified (args, JAX_* env, or auto_detect) — for a "
                "single-process virtual mesh use hetu_tpu.utils."
                "ensure_devices instead")
        return False

    if local_device_count is not None:
        # must happen before the backend initializes (initialize() is the
        # process's first jax touch): the virtual-CPU world is the CPU pin
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(local_device_count))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    return True


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def global_mesh(dp: int = 0, pp: int = 1, tp: int = 1, sp: int = 1,
                ep: int = 1) -> Mesh:
    """A mesh over EVERY device in the job (all processes). ``dp=0`` means
    "fill dp with whatever remains after the model axes" — the common case
    where adding hosts grows the data-parallel degree."""
    from .mesh import auto_mesh, make_mesh
    if dp == 0:
        return auto_mesh(tp=tp, pp=pp, sp=sp, ep=ep)
    return make_mesh(dp=dp, pp=pp, tp=tp, sp=sp, ep=ep, devices=jax.devices())


def host_local_batch(mesh: Mesh, spec: P, host_data: np.ndarray):
    """Assemble a GLOBAL array from this process's local shard of the batch.

    Each process feeds only the rows its own devices will hold (the
    reference's dataloader rank-sharding, ``dataloader.py:19-24``, lifted to
    host granularity); no cross-host data movement happens here.
    """
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), np.asarray(host_data))


def barrier(name: str = "hetu_barrier",
            deadline_s: Optional[float] = None) -> None:
    """Block until every process arrives (reference: PS worker barrier /
    MPI_Barrier).

    ``deadline_s`` arms a one-shot hang watchdog around the wait: a barrier
    a dead peer will never reach dumps thread stacks and aborts with
    ``resilience.EXIT_WATCHDOG`` instead of hanging the job forever (the
    supervising launcher then restarts from the latest checkpoint)."""
    from jax.experimental import multihost_utils
    if deadline_s is None:
        multihost_utils.sync_global_devices(name)
        return
    from ..resilience import Watchdog
    with Watchdog(deadline_s) as wd:
        wd.beat(phase=f"barrier:{name}")
        multihost_utils.sync_global_devices(name)


def any_process_flag(flag) -> bool:
    """True iff ANY process passed a truthy flag — the coordinated-decision
    primitive for preemption (one host gets SIGTERM; every host must join
    the emergency checkpoint at the same step or the collective write
    deadlocks). Plain local bool outside a multi-process world."""
    if not _initialized or jax.process_count() <= 1:
        return bool(flag)
    flags = process_allgather(np.asarray(bool(flag), np.int32))
    return bool(np.max(flags) > 0)


def process_allgather(x):
    """Gather a host-local value from every process (returns stacked array on
    each host). Reference analogue: MPI allgather on the CPU world."""
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(x)


def broadcast_from_chief(x):
    """Replicate chief's (process 0's) host value to every process — e.g. a
    seed or a config blob decided at rank 0."""
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(x)


def fetch_replicated(garr) -> np.ndarray:
    """Bring a global array to the host as numpy, same shape whether this
    process holds every shard (single-process / fully-addressable) or not
    (multi-host, where the value is first replicated across processes)."""
    if garr.is_fully_addressable:
        return np.asarray(jax.device_get(garr))
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(garr, tiled=True))


def local_devices() -> Sequence:
    return jax.local_devices()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()
