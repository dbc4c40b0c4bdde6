"""hetu_tpu — a TPU-native deep-learning framework with the capabilities of
Hetu (PKU DAIR Lab), built on JAX/XLA/Pallas/pjit.

Public surface mirrors the reference's ``python/hetu/__init__.py`` so model
code written against the reference imports unchanged:

    import hetu_tpu as ht
    x = ht.Variable(name='x', trainable=False)
    w = ht.init.random_normal((784, 10), stddev=0.1, name='w')
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y), [0])
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    executor = ht.Executor({'train': [loss, train_op]}, ctx=ht.tpu(0))
    executor.run('train', feed_dict={...})
"""
import sys as _sys
import time as _time
_IMPORT_T0 = _time.perf_counter()       # `hetu.import` starts here
_JAX_PRELOADED = "jax" in _sys.modules
from .graph.ops import *  # noqa: F401,F403 — the ~55-op registry
from .graph.node import Variable, placeholder_op, Op, find_topo_sort
from .graph.gradients import gradients
from .graph.executor import (
    Executor, HetuConfig, SubExecutor,
    wrapped_mpi_nccl_init, mpi_nccl_init, mpi_nccl_finish, new_group_comm,
    scheduler_init, scheduler_finish, server_init, server_finish,
    worker_init, worker_finish, get_worker_communicate,
)
from .context import context, get_current_context, DeviceGroup
from .dataloader import dataloader_op, Dataloader, DataloaderOp, GNNDataLoaderOp
from .ndarray import (
    cpu, gpu, tpu, rcpu, rgpu, rtpu, array, sparse_array, empty,
    is_gpu_ctx, is_tpu_ctx, NDArray, ND_Sparse_Array, IndexedSlices, DLContext,
)
from .cstable import CacheSparseTable
# re-bind the real PS package: `from .graph.ops import *` above leaks the
# graph-level ops.ps MODULE under the name `ps`, shadowing hetu_tpu.ps
from . import ps
from . import optimizer as optim
from . import resilience
from . import analysis
from . import lr_scheduler as lr
from . import initializers as init
from . import data
from . import metrics
from . import onnx
from . import graphboard
from . import telemetry
from . import tokenizers

__version__ = "0.1.0"
telemetry.tracing.note_import(telemetry.tracing.IMPORT, _IMPORT_T0,
                              _JAX_PRELOADED)
