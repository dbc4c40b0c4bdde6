"""hetukern: the Pallas kernel tier (docs/KERNELS.md).

Layout:

- :mod:`registry` — the dispatch gate every kernel call goes through
  (``HetuConfig(kernels="off"|"auto"|"force")`` / ``HETU_KERNELS``,
  per-call eligibility, ``hetu_kernel_dispatch_total{kernel,path}``).
- :mod:`embed_grad` — fused sparse embedding gradient: sort/unique +
  segment-sum into IndexedSlices-style ``(rows, grads)``.
- :mod:`csr_spmm` — blocked rows-into-VMEM segment-MAC for the
  CSR/COO sparse products (csrmm/csrmv, DistGCN 1.5D).
- :mod:`quant_comm` — one-pass blockwise quantize/dequantize fused into
  the hetuq AllReduce legs (bit-identical wire payloads).
- :mod:`fused_opt` — multi-tensor Adam/SGD apply in one VMEM pass.
- :mod:`flash_attention` / :mod:`fused_ce` — the two pre-tier kernels
  (their ``should_fuse``-style gating predates the registry and is
  documented in docs/KERNELS.md).
- :mod:`rope` — the rotation of q and k in one pass through VMEM, latent
  attention's interleaved pairs and every other model's rotate-half
  columns, gated the pre-tier way (``rope.takes``).
- :mod:`ssd` / :mod:`kda` / :mod:`gdn` — the chunked scans of Mamba-2, of
  Kimi Delta Attention (the gated delta rule with a decay a channel) and of
  Gated DeltaNet (a decay a head) through VMEM, forward and backward, the
  state in scratch over the chunks, gated the same way (``ssd.takes``,
  ``kda.refusal``, ``gdn.refusal``).
- :mod:`grouped_matmul` — the experts' grouped matmul, forward, dx and dW,
  with tiles made from the widths, on a TPU in one program at every
  width (``ragged_dot`` under a mesh and off a TPU); registered here
  (``grouped_matmul``) and dispatched by ``transformer._grouped_matmul``.

Importing this package registers the four tier kernels; the graph ops
import it lazily inside their compute fns so jax-free tools never pay
for it.
"""
import sys as _sys
import time as _time
_IMPORT_T0 = _time.perf_counter()       # `hetu.import.kernels` starts here
_PALLAS_PRELOADED = "jax.experimental.pallas" in _sys.modules
from . import registry                            # noqa: F401
from .registry import (                           # noqa: F401
    KernelEligibilityError, KernelSpec, active, current_mode, dispatch,
    dispatch_stats, eligibility_of, fallback_ratio, register_kernel,
    registered_kernels, reset_stats, resolve_mode,
)
from . import embed_grad, csr_spmm, quant_comm, fused_opt  # noqa: F401
from ..telemetry import tracing as _tracing
_tracing.note_import(_tracing.IMPORT_KERNELS, _IMPORT_T0, _PALLAS_PRELOADED)
