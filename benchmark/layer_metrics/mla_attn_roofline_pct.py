"""Kernels: the flash attention kernels' share of the chip's peak FLOP/s at
latent attention's TWO head widths, causal: the calls counted in the trace x
the operations each requires (reduce/mla.py: forward 2*B*H*P*(qk + v),
backward 2*B*H*P*(3*qk + 2*v), P = T(T+1)/2 causal pairs; a forward run again
under `remat` counted as run) over their device time x the published peak of
this `device_kind` (reduce/peaks.py). Compute-bound. Traced run only."""
from benchmark.reduce import mla


def read(run):
    r = mla.for_run(run)
    if not r:
        return None
    cell = run["cell"]
    return mla.attn_roofline_pct(r["flash"], cell.config, cell.traffic,
                                 run["device"]["kind"])
