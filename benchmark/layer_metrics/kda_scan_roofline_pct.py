"""Kernels: the gated delta rule's share of its roofline: the least time the
chip could take for every kda layer's scan of a step, forward and backward
(the larger of `kda.scan_required_flops` over the published peak FLOP/s and
`kda.scan_required_bytes` over the published peak bytes/s of this
`device_kind`, from the scan's SHAPES, whatever implements it), over the
device time measured under `hetu_kda_scan` (the solve's included),
recomputation included in the time and not in the requirement. Traced run
only."""
from benchmark.reduce import kda


def read(run):
    ms = kda.scope_ms(run, kda.SCAN, kda.SOLVE)
    if not ms:
        return None
    cell = run["cell"]
    return kda.scan_roofline_pct(ms, cell.config, cell.traffic,
                                 run["device"]["kind"])
