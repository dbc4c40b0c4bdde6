"""Kernels: the gated delta rule's share of its roofline with a decay a
head: the least time the chip could take for every gdn layer's scan of a
step, forward and backward (the larger of `gdn.scan_required_flops` over the
published peak FLOP/s and `gdn.scan_required_bytes` over the published peak
bytes/s of this `device_kind`, from the scan's SHAPES, whatever implements
it), over the device time measured under `hetu_gdn_scan` (the solve's
included), recomputation included in the time and not in the requirement.
Traced run only."""
from benchmark.reduce import gdn


def read(run):
    ms = gdn.scope_ms(run, gdn.SCAN)
    if not ms:
        return None
    cell = run["cell"]
    return gdn.scan_roofline_pct(ms, cell.config, cell.traffic,
                                 run["device"]["kind"])
