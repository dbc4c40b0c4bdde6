"""Kernels: the chunked scan's share of its roofline: the least time the
chip could take for every mamba layer's scan of a step, forward and
backward (the larger of `ssd_required_flops` over the published peak FLOP/s
and `ssd_required_bytes` over the published peak bytes/s of this
`device_kind`; at Granite's shapes the bytes bound: 0.51 ms against 0.40 a
layer), over the device time measured under `hetu_ssm_scan`, recomputation
included in the time and not in the requirement. Traced run only."""
from benchmark.reduce import ssm


def read(run):
    ms = ssm.scope_ms(run, ssm.SCAN)
    if not ms:
        return None
    cell = run["cell"]
    return ssm.scan_roofline_pct(ms, cell.config, cell.traffic,
                                 run["device"]["kind"])
