"""Kernels: the grouped matmuls' share of the chip's peak FLOP/s: the
operations the calls counted in the trace required (each call, forward or
either backward product, is 2 * picks * D * F: reduce/moe.py; a forward run
again under `remat` counted as run) over their device time x the published
peak of this `device_kind` (reduce/peaks.py). Compute-bound at OLMoE's
shapes (585 FLOP a byte against the chip's 240): the FLOP bound is the
larger one. Traced run only."""
from benchmark.reduce import moe, peaks


def read(run):
    r = moe.for_run(run)
    if not r or not r["grouped_matmul"]["ms_per_step"]:
        return None
    c, t = run["cell"].config, run["cell"].traffic
    picks = t["sequences"] * t["seq_len"] * c["num_experts_per_tok"]
    g = r["grouped_matmul"]
    flops = g["calls_per_step"] * moe.moe_expert_matmul_flops(
        picks, c["hidden_size"], c["intermediate_size"])
    peak = peaks.peaks(run["device"]["kind"])["tflops"] * 1e12
    return 100.0 * flops / (g["ms_per_step"] / 1e3) / peak
