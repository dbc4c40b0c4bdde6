"""Flagship step: model FLOP/s utilization. Items per second of the window
times the operations one item requires (the adapter takes them from
benchmark/reduce/flops.py; recomputation does not count) over chips x the
published peak of this `device_kind`."""
from benchmark.reduce import peaks


def read(run):
    per_item = run["counters"].get("flops_per_item")
    if not per_item or not run["items_per_s"]:
        return None
    return 100.0 * peaks.utilization(run["items_per_s"], per_item,
                                     run["chips"], run["device"]["kind"])
