"""Flagship step: the fullest expert's load over the mean load (1 is
perfectly even), the largest over the layers, from the program's own
`transformer.moe_routing_stats` on the correctness sample after the window:
the spread of the group sizes the grouped matmul is given."""


def read(run):
    stats = run["counters"].get("moe")
    return max(stats["max_over_mean"]) if stats else None
