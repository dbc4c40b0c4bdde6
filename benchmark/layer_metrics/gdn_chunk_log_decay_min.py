"""Flagship step: the most negative log-decay cumulated inside a chunk of 64
positions, over the heads and chunks of the FIRST gdn layer on the
correctness sample, from the program's own pure function beside the step
(`transformer.gdn_terms`, the routing-stats pass of the adapter's check): how
far past float32's 1 / exp(G) (-88) the stable chunked form is worked. None
where the adapter reports no such counter."""


def read(run):
    return (run["counters"].get("gdn") or {}).get("chunk_log_decay_min")
