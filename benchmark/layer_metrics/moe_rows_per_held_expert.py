"""Flagship step: the rows (picks) a HELD expert takes a layer a step, the
mean over the held experts, the expert layers and the traced steps, from the
program's own counter (`transformer.move_router_bias` writes the picks each
expert took in a step beside the selection bias; the adapter keeps a copy a
traced step): the M of the grouped matmuls' (M, D) x (D, F) products. The
even share of nemotron-twotower-30b-a3b's one sequence is 8,192 x 6 / 128 =
384; the sixteen-chip deployment's is 6,144. None where the program counts
no picks."""
from benchmark.reduce import nemotron_h


def read(run):
    return nemotron_h.rows_per_held_expert(run)
