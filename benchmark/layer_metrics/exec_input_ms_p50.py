"""Graph executor: median host time a `hetu_step` spends on its inputs:
`hetu.feed` (placeholders through `_prepare_input`) + `hetu.dl_wait`
(dataloader `get_batch`, resident cursors): the input wait inside
`Executor.run` (reduce/inside.py; traced run only)."""
from benchmark.reduce import inside


def read(run):
    return inside.host_value(run, "input_ms_p50")
