"""Entry points: compile requests jax reported inside the measured window
(harness/device.py:CompileCounter). Must read 0: every shape is warmed in
set-up, and the Executor's second compile falls in the warm-up."""


def read(run):
    return run["compiles_in_window"]
