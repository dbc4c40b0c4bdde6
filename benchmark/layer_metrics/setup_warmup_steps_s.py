"""Entry points: the warm-up's wall time (the first step span's start to the end
of the first read of the loss) less the compile log's spans inside it: the
device running `warmup_steps` steps, which moves with step time (traced run
only: the benchmark's spans are kept there)."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "warmup_steps_s")
