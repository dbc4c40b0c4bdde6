"""Entry points: seconds `import hetu_tpu` took, from the package's first line to
its last (the program's `hetu.import` record; jax came first in the harness)."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "import_s")
