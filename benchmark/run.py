"""The benchmark's one command (BENCHMARK.json `command`):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, started from the root of the checkout. It fails, with
no result line, when jax finds no TPU or fewer chips than the cell asks
for; otherwise the last line of stdout is the contract's JSON object.
Everything a cell needs is found by name from BENCHMARK.json (see
harness/manifest.py): a later PR adds files and entries and edits nothing.
"""
import time

_T0 = time.perf_counter()   # set-up is counted from here

import os   # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.harness.main import main
    sys.exit(main(sys.argv[1:], root=ROOT, t0=_T0))
