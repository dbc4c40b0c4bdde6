"""`benchmark/tests/test_kanana.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("kanana", globals())
