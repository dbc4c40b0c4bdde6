"""`benchmark/tests/test_lfm2.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("lfm2", globals())
