"""`benchmark/tests/test_kimi_linear.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("kimi_linear", globals())
