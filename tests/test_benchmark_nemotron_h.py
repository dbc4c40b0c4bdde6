"""`benchmark/tests/test_nemotron_h.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("nemotron_h", globals())
