"""`benchmark/tests/test_smallthinker.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("smallthinker", globals())
