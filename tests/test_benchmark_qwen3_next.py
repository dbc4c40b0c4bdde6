"""`benchmark/tests/test_qwen3_next.py` in tier-1: an xdist unit of its own."""
from benchmark_tests_loader import export
export("qwen3_next", globals())
