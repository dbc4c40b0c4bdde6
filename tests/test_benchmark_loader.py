"""`benchmark_tests_loader`'s own checks, over ALL files of `benchmark/tests/`
without importing one: every file has the module that brings it into tier-1,
and no test name is defined twice."""
import ast
import collections
import glob
import os

from benchmark_tests_loader import HERE

FILES = sorted(glob.glob(os.path.join(HERE, "test_*.py")))


def test_every_file_of_benchmark_tests_has_its_loader_module():
    """A `model_config` PR that adds `benchmark/tests/test_<name>.py` adds
    `tests/test_benchmark_<name>.py` too, or its tests never run."""
    tests = os.path.dirname(os.path.abspath(__file__))
    for path in FILES:
        name = os.path.basename(path)[len("test_"):-len(".py")]
        loader = os.path.join(tests, f"test_benchmark_{name}.py")
        assert os.path.exists(loader), (
            f"{os.path.relpath(path, os.path.dirname(tests))} has no "
            f"tests/test_benchmark_{name}.py: three lines, "
            f'`export("{name}", globals())`')
        with open(loader) as f:
            assert f'export("{name}", globals())' in f.read(), loader
    assert len(FILES) >= 12


def test_no_test_name_is_defined_by_two_files():
    """One name, one test: a report names a test of the benchmark by its
    function alone, and a file that imports another's helpers cannot shadow
    a test by defining its name again."""
    where = collections.defaultdict(list)
    for path in FILES:
        with open(path) as f:
            for node in ast.parse(f.read()).body:
                if isinstance(node, ast.FunctionDef) and node.name.startswith(
                        "test_"):
                    where[node.name].append(os.path.basename(path))
    twice = {name: files for name, files in where.items() if len(files) > 1}
    assert not twice, twice
