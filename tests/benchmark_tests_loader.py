"""The tests of `benchmark/`, the stack that judges every PR, as part of
`pytest tests/`: a library change that breaks an adapter or renames a scope
a reader looks for fails here, not on the chip. `tests/test_benchmark_<name>
.py` re-exports `benchmark/tests/test_<name>.py`: ONE file a module, so that
xdist (`--dist loadfile`) spreads them.

The modules are imported, not collected in place: `benchmark/tests/conftest.py`
pins four virtual devices, XLA takes the last such flag, and the 8-device
tests of the same worker would then fail. They run on this suite's eight."""
import importlib
import os
import sys

import pytest
from _pytest.fixtures import getfixturemarker

HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")
# the files import each other's helpers by bare name too; appended, so that
# `conftest` stays this suite's
sys.path.append(HERE)
pytest.register_assert_rewrite("benchmark.tests")


def export(name, into):
    """The tests `benchmark/tests/test_<name>.py` DEFINES and the fixtures
    it uses (its own and those it imports), into the namespace `into`."""
    mod = importlib.import_module("benchmark.tests.test_" + name)
    for key, obj in vars(mod).items():
        if getfixturemarker(obj) is not None or (
                key.startswith("test_") and obj.__module__ == mod.__name__):
            into[key] = obj
