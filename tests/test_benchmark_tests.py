"""The tests of `benchmark/`, the stack that judges every PR, as part of
`pytest tests/`: a library change that breaks an adapter or renames a scope
a reader looks for fails here, not on the chip.

The modules are imported, not collected in place: `benchmark/tests/conftest.py`
pins four virtual devices, XLA takes the last such flag, and the 8-device
tests of the same worker would then fail. They run on this suite's eight."""
import glob
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
for _path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
    _mod = importlib.import_module(
        "benchmark.tests." + os.path.basename(_path)[:-3])
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_") or getfixturemarker(_obj) is not None:
            # two files defining one name would silently lose a test
            assert globals().setdefault(_name, _obj) is _obj, _name
