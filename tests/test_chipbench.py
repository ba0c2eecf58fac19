"""The benchmark's own tests (``chipbench/tests``), counted by tier-1.

They are the yardstick's tests and live beside it; this file only loads
them, so that a change to the harness, a metric's spec or a reducer is
held to them by the same run that holds the program to its tests.
"""

import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "tests")

for _file in sorted(os.listdir(_DIR)):
    if _file.startswith("test_") and _file.endswith(".py"):
        _spec = importlib.util.spec_from_file_location(
            "chipbench_tests_" + _file[:-3], os.path.join(_DIR, _file))
        _mod = importlib.util.module_from_spec(_spec)
        _spec.loader.exec_module(_mod)
        # the tests and the fixtures they name
        globals().update({k: v for k, v in vars(_mod).items()
                          if not k.startswith("_")})
