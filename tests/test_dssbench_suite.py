"""The benchmark's own tests, where tier-1 sees them.

`dssbench/tests/` sits outside `pytest tests/`, so the schema check,
the `stale` / `lowprec` controls and the trace readers' hand-worked
cases ran for nobody.  This module loads each of its files by path and
adopts its tests and fixtures, so `pytest tests/ -m 'not slow'`
collects them under this module's name with their marks (the three
that boot a server keep `slow`) and their module-scoped fixtures.  No
file under `dssbench/` changes; a test added there is collected here.
"""

from __future__ import annotations

import glob
import importlib.util
import os

_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "dssbench", "tests",
)


def _adopt(path: str) -> None:
    name = "dssbench_tests_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for key, val in vars(mod).items():
        if getattr(val, "__module__", None) != name or key.startswith("_"):
            continue  # what the file imported, and its private helpers
        if key in globals():
            raise ImportError(
                f"{path}: {key} is already defined by another file of "
                "dssbench/tests; one of the two would go uncollected"
            )
        globals()[key] = val


for _path in sorted(glob.glob(os.path.join(_DIR, "test_*.py"))):
    _adopt(_path)
