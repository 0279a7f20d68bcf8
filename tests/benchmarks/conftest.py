"""A reducer that is a file (benchmarks/reducers/<name>.py, found by
reduce.reduce_metric when a metric names it) is also put into
reduce.REDUCERS for these tests: test_benchmark_harness.py checks every
metric file's reducer by looking it up there and nowhere else.
"""
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R            # noqa: E402

for _file in sorted(os.listdir(os.path.join(ROOT, "benchmarks", "reducers"))):
    if _file.endswith(".py"):
        R.REDUCERS.setdefault(_file[:-3], importlib.import_module(
            "benchmarks.reducers." + _file[:-3]).read)
