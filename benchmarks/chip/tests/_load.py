"""Import helpers for the benchmark's tests: the harness modules sit in
the directory above, and ``run.py`` is loaded under a name of its own so
that no other ``run`` module can stand in for it."""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def harness():
    mod = sys.modules.get("chipbench_run")
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            "chipbench_run", os.path.join(HERE, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chipbench_run"] = mod
        spec.loader.exec_module(mod)
    return mod
