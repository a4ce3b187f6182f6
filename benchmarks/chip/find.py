"""Find a piece of the benchmark by name.

Drivers, graph generators and metric readers each live in a file of
their own, ``<kind>/<name>.py`` under the benchmark's directory
(``drivers/serve.py``, ``generators/delaunay.py``,
``metrics/request_p95_ms.py``).  A new one is a new file: nothing lists
them.
"""

from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def module(kind: str, name: str, here: str = HERE):
    """The module ``<here>/<kind>/<name>.py``, loaded under a name of its
    own so that a file such as ``drivers/select.py`` cannot stand in for
    a module of the same name elsewhere."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(here, kind))
                       if f.endswith(".py"))
        raise ValueError(f"no {kind} file for {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", f"{kind}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
