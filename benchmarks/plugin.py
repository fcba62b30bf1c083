"""Code found by name: a metric's reader (``readers/``), a traffic
operation (``ops/``) and a key order (``orders/``) are each one small Python
file that a data file names. A later PR adds a file; nothing is a table in
the harness."""

from __future__ import annotations

import importlib.util
import os

_loaded: dict[str, object] = {}


def load(directory: str, name: str):
    """The module ``<directory>/<name>.py``."""
    path = os.path.join(directory, name + ".py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise ValueError(f"no {os.path.basename(directory)} named {name!r} "
                             f"({path})")
        spec = importlib.util.spec_from_file_location(
            f"{os.path.basename(directory)}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
