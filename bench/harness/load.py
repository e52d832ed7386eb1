"""Load a benchmark file that is found by name (a runner, a reference, a
FLOP count, a metric reader) as a module."""

from __future__ import annotations

import importlib.util
import os
import sys


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
