"""The benchmark's own files, found by name under ``bench/``.

Modules are loaded by path: their names hold dots and dashes
(``configs/qwen2-0.5b.ref.py``), and ``trace`` would otherwise be the
standard library's module.  Each is loaded once per process.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    """A JSON file under ``bench/``."""
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def module(*parts: str):
    """A Python file under ``bench/``, loaded once."""
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, *parts))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]
