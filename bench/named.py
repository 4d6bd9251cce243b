"""The benchmark's pieces found by name: ``bench/<kind>/<name>.py``."""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (``kind`` "" for ``bench/``); a name
    may hold dots, as the metrics' do."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(filter(None, (kind, name.replace(".", "_")))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
