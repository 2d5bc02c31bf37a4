"""Helpers the benchmark's drivers and the harness share."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path


def load_module(path: Path, name: str = None):
    """Import a Python file by path (names may hold ``-`` and ``.``)."""
    path = Path(path)
    mod_name = name or "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        "-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def study_seed(seed: int, i: int, n_rep: int) -> int:
    """Seed of study ``i`` of a run: replications draw ``seed + rep``, so
    studies are spaced ``n_rep`` apart and never share a replication."""
    return int(seed) * 65_536 + i * max(int(n_rep), 1)
