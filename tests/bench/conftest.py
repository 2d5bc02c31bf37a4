"""Shared fixtures of the benchmark's CPU tests.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) with every traffic mix cut to a size a test run can hold; the
program is imported from the repository's ``src``.  Nothing here touches a
TPU: the harness is driven with its chip check off.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: per traffic mix (or configuration): entries replaced at test size
TINY = {
    "bench/traffic/paper.fleet.json": {"n_rep": 4, "frames": 3},
    "bench/traffic/paper.online.json": {"pool": 6, "warmup": 2},
}


def _update(path: Path, changes: dict):
    d = json.loads(path.read_text())
    for k, v in changes.items():
        if isinstance(v, dict):
            d.setdefault(k, {}).update(v)
        else:
            d[k] = v
    path.write_text(json.dumps(d, indent=1))


def make_tiny_root(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, changes in TINY.items():
        _update(dst / rel, changes)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
