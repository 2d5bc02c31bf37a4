"""Shared pieces of the benchmark's arrival generators, NumPy only.

Each scenario the benchmark runs has a file of its own beside this one,
``bench/gen/<scenario>.py``, with a ``trace(seed, n_edge, n_services, sim,
params)`` function: a copy of how ``repro.core.scenarios`` draws that
scenario's requests, kept with the benchmark so
that the reference regenerates every request from the seed without the
program.  A trace is a dict of columns sorted by arrival: ``arrival_ms``,
``cover``, ``service``, ``A``, ``C``, ``size``.
"""
from __future__ import annotations

import numpy as np

COLS = ("arrival_ms", "cover", "service", "A", "C", "size")


def sorted_columns(parts):
    cat = {k: np.concatenate([p[k] for p in parts]) if parts else
           np.zeros(0, np.int64 if k in ("cover", "service") else np.float64)
           for k in COLS}
    order = np.argsort(cat["arrival_ms"], kind="stable")
    return {k: v[order] for k, v in cat.items()}


def buckets(tr: dict, frame_ms: float, n_frames: int):
    """Per-frame column slices: frame ``t`` holds ``[t, t+1) * frame_ms``,
    anything past the last boundary clamps into the final frame."""
    edges = np.searchsorted(tr["arrival_ms"], np.arange(1, n_frames) * frame_ms, side="left")
    bounds = np.concatenate([[0], edges, [tr["arrival_ms"].size]])
    return [{k: v[int(bounds[i]):int(bounds[i + 1])] for k, v in tr.items()}
            for i in range(n_frames)]
