"""Peaks of each chip, and the work each scheduler must do, from shapes.

``PEAKS`` is keyed by ``device_kind`` as JAX reports it; a kind that is
not in the table is an error, never a default.

The work of a scheduler call is counted from the frame's *real* rows
(requests, padding excluded) and the dtypes of what it reads
and writes, not from what any implementation happens to compile: so the
XLA loop and the Pallas kernel are held to one count, and padding that a
program drags along shows as a lower share of the roofline.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bytes_per_s=819e9,
                        source="Google Cloud documentation, TPU v5e"),
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}") from None


def _size(dtypes: Mapping[str, object], name: str) -> int:
    return np.dtype(dtypes[name]).itemsize


#: leaves GUS reads per (request, server, variant) cell, per request, per frame
GUS_CELL = ("acc", "ctime", "v", "u", "avail")
GUS_ROW = ("cover", "A", "C", "w_a", "w_c")
GUS_FRAME = ("gamma", "eta")


def gus_work(dtypes: Mapping[str, object], n_rows: int, n_frames: int, M: int,
             L: int) -> dict:
    """Bytes and operations of dense GUS over ``n_rows`` real requests in
    ``n_frames`` frames: every candidate cell read once, the utility (two
    subtractions, two divisions, two products, one sum) evaluated once per
    cell, and the two int32 assignments written per request."""
    cell = sum(_size(dtypes, k) for k in GUS_CELL)
    row = sum(_size(dtypes, k) for k in GUS_ROW) + 2 * 4
    frame = sum(M * _size(dtypes, k) for k in GUS_FRAME) + 2 * 4
    return dict(bytes=n_rows * (M * L * cell + row) + n_frames * frame,
                flops=7 * n_rows * M * L)


def share_pct(work: dict, seconds: float, kind: str):
    """Least time the chip could take for ``work`` over ``seconds`` of
    device time, in percent; ``None`` where nothing was measured."""
    if not seconds or seconds <= 0 or not work or not work.get("bytes"):
        return None
    p = peaks(kind)
    least = max(work["bytes"] / p["hbm_bytes_per_s"], work["flops"] / p["flops"])
    return 100.0 * least / seconds


def roofline_share(ctx: dict, work_key: str, program: str):
    """A scheduler kernel's share of its roofline in a traced run: the least
    time the chip could take for ``ctx[work_key]`` over the device time of
    the programs whose names match ``program``; ``None`` where either is
    missing."""
    from bench.trace_reduce import program_seconds

    tr = ctx.get("trace")
    if not tr or not ctx.get(work_key):
        return None
    return share_pct(ctx[work_key], program_seconds(tr, program), ctx["kind"])
