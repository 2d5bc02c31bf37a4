"""Readers of the program's own spans and counters in a traced run.

A traced run's ``breakdown.idle_gaps`` (``bench/trace_reduce.py``) names
each device idle gap by the innermost host annotation active at its
midpoint and keeps the ``TOP`` names with most idle.  The program's spans
are such annotations only where it writes them into the profiler trace.
Whether it does is read from the trace itself: a list that names none of
the cell's program spans (``ONLINE_SPANS``, ``FLEET_SPANS``) comes from a
program that writes none, and its readings are ``None``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

#: ``reduce_trace``'s default ``top``: a list this long may be truncated
TOP = 10
#: the spans of ``paper.online``'s decision that can own a device gap
ONLINE_SPANS = ("gus/pad", "gus/call")
#: the spans of ``paper.fleet``'s dense fleet that can own a device gap
FLEET_SPANS = ("fleet/generate_traces", "fleet/arrivals", "fleet/grid_build",
               "fleet/dispatch", "fleet/put", "fleet/drain", "fleet/window_metrics")


def idle_s(ctx: Dict, names: Iterable[str], program: Iterable[str]) -> Optional[float]:
    """Device idle seconds under the spans ``names``, summed.

    ``None`` without gaps (no device in the trace), when no gap is named
    by any span in ``program`` (the program's spans are not annotations),
    and for a full list that lacks every name read (truncated: the names
    may sit below the cut).  Otherwise a name absent from the list owned
    no gap and reads 0."""
    gaps = dict(((ctx.get("trace") or {}).get("breakdown") or {}).get("idle_gaps") or ())
    names = tuple(names)
    if not any(n in gaps for n in program):
        return None
    if len(gaps) >= TOP and not any(n in gaps for n in names):
        return None
    return sum(gaps.get(n, 0.0) for n in names)


def per_kuser_ms(ctx: Dict, names: Iterable[str]) -> Optional[float]:
    """Idle milliseconds under the fleet spans ``names`` per 1000 users of
    the window."""
    s = idle_s(ctx, names, FLEET_SPANS)
    if s is None or not ctx.get("users"):
        return None
    return 1e3 * s / (ctx["users"] / 1e3)


def per_frame_ms(ctx: Dict, names: Iterable[str]) -> Optional[float]:
    """Idle milliseconds under the decision spans ``names`` per frame
    decided in the window."""
    s = idle_s(ctx, names, ONLINE_SPANS)
    if s is None or not ctx.get("frames"):
        return None
    return 1e3 * s / ctx["frames"]


def counter_total(ctx: Dict, *names: str) -> Optional[int]:
    """A program counter (``Stopwatch.count`` keys in each unit's
    ``timings``), summed over names and units; ``None`` when no unit has
    any of them."""
    t = ctx.get("timings") or ()
    if not any(n in d for d in t for n in names):
        return None
    return sum(int(d.get(n, 0)) for d in t for n in names)
