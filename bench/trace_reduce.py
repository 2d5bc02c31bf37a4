"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* the traced window is the host annotation ``bench/window`` that the
  harness wraps around its measured loop;
* a device is a plane named ``/device:TPU:<n>``; its busy time is the union
  of the intervals of its operations (line ``XLA Ops``) inside the window,
  and its idle gaps are the rest of the window;
* a program's device time is the sum of its executions (line
  ``XLA Modules``), keyed by the module name without its ``(id)`` suffix;
* ``breakdown``: the device operations that took most time
  (``module:op``, the op named by its HLO instruction and placed in the
  program whose execution encloses it),
  and idle time by what the host was doing in it, named by the innermost
  host annotation of the program's or the benchmark's vocabulary active
  at each gap's midpoint, summed per name.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: host annotations that name what the host was doing
VOCAB = ("bench/", "fleet/", "gus/", "sim/")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(text: str) -> str:
    """``%while.17 = (...) while(...)`` -> ``while.17``: operation events
    carry their whole HLO instruction as the name."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _window(host_events) -> Tuple[float, float]:
    spans = [(s, e) for name, s, e in host_events if name == WINDOW]
    if not spans:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith("$"):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def reduce_trace(path_or_data, n_devices: Optional[int] = None, top: int = 10) -> Dict:
    """Device busy/idle, per-program device time and ``breakdown``.

    ``n_devices`` is the number of chips the run used: busy time is
    averaged over that many devices (a used chip with no operation counts
    as idle).  Times are seconds."""
    from jax.profiler import ProfileData

    pd = (ProfileData.from_file(str(path_or_data)) if not hasattr(path_or_data, "planes")
          else path_or_data)
    host = _host_events(pd)
    w0, w1 = _window(host)
    window_s = (w1 - w0) * 1e-9
    busy: Dict[int, float] = {}
    gaps: List[Tuple[float, float]] = []
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev:
            continue
        iv, mods = [], []
        lines = {line.name: line for line in plane.lines}
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else ()):
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                programs[_module_name(ev.name)] += (e - s) * 1e-9
                mods.append((ev.start_ns, ev.start_ns + ev.duration_ns, _module_name(ev.name)))
        mods.sort()
        mod_starts = [m[0] for m in mods]
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            iv.append((s, e))
            k = bisect.bisect_right(mod_starts, ev.start_ns) - 1
            mod = mods[k][2] if k >= 0 and ev.start_ns < mods[k][1] else "?"
            ops[f"{mod}:{_op_name(ev.name)}"] += (e - s) * 1e-9
        u = _union(np.asarray(iv, np.float64).reshape(-1, 2))
        busy[int(dev.group(1))] = float((u[:, 1] - u[:, 0]).sum()) * 1e-9 if u.size else 0.0
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        gaps.extend((float(a), float(b)) for a, b in edges if b > a)
    n = n_devices or max(len(busy), 1)
    busy_s = sum(busy.values()) / n
    idle = defaultdict(float)
    vocab = sorted((s, e, nm) for nm, s, e in host if nm.startswith(VOCAB))
    active: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (a + b)
        while k < len(vocab) and vocab[k][0] <= mid:
            active.append(vocab[k])
            k += 1
        active = [x for x in active if x[1] > mid]
        name = re.sub(r"\d+$", "", max(active)[2]) if active else "(no annotation)"
        idle[name] += (b - a) * 1e-9 / n
    return dict(
        window_s=window_s,
        busy_s=busy_s,
        busy_by_device={str(k): v for k, v in sorted(busy.items())},
        programs=dict(programs),
        breakdown=dict(
            device_ops=[[k, v] for k, v in sorted(ops.items(), key=lambda x: -x[1])[:top]],
            idle_gaps=[[k, v] for k, v in sorted(idle.items(), key=lambda x: -x[1])[:top]],
        ),
    )


def program_seconds(reduced: Dict, pattern: str) -> Optional[float]:
    """Device seconds of every program whose name matches ``pattern``
    (a regular expression), or ``None`` when no such program ran."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced.get("programs", {}).items() if rx.search(k)]
    return sum(hits) if hits else None


def idle_share_pct(ctx: Dict) -> Optional[float]:
    """Device idle share of a traced run's window, in percent: 1 - busy /
    window, busy being the union of the device's operation intervals
    averaged over the cell's chips; ``None`` without a trace."""
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
