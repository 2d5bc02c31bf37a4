"""Host span tracing — a thread-aware recorder emitting Chrome trace JSON.

:class:`span` is the program's one instrument.  One ``with`` block
feeds three consumers from the same ``perf_counter`` pair:

* a :class:`Stopwatch` (``acc=``), whose totals become the simulators'
  timing fields (``FleetResult.gen_s`` / ``dispatch_s`` / ``timings``,
  ``SimResult.timings``);
* the process-wide Chrome recorder, once one is installed
  (:func:`start_trace` / :func:`recording`);
* the ``jax.profiler`` trace, while :mod:`repro.obs.profiler` has a
  profile active (its module-level ``_ACTIVE`` flag): the span opens a
  ``TraceAnnotation`` of its name (a ``StepTraceAnnotation`` when given
  ``step=``), so program spans land on the device trace's clock.  Spans
  of category :data:`CAT_WAIT` only wait for another thread and open
  none: a trace names a device gap by the most recently started host
  annotation, and a wait would take the idle of the work it waits for.

With neither recorder nor profile, ``__enter__``/``__exit__`` cost two
``time.perf_counter()`` calls, one ``None`` check and one flag check;
nothing is allocated, and no lock is taken but a :class:`Stopwatch`'s
(``benchmarks/telemetry_overhead.py`` gates that cost at < 1% of the
64-replication fleet bench point).

:meth:`Stopwatch.count` adds integer counters (bytes, rows) at the same
boundaries; an active recorder gets a ``"C"`` event for each.

Events carry the recording thread's id and name, so spans from
``simulate_fleet``'s producer thread ("fleet-window-producer") and the
async JSONL exporter land on their own tracks in ``chrome://tracing`` /
Perfetto.  The emitted JSON object format is::

    {"traceEvents": [
        {"name": ..., "cat": ..., "ph": "X", "ts": us, "dur": us,
         "pid": <pid>, "tid": <tid>, "args": {...}},
        {"ph": "M", "name": "thread_name", ...},           # metadata
        {"ph": "i", "name": ..., "ts": us, "s": "t", ...}, # instants
     ],
     "displayTimeUnit": "ms"}

:func:`validate_chrome_trace` checks that shape (the telemetry test
suite and the CI artifact smoke both run it).
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Union

import jax

from . import profiler as _profiler

__all__ = [
    "CAT_GEN",
    "CAT_BUILD",
    "CAT_SCHED",
    "CAT_DISPATCH",
    "CAT_METRICS",
    "CAT_IO",
    "CAT_COMPILE",
    "CAT_WAIT",
    "TraceRecorder",
    "span",
    "instant",
    "Stopwatch",
    "start_trace",
    "stop_trace",
    "recording",
    "active_recorder",
    "save_chrome_trace",
    "validate_chrome_trace",
]

#: span categories used across the pipeline — a stable vocabulary so the
#: CI artifact diff can see a category disappear
CAT_GEN = "gen"            # arrival-trace generation / stream pulls
CAT_BUILD = "build"        # frame-grid / instance building (host)
CAT_SCHED = "sched"        # scheduler calls (host-dispatched)
CAT_DISPATCH = "dispatch"  # jitted fleet-program dispatch + materialization
CAT_METRICS = "metrics"    # window metrics drain / satisfaction reductions
CAT_IO = "io"              # telemetry export (JSONL writer thread)
CAT_COMPILE = "compile"    # compile-cache misses (runner/policy binding)
CAT_WAIT = "wait"          # blocked on another thread (never a profiler annotation)


class TraceRecorder:
    """Thread-safe in-memory event sink for one recording session.

    Timestamps are ``perf_counter`` microseconds relative to the
    recorder's creation, which is what Chrome's trace viewer expects of a
    single-process capture.
    """

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._thread_names: Dict[int, str] = {}

    # -- recording --------------------------------------------------------
    def _note_thread(self, tid: int) -> None:
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name

    def add_complete(
        self, name: str, cat: str, t_start: float, t_end: float,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """One complete ("X") event from a pair of ``perf_counter`` readings."""
        tid = threading.get_ident()
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (t_start - self._t0) * 1e6,
            "dur": max(t_end - t_start, 0.0) * 1e6,
            "pid": os.getpid(),
            "tid": tid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._note_thread(tid)
            self._events.append(ev)

    def add_instant(
        self, name: str, cat: str, args: Optional[Dict[str, Any]] = None
    ) -> None:
        """One instant ("i") event at the current time (thread-scoped)."""
        tid = threading.get_ident()
        ev = {
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "t",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": os.getpid(),
            "tid": tid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._note_thread(tid)
            self._events.append(ev)

    def add_counter(self, name: str, value: int) -> None:
        """One counter ("C") event at the current time."""
        tid = threading.get_ident()
        ev = {
            "name": name,
            "cat": "count",
            "ph": "C",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": os.getpid(),
            "tid": tid,
            "args": {name: value},
        }
        with self._lock:
            self._note_thread(tid)
            self._events.append(ev)

    # -- introspection ----------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def categories(self) -> set:
        return {e["cat"] for e in self.events() if e["ph"] != "M"}

    def thread_ids(self) -> set:
        return {e["tid"] for e in self.events()}

    def span_names(self) -> set:
        return {e["name"] for e in self.events() if e["ph"] == "X"}

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- export -----------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (events + thread metadata)."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in sorted(names.items())
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(str(path))), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


#: the process-wide recorder; ``None`` means tracing is off (the default)
_RECORDER: Optional[TraceRecorder] = None
_INSTALL_LOCK = threading.Lock()


def active_recorder() -> Optional[TraceRecorder]:
    return _RECORDER


def start_trace() -> TraceRecorder:
    """Install a fresh process-wide recorder (replacing any active one)."""
    global _RECORDER
    with _INSTALL_LOCK:
        _RECORDER = TraceRecorder()
        return _RECORDER


def stop_trace() -> Optional[TraceRecorder]:
    """Uninstall and return the active recorder (``None`` if none)."""
    global _RECORDER
    with _INSTALL_LOCK:
        rec, _RECORDER = _RECORDER, None
        return rec


@contextmanager
def recording():
    """``with recording() as rec: ...`` — record for the block's duration."""
    rec = start_trace()
    try:
        yield rec
    finally:
        with _INSTALL_LOCK:
            global _RECORDER
            if _RECORDER is rec:
                _RECORDER = None


class span:
    """Timed block: ``with span("fleet/dispatch", CAT_DISPATCH) as s: ...``.

    Always measures (``s.elapsed_s`` is valid after exit — the simulators'
    timing fields are built from it); records a trace event only when a
    process-wide recorder is active at ``__enter__``, and opens a profiler
    annotation only when a profile is active then and ``cat`` is not
    :data:`CAT_WAIT` (``step=`` makes it a ``StepTraceAnnotation``).  An
    exception inside the block still closes and records the span.
    """

    __slots__ = ("name", "cat", "args", "acc", "step", "_t0", "_rec", "_ann", "elapsed_s")

    def __init__(
        self,
        name: str,
        cat: str = CAT_SCHED,
        acc: Optional["Stopwatch"] = None,
        step: Optional[int] = None,
        **args: Any,
    ) -> None:
        self.name = name
        self.cat = cat
        self.args = args or None
        self.acc = acc
        self.step = step
        self.elapsed_s = 0.0

    def __enter__(self) -> "span":
        self._rec = _RECORDER  # snapshot: recorder swaps mid-span stay sane
        # read through the module: the flag is flipped at run time
        if _profiler._ACTIVE and self.cat != CAT_WAIT:
            self._ann = (
                jax.profiler.TraceAnnotation(self.name) if self.step is None
                else jax.profiler.StepTraceAnnotation(self.name, step_num=self.step)
            )
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        self.elapsed_s = t1 - self._t0
        if self.acc is not None:
            self.acc._add(self.name, self.elapsed_s)
        rec = self._rec
        if rec is not None:
            rec.add_complete(self.name, self.cat, self._t0, t1, self.args)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)


def instant(name: str, cat: str = CAT_COMPILE, **args: Any) -> None:
    """Record an instant event (no-op when tracing is off)."""
    rec = _RECORDER
    if rec is not None:
        rec.add_instant(name, cat, args or None)


class Stopwatch:
    """Per-run accumulator of span durations and counts, keyed by name.

    ``simulate`` / ``simulate_fleet`` each create one and wire their spans
    through it (``sw.span(...)``), then read totals to fill their timing
    fields — the trace recorder and the result fields see the *same*
    ``perf_counter`` pairs, so enabling tracing cannot skew the numbers.
    :meth:`count` accumulates integers (bytes, rows) beside the totals.
    The fleet's producer and worker threads share one stopwatch, so
    updates take a lock.
    """

    __slots__ = ("totals", "counts", "_lock")

    def __init__(self) -> None:
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def _add(self, name: str, elapsed_s: float) -> None:
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + elapsed_s

    def span(self, name: str, cat: str = CAT_SCHED, step: Optional[int] = None,
             **args: Any) -> span:
        return span(name, cat, acc=self, step=step, **args)

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to counter ``name`` (and emit a ``"C"`` event of ``n``
        to an active recorder)."""
        n = int(n)
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n
        rec = _RECORDER
        if rec is not None:
            rec.add_counter(name, n)

    def total(self, *names: str) -> float:
        return sum(self.totals.get(n, 0.0) for n in names)

    def as_dict(self) -> Dict[str, Union[float, int]]:
        """Span totals (float seconds) and counters (integer counts:
        ``*_bytes``, ``*/rows``), in one dict."""
        with self._lock:
            return {**self.totals, **self.counts}


def save_chrome_trace(recorder: TraceRecorder, path) -> None:
    recorder.save(path)


_VALID_PH = {"X", "i", "M", "B", "E", "C"}


def validate_chrome_trace(obj: Any) -> List[str]:
    """Schema check of a Chrome trace-event JSON object; returns the list
    of violations (empty == valid).  Accepts the object-form trace this
    module emits (and the bare event-array form, for robustness)."""
    errors: List[str] = []
    if isinstance(obj, dict):
        events = obj.get("traceEvents")
        if not isinstance(events, list):
            return ["top-level 'traceEvents' missing or not a list"]
    elif isinstance(obj, list):
        events = obj
    else:
        return ["trace is neither an object with 'traceEvents' nor an array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _VALID_PH:
            errors.append(f"event {i}: bad or missing ph {ph!r}")
            continue
        if not isinstance(ev.get("name"), str):
            errors.append(f"event {i}: missing name")
        if not isinstance(ev.get("pid"), int) or not isinstance(ev.get("tid"), int):
            errors.append(f"event {i}: missing pid/tid")
        if ph in ("X", "i"):
            if not isinstance(ev.get("ts"), (int, float)):
                errors.append(f"event {i}: missing ts")
            if not isinstance(ev.get("cat"), str):
                errors.append(f"event {i}: missing cat")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i}: X event needs dur >= 0")
    return errors
