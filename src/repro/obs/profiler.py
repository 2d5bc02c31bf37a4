"""``jax.profiler`` hooks — device-side profiling of the fleet pipeline.

:func:`profile_trace` wraps a run in a ``jax.profiler`` trace (TensorBoard
/ Perfetto-loadable device profile) and sets the module-level ``_ACTIVE``
flag while it runs.  The program's annotations are its
:class:`repro.obs.trace.span` blocks: each reads ``_ACTIVE`` at enter and,
when set, opens a ``TraceAnnotation`` of its own name, so the
``Stopwatch`` totals, the Chrome recorder and the profiler trace all come
from one ``with`` block.  A harness that starts the profiler itself sets
``_ACTIVE`` the same way; the flag stays a plain module-level ``bool``.

A profile that was asked for and cannot start or stop raises: a run told
to trace never finishes without its trace.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax

__all__ = ["profile_trace", "profiling_active"]

_ACTIVE = False


def profiling_active() -> bool:
    return _ACTIVE


@contextmanager
def profile_trace(log_dir):
    """Capture a ``jax.profiler`` trace of the block into ``log_dir``.

    The profiler starts with the Python tracer off (it would record every
    Python call of the host layers) and host tracer level 2, as the
    benchmark starts its own trace.  ``log_dir`` of ``None``/empty yields
    without starting anything, so callers can thread an optional
    ``--profile DIR`` flag straight through.  A trace that cannot start or
    stop raises — the exception from ``jax.profiler`` propagates.
    """
    global _ACTIVE
    if not log_dir:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    _ACTIVE = True
    try:
        yield
    finally:
        _ACTIVE = False
        jax.profiler.stop_trace()
