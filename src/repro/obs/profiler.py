"""``jax.profiler`` hooks — device-side profiling of the fleet pipeline.

:func:`profile_trace` wraps a run in ``jax.profiler.trace`` (TensorBoard
/ Perfetto-loadable device profile); inside it, :func:`annotate` marks
host-dispatched regions (per-group fleet dispatch, the Pallas-vs-XLA
scheduler call) with ``jax.profiler.TraceAnnotation`` and
:func:`step_annotation` marks scan windows with ``StepTraceAnnotation``.

When no profile is active — the default — both helpers return one shared
``nullcontext`` instance, so instrumented call sites cost a function
call and a flag check.  A profile that was asked for and cannot start or
stop raises: a run told to trace never finishes without its trace.
"""
from __future__ import annotations

from contextlib import contextmanager, nullcontext

import jax

__all__ = ["profile_trace", "annotate", "step_annotation", "profiling_active"]

_ACTIVE = False
_NOOP = nullcontext()


def profiling_active() -> bool:
    return _ACTIVE


@contextmanager
def profile_trace(log_dir):
    """Capture a ``jax.profiler`` trace of the block into ``log_dir``.

    ``log_dir`` of ``None``/empty yields without starting anything, so
    callers can thread an optional ``--profile DIR`` flag straight
    through.  A trace that cannot start or stop raises — the exception
    from ``jax.profiler`` propagates.
    """
    global _ACTIVE
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(str(log_dir))
    _ACTIVE = True
    try:
        yield
    finally:
        _ACTIVE = False
        jax.profiler.stop_trace()


def annotate(name: str, **kwargs):
    """``TraceAnnotation(name)`` under an active profile, else a no-op."""
    if not _ACTIVE:
        return _NOOP
    return jax.profiler.TraceAnnotation(name, **kwargs)


def step_annotation(name: str, step: int):
    """``StepTraceAnnotation`` (profiler step marker) under an active
    profile, else a no-op — one per fleet scan window."""
    if not _ACTIVE:
        return _NOOP
    return jax.profiler.StepTraceAnnotation(name, step_num=step)
