"""GUS — the paper's greedy scheduler (Algorithm 1) as a composable JAX module.

Three implementations behind one dispatcher:

* ``gus_schedule_np``  — direct NumPy transcription of Algorithm 1 (the oracle).
* ``backend="xla"``    — pure-JAX: ``lax.fori_loop`` over requests (the greedy
  is sequential in its capacity state) with fully vectorized masked-argmax over
  the (M, L) candidate grid per step.  ``jit``-able and ``vmap``-able over a
  leading instance-batch axis — the paper's 20 000 Monte-Carlo repetitions
  become one device program.  The default.
* ``backend="pallas"`` — the fused Pallas kernel
  (:mod:`repro.kernels.gus_pallas`): utility computation, feasibility and the
  greedy capacity loop in one on-chip program, one grid step per frame in the
  batch.  Compiled Mosaic on TPU; interpret mode (plain jax ops) on CPU,
  which is how CI validates it
  (:func:`repro.kernels.gus_pallas.pallas_interpret` decides).

All three return ``Assignment(j, l)`` with j = l = -1 encoding *drop* and are
held to **bit-identical** assignments on the same frame — integer outputs, so
exact equality, not tolerance, is the test bar (``tests/test_gus_parity.py``).
The backend is picked per call (``backend=``) or process-wide via the
``REPRO_GUS_BACKEND`` environment variable (read when no explicit ``backend=``
is passed; the default is ``"xla"``).

The shared tie-break rule: among equal-utility feasible candidates, the lowest
flat ``(j * L + l)`` index wins.  The JAX paths get this from ``argmax``'s
first-occurrence semantics; the NumPy oracle uses a *stable* descending sort
so duplicate-utility frames (padding rows, quantized QoS tiers) cannot drift
between implementations.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gus_pallas import gus_assign_pallas, pallas_interpret
from repro.obs.trace import CAT_SCHED, span

from .instance import FlatInstance
from .satisfaction import hard_feasible, us_tensor

__all__ = [
    "Assignment",
    "GUS_BACKENDS",
    "gus_schedule",
    "gus_schedule_np",
    "gus_schedule_batch",
    "gus_backend_fn",
    "resolve_gus_backend",
]

NEG = -1e30

#: registered GUS dispatch backends (``gus_schedule``'s ``backend=``)
GUS_BACKENDS = ("xla", "pallas")


def resolve_gus_backend(backend=None) -> str:
    """Resolve a ``backend=`` argument under the engine-wide precedence
    order (explicit > ``REPRO_GUS_BACKEND`` > ``"xla"``), delegating to
    :func:`repro.core.options.resolve_backend` — the single environment
    lookup site for the backend axis."""
    from .options import resolve_backend

    return resolve_backend(backend)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Assignment:
    """Scheduling decision per request: server j and variant l (-1 = dropped)."""

    j: jnp.ndarray  # (..., N) int32
    l: jnp.ndarray  # (..., N) int32

    def served(self):
        return self.j >= 0

    def offloaded(self, inst: FlatInstance):
        return self.served() & (self.j != inst.cover)


# ---------------------------------------------------------------------------
# NumPy reference (Algorithm 1, line-by-line)
# ---------------------------------------------------------------------------

def gus_schedule_np(inst: FlatInstance) -> Assignment:
    cover = np.asarray(inst.cover)
    A = np.asarray(inst.A)
    C = np.asarray(inst.C)
    acc = np.asarray(inst.acc)
    ctime = np.asarray(inst.ctime)
    v = np.asarray(inst.v)
    u = np.asarray(inst.u)
    avail = np.asarray(inst.avail)
    gamma = np.asarray(inst.gamma).copy()
    eta = np.asarray(inst.eta).copy()
    N, M, L = acc.shape

    us = np.asarray(us_tensor(inst))
    out_j = np.full(N, -1, np.int32)
    out_l = np.full(N, -1, np.int32)

    for i in range(N):  # foreach request (line 1)
        s_i = cover[i]  # line 2
        # line 3: servers sorted by US descending.  The sort is *stable* so
        # equal-utility candidates keep ascending flat (j*L + l) order — the
        # same tie-break argmax's first-occurrence rule gives the JAX and
        # Pallas backends, which is what makes bit-parity well-defined on
        # duplicate-utility frames.
        order = np.argsort(-us[i], axis=None, kind="stable")
        for flat in order:
            j, l = divmod(int(flat), L)
            # line 4: deadline, accuracy floor, compute capacity, placement
            if not avail[i, j, l]:
                continue
            if ctime[i, j, l] > C[i] or acc[i, j, l] < A[i]:
                continue
            if v[i, j, l] > gamma[j]:
                continue
            if j == s_i:  # lines 5-9: local processing
                out_j[i], out_l[i] = j, l
                gamma[j] -= v[i, j, l]
                break
            elif u[i, j, l] <= eta[s_i]:  # lines 10-14: offload
                out_j[i], out_l[i] = j, l
                gamma[j] -= v[i, j, l]
                eta[s_i] -= u[i, j, l]
                break
        # else: dropped (stays -1)
    return Assignment(jnp.asarray(out_j), jnp.asarray(out_l))


# ---------------------------------------------------------------------------
# Pure-JAX implementation
# ---------------------------------------------------------------------------

def _gus_body(i, state, *, inst, us, feas):
    gamma, eta, out_j, out_l = state
    M, L = us.shape[1], us.shape[2]
    s_i = inst.cover[i]

    row_us = us[i]          # (M, L)
    row_v = inst.v[i]
    row_u = inst.u[i]
    is_local = jnp.arange(M) == s_i  # (M,)

    ok = (
        feas[i]
        & (row_v <= gamma[:, None])
        & (is_local[:, None] | (row_u <= eta[s_i]))
    )
    score = jnp.where(ok, row_us, NEG)
    flat = jnp.argmax(score.reshape(-1))
    any_ok = score.reshape(-1)[flat] > NEG
    j = (flat // L).astype(jnp.int32)
    l = (flat % L).astype(jnp.int32)

    served = any_ok
    offload = served & (j != s_i)
    gamma = gamma.at[j].add(jnp.where(served, -row_v[j, l], 0.0))
    eta = eta.at[s_i].add(jnp.where(offload, -row_u[j, l], 0.0))
    out_j = out_j.at[i].set(jnp.where(served, j, -1))
    out_l = out_l.at[i].set(jnp.where(served, l, -1))
    return gamma, eta, out_j, out_l


@partial(jax.jit, static_argnames=("relax_compute", "relax_comm"))
def _gus_schedule_xla(
    inst: FlatInstance,
    *,
    relax_compute: bool = False,
    relax_comm: bool = False,
) -> Assignment:
    """The jitted XLA implementation (the default backend)."""
    us = us_tensor(inst)
    feas = hard_feasible(inst)
    N = us.shape[0]
    gamma0 = jnp.full_like(inst.gamma, jnp.inf) if relax_compute else inst.gamma
    eta0 = jnp.full_like(inst.eta, jnp.inf) if relax_comm else inst.eta
    out_j = jnp.full((N,), -1, jnp.int32)
    out_l = jnp.full((N,), -1, jnp.int32)
    if N == 0:  # static under jit; fori_loop would trace a size-0 gather
        return Assignment(out_j, out_l)
    body = partial(_gus_body, inst=inst, us=us, feas=feas)
    gamma, eta, out_j, out_l = jax.lax.fori_loop(
        0, N, body, (gamma0, eta0, out_j, out_l)
    )
    return Assignment(out_j, out_l)


def _relaxed_budgets(inst: FlatInstance, relax_compute: bool, relax_comm: bool):
    """The Happy-* budget substitution, shared by both JAX backends."""
    gamma0 = jnp.full_like(inst.gamma, jnp.inf) if relax_compute else inst.gamma
    eta0 = jnp.full_like(inst.eta, jnp.inf) if relax_comm else inst.eta
    return gamma0, eta0


@partial(jax.jit, static_argnames=("relax_compute", "relax_comm", "interpret"))
def _gus_schedule_pallas(
    inst: FlatInstance,
    *,
    relax_compute: bool = False,
    relax_comm: bool = False,
    interpret: bool,
) -> Assignment:
    """Single-frame entry to the fused Pallas kernel (batch of one grid
    program; ``vmap`` lifts it to one program per batched frame)."""
    gamma0, eta0 = _relaxed_budgets(inst, relax_compute, relax_comm)
    add = lambda x: jnp.asarray(x)[None]  # noqa: E731 — lift to batch of 1
    j, l = gus_assign_pallas(
        add(inst.cover), add(inst.A), add(inst.C), add(inst.w_a), add(inst.w_c),
        add(inst.acc), add(inst.ctime), add(inst.v), add(inst.u), add(inst.avail),
        add(gamma0), add(eta0), add(inst.max_as), add(inst.max_cs),
        interpret=interpret,
    )
    return Assignment(j[0], l[0])


@partial(jax.jit, static_argnames=("relax_compute", "relax_comm", "interpret"))
def _gus_schedule_batch_pallas(
    batch: FlatInstance,
    *,
    relax_compute: bool = False,
    relax_comm: bool = False,
    interpret: bool,
) -> Assignment:
    """Natively-batched Pallas entry: grid = the leading batch axis, one
    grid program per frame — no vmap lifting."""
    gamma0, eta0 = _relaxed_budgets(batch, relax_compute, relax_comm)
    j, l = gus_assign_pallas(
        batch.cover, batch.A, batch.C, batch.w_a, batch.w_c,
        batch.acc, batch.ctime, batch.v, batch.u, batch.avail,
        gamma0, eta0, batch.max_as, batch.max_cs,
        interpret=interpret,
    )
    return Assignment(j, l)


def gus_schedule(
    inst: FlatInstance,
    *,
    relax_compute: bool = False,
    relax_comm: bool = False,
    backend: str = None,
) -> Assignment:
    """Run GUS on one instance.  ``relax_*`` implement the paper's
    Happy-Computation / Happy-Communication baselines (constraints 2d/2e
    dropped).  ``backend`` selects the implementation (``"xla"`` jitted
    loop, ``"pallas"`` fused kernel; ``None`` defers to the
    ``REPRO_GUS_BACKEND`` environment variable) — assignments are
    bit-identical across backends."""
    backend = resolve_gus_backend(backend)
    with span("gus/call", CAT_SCHED, backend=backend):
        if backend == "pallas":
            return _gus_schedule_pallas(
                inst, relax_compute=relax_compute, relax_comm=relax_comm,
                interpret=pallas_interpret(),
            )
        return _gus_schedule_xla(
            inst, relax_compute=relax_compute, relax_comm=relax_comm
        )


@partial(jax.jit, static_argnames=("relax_compute", "relax_comm"))
def _gus_schedule_batch_xla(
    batch: FlatInstance, *, relax_compute: bool = False, relax_comm: bool = False
) -> Assignment:
    fn = partial(
        _gus_schedule_xla, relax_compute=relax_compute, relax_comm=relax_comm
    )
    return jax.vmap(fn)(batch)


def gus_schedule_batch(
    batch: FlatInstance,
    *,
    relax_compute: bool = False,
    relax_comm: bool = False,
    backend: str = None,
) -> Assignment:
    """GUS over a leading instance-batch axis (Monte-Carlo runs): vmapped
    XLA by default, or the natively-batched Pallas kernel (one grid program
    per frame) with ``backend="pallas"``."""
    backend = resolve_gus_backend(backend)
    with span("gus/call_batch", CAT_SCHED, backend=backend):
        if backend == "pallas":
            return _gus_schedule_batch_pallas(
                batch, relax_compute=relax_compute, relax_comm=relax_comm,
                interpret=pallas_interpret(),
            )
        return _gus_schedule_batch_xla(
            batch, relax_compute=relax_compute, relax_comm=relax_comm
        )


@functools.lru_cache(maxsize=None)
def gus_backend_fn(backend: str):
    """A stable-identity ``FlatInstance -> Assignment`` callable for one
    backend.  The fleet runner's compiled-program cache keys on the schedule
    function's identity, so ad-hoc ``partial(gus_schedule, backend=...)``
    objects would force a re-trace per call — this cache hands every caller
    the same object per backend."""
    backend = resolve_gus_backend(backend)
    if backend == "xla":
        return gus_schedule  # the default object every existing cache keys on
    return functools.partial(gus_schedule, backend=backend)
