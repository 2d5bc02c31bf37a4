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
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gus_pallas import gus_assign_pallas, pallas_interpret
from repro.obs.trace import CAT_SCHED, span

from .instance import FlatInstance, _host_array
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
# One upload per call: host leaves packed into one buffer
# ---------------------------------------------------------------------------

#: ``FlatInstance``'s leaves, in field order
_FIELDS = tuple(f.name for f in dataclasses.fields(FlatInstance))


@functools.lru_cache(maxsize=None)
def _upload_layout(specs: tuple) -> tuple:
    """Where each packed leaf sits in the upload buffer.

    ``specs`` is ``((name, shape, dtype), ...)``; the result is
    ``((name, word_offset, n_words, shape, dtype), ...)``, every leaf
    starting on a 32-bit word.  Static and hashable: one padding bucket
    gives one layout, and so one compiled program."""
    layout, off = [], 0
    for name, shape, dt in specs:
        n = -(-math.prod(shape) * dt.itemsize // 4)
        layout.append((name, off, n, shape, dt))
        off += n
    return tuple(layout)


def _host_leaves(inst: FlatInstance) -> dict:
    """The leaves ``gus_schedule`` packs: NumPy arrays and scalars, in the
    dtypes JAX would give them, unless a leaf is a tracer (called under a
    transform, where nothing is uploaded)."""
    leaves = {k: getattr(inst, k) for k in _FIELDS}
    if any(isinstance(x, jax.core.Tracer) for x in leaves.values()):
        return {}
    return {k: _host_array(x) for k, x in leaves.items()
            if isinstance(x, (np.ndarray, np.generic))}


def _pack(host: dict, layout: tuple) -> np.ndarray:
    """The host leaves' bytes, one ``uint32`` buffer laid out by ``layout``."""
    words = np.empty(sum(n for _, _, n, _, _ in layout), np.uint32)
    b = words.view(np.uint8)
    for (_, off, _, _, _), x in zip(layout, host.values()):
        b[4 * off:4 * off + x.nbytes] = np.ascontiguousarray(x).reshape(-1).view(np.uint8)
    return words


def _unpack(words: jnp.ndarray, layout: tuple) -> dict:
    """Inverse of :func:`_pack`, inside the jitted program: each leaf's
    words sliced out and reinterpreted bit for bit (``bool`` as ``!= 0``).
    Leaves are at most 32 bits wide, as JAX canonicalises them."""
    out = {}
    for name, off, n, shape, dt in layout:
        x = words[off:off + n]
        if dt.itemsize < 4:  # several elements per word: split it
            x = jax.lax.bitcast_convert_type(x, np.dtype(f"uint{8 * dt.itemsize}"))
            x = x.reshape(-1)[:math.prod(shape)]
        x = x.reshape(shape)
        out[name] = x != 0 if dt == np.bool_ else jax.lax.bitcast_convert_type(x, dt)
    return out


def _with_unpacked(inst: FlatInstance, words, layout: tuple) -> FlatInstance:
    """``inst`` with the leaves packed into ``words`` put back."""
    if words is None:
        return inst
    return dataclasses.replace(inst, **_unpack(words, layout))


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


@partial(jax.jit, static_argnames=("layout", "relax_compute", "relax_comm"))
def _gus_schedule_xla(
    inst: FlatInstance,
    words=None,
    *,
    layout: tuple = (),
    relax_compute: bool = False,
    relax_comm: bool = False,
) -> Assignment:
    """The jitted XLA implementation (the default backend).  Leaves of
    ``inst`` that are ``None`` arrive packed in ``words`` (``layout``)."""
    inst = _with_unpacked(inst, words, layout)
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


@partial(jax.jit, static_argnames=("layout", "relax_compute", "relax_comm", "interpret"))
def _gus_schedule_pallas(
    inst: FlatInstance,
    words=None,
    *,
    layout: tuple = (),
    relax_compute: bool = False,
    relax_comm: bool = False,
    interpret: bool,
) -> Assignment:
    """Single-frame entry to the fused Pallas kernel (batch of one grid
    program; ``vmap`` lifts it to one program per batched frame).  Packed
    leaves as in :func:`_gus_schedule_xla`."""
    inst = _with_unpacked(inst, words, layout)
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
    bit-identical across backends.

    Leaves that are NumPy arrays are written into one buffer on the host,
    uploaded with one ``jax.device_put`` and unpacked bit for bit inside
    the jitted program, so a host-side frame costs one transfer and one
    dispatch; ``jax.Array`` leaves are passed as they are, and under a
    transform (tracer leaves) nothing is packed.  The ``gus/call`` span's
    ``h2d_bytes`` / ``h2d_transfers`` args count the upload.

    A concrete call's answer is read on the host next by every caller in
    the program (the online controller, ``simulate()``, the baselines), so
    the copies of ``j`` and ``l`` to the host are started right after
    dispatch: they run as soon as the program ends, and
    ``np.asarray`` of the returned arrays costs no further round trip.
    The result is still an ``Assignment`` of ``jax.Array``s, so a caller
    may keep working on the device.  Under a transform (the outputs are
    tracers) no copy is started.  The span's ``d2h_transfers`` /
    ``d2h_bytes`` args count the answer started to the host (``0`` and
    ``0`` otherwise)."""
    backend = resolve_gus_backend(backend)
    host = _host_leaves(inst)
    layout = _upload_layout(tuple((k, x.shape, x.dtype) for k, x in host.items()))
    with span("gus/call", CAT_SCHED, backend=backend,
              h2d_bytes=sum(4 * n for _, _, n, _, _ in layout),
              h2d_transfers=int(bool(host))) as s:
        words = None
        if host:
            inst = dataclasses.replace(inst, **dict.fromkeys(host))
            words = jax.device_put(_pack(host, layout))
        kw = dict(layout=layout, relax_compute=relax_compute, relax_comm=relax_comm)
        if backend == "pallas":
            out = _gus_schedule_pallas(inst, words, interpret=pallas_interpret(), **kw)
        else:
            out = _gus_schedule_xla(inst, words, **kw)
        fetch = not isinstance(out.j, jax.core.Tracer)
        if fetch:
            out.j.copy_to_host_async()
            out.l.copy_to_host_async()
        s.note(d2h_transfers=int(fetch), d2h_bytes=out.j.nbytes + out.l.nbytes if fetch else 0)
        return out


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
