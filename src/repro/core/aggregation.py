"""Hierarchical class-aggregate scheduling (the ``10^5+`` users-per-frame path).

The paper's GUS walks every request over the dense ``N x M x L`` grid, which
caps frames in the low thousands of requests.  But the QoS space is tiny:
requests differ only in (covering edge, service, accuracy floor ``A``,
deadline ``C``, payload size, queueing age ``Tq``), and with discrete QoS
tiers most of those axes collapse.  This module buckets requests into
**QoS classes** and schedules the class *aggregates* — a grid of
``n_classes x M x L`` with per-class member counts — then maps class-level
allocations back to individual requests.

The scheduler is two-level:

1. **Per-edge local pass** — embarrassingly parallel over covering edges:
   requests are bucketed into classes, each class's utility / feasibility /
   cost rows are built once from a representative member, and classes with
   no feasible candidate anywhere are retired immediately.  Nothing in this
   pass touches shared state.
2. **Global cloud-contention pass** — the per-edge class tables are merged
   in first-request-index order and a single sequential greedy allocates
   *chunks* (class, server j, variant l, count) against the shared capacity
   vectors, reconciling cross-edge contention for cloud compute, remote
   edge compute, and each edge's uplink ``eta``.  This is the only
   sequential step, and it runs over ``n_classes`` rows instead of ``N``.
3. **De-aggregation** — chunks are mapped back to per-request assignments
   by consuming each class's members in ascending request index, so the
   result is deterministic and reproducible regardless of how requests were
   grouped.

Parity with dense GUS
---------------------
In ``exact=True`` mode the chunk allocator emulates the NumPy oracle's
float32 sequential capacity subtraction member by member, re-checking only
the chosen cell (capacity is monotone decreasing, so the feasible-argmax of
a class of identical rows can only move when the chosen cell dies — at
which point the full argmax is recomputed).  Consequences, pinned by
``tests/test_aggregation.py``:

* with lossless keys (``decimals=None``) every class groups bit-identical
  rows; on frames where classes are index-contiguous (in particular on any
  frame where all classes are singletons, i.e. every real scenario frame)
  the assignment is **bit-identical** to :func:`repro.core.gus.gus_schedule_np`;
* with quantized keys the representative row stands in for near-identical
  members, trading exactness for aggregation — the satisfaction gap vs
  dense GUS stays within the paper-scale tolerance asserted in tests.

The fleet's ``scheduler="hierarchical"`` path (``simulator.py``) reuses
:func:`aggregate_requests` / :func:`hier_assign` / :func:`deaggregate` but
builds only the class-level tensors, never the dense ``N x M x L`` grid —
that is what bounds memory at ``10^5+`` users per frame.

Device backends
---------------
The fleet's analytic allocation also exists as a jitted XLA program
(:func:`hier_cells`, ``backend="xla"``) and a fused Pallas kernel
(:mod:`repro.kernels.hier_pallas`, ``backend="pallas"``), dispatched
through the same ``backend=`` / ``REPRO_GUS_BACKEND`` switch as the dense
GUS implementations.  All three speak a fixed-shape *cell* contract
instead of a variable-length chunk list: for classes *pre-sorted by first
request index*, ``(take, start)`` are ``(C, M, L)`` int32 tensors where
``take[c, j, l]`` members of class ``c`` run variant ``l`` on server ``j``
and ``start[c, j, l]`` is their offset into the class's (ascending)
member list.  Consecutive re-picks of one cell accumulate, so member
ranges stay contiguous and :func:`deaggregate` semantics are preserved.
The chunk sizing is float32 with one explicit IEEE op sequence — the
fit count ``max{t : f32(t * cost) <= budget}``, ``min`` against the
remainder, ``budget - take * cost`` — shared verbatim by the NumPy oracle
(:func:`hier_cells_np`), the XLA scan and the Pallas kernel, which is
what makes three-way bit-parity (``tests/test_hier_parity.py``)
well-defined with jax's default float32 everywhere.  The fit count starts
from ``floor(budget / cost)`` and corrects it by one step each way with
f32 multiplies: a TPU's f32 divide is not correctly rounded, and on an
exact multiple the bare floor lands one lower than NumPy's, while the
corrected count depends only on correctly rounded products.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gus_pallas import pallas_interpret
from repro.kernels.hier_pallas import fit_count, hier_cells_pallas

from .gus import Assignment, resolve_gus_backend
from .instance import FlatInstance
from .satisfaction import hard_feasible, us_tensor

__all__ = [
    "AggregateClasses",
    "QuantizationConfig",
    "aggregate_instance",
    "aggregate_requests",
    "class_keys",
    "hier_assign",
    "hier_cells_np",
    "hier_cells",
    "hier_backend_fn",
    "deaggregate",
    "hier_schedule_np",
    "make_gus_hier",
]


@dataclasses.dataclass(frozen=True)
class QuantizationConfig:
    """How request attributes are bucketed into QoS classes (fleet path).

    ``acc_decimals`` / ``deadline_decimals`` round the accuracy floor and
    deadline with :func:`numpy.round` (negative = coarser than integer), so
    discrete QoS tiers collapse losslessly.  ``size_bin_bytes`` /
    ``tq_bin_ms`` are *anchored* absolute-width bins
    (``floor(x / width)``): a request's class key depends only on its own
    attributes, never on which other requests share the frame.  The earlier
    observed-min/max equal-width bins made keys a function of the frame's
    extremes, so the same trace produced different classes under
    ``rng_mode="vectorized"`` vs object mode (different float roundtrips)
    and under different window chunkings — the instability pinned down by
    ``test_class_keys_chunk_invariant``.  The defaults keep the old
    granularity on the default generator: 12.5 kB over the 20–120 kB
    payload range ≈ the old 8 bins, 750 ms over a frame ≈ the old 4 bins.
    """

    acc_decimals: int = 0
    deadline_decimals: int = -2
    size_bin_bytes: float = 12_500.0
    tq_bin_ms: float = 750.0


@dataclasses.dataclass(frozen=True)
class AggregateClasses:
    """Class-aggregate view of one frame: grouping plus per-class rows.

    ``members`` lists request indices grouped by class and ascending within
    each class; class ``c`` owns ``members[offsets[c]:offsets[c + 1]]``.
    ``us`` / ``feas`` / ``v`` / ``u`` are the representative rows on the
    ``(n_classes, M, L)`` candidate grid.
    """

    count: np.ndarray      # (n_c,) int64 member counts
    first_idx: np.ndarray  # (n_c,) int64 lowest member request index
    members: np.ndarray    # (N,)  int64 request indices, class-grouped
    offsets: np.ndarray    # (n_c + 1,) int64 slice bounds into ``members``
    cover: np.ndarray      # (n_c,) int64 covering edge
    us: np.ndarray         # (n_c, M, L) f32 utility of the representative
    feas: np.ndarray       # (n_c, M, L) bool hard feasibility
    v: np.ndarray          # (n_c, M, L) f32 compute cost
    u: np.ndarray          # (n_c, M, L) f32 comm cost

    @property
    def n_classes(self) -> int:
        return self.count.shape[0]


def _group(inv: np.ndarray, n_classes: int):
    """Grouping arrays from a class-id-per-request vector."""
    n = inv.shape[0]
    count = np.bincount(inv, minlength=n_classes).astype(np.int64)
    first_idx = np.full(n_classes, n, np.int64)
    np.minimum.at(first_idx, inv, np.arange(n, dtype=np.int64))
    members = np.argsort(inv, kind="stable").astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(count)]).astype(np.int64)
    return count, first_idx, members, offsets


def aggregate_instance(
    inst: FlatInstance, decimals: Optional[int] = None
) -> AggregateClasses:
    """Bucket a dense :class:`FlatInstance`'s rows into QoS classes.

    This is the per-edge local pass for the drop-in ``gus-hier`` policy: it
    operates on an instance the engine has already built, so rows are
    grouped directly by their candidate-grid content — two requests share a
    class iff their scheduling problem is identical: same covering edge,
    same QoS (``A``, ``C``, weights) and the same ``ctime``/``v``/``u``/
    ``acc``/``avail`` rows.  ``decimals=None`` keys on exact values
    (lossless classes); an integer rounds ``ctime`` and ``u`` first, merging
    near-identical requests (e.g. same tier, payloads within a bin).

    The representative of each class is its lowest-index member, whose
    *unrounded* rows feed utility and feasibility.
    """
    A = np.asarray(inst.A)
    N = A.shape[0]
    ct = np.asarray(inst.ctime, dtype=np.float64)
    uu = np.asarray(inst.u, dtype=np.float64)
    if decimals is not None:
        ct = np.round(ct, decimals)
        uu = np.round(uu, decimals)
    mat = np.concatenate(
        [
            np.asarray(inst.cover, dtype=np.float64)[:, None],
            A.astype(np.float64)[:, None],
            np.asarray(inst.C, dtype=np.float64)[:, None],
            np.asarray(inst.w_a, dtype=np.float64)[:, None],
            np.asarray(inst.w_c, dtype=np.float64)[:, None],
            ct.reshape(N, -1),
            uu.reshape(N, -1),
            np.asarray(inst.v, dtype=np.float64).reshape(N, -1),
            np.asarray(inst.acc, dtype=np.float64).reshape(N, -1),
            np.asarray(inst.avail).astype(np.float64).reshape(N, -1),
        ],
        axis=1,
    )
    _, inv = np.unique(mat, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    count, first_idx, members, offsets = _group(inv, int(inv.max()) + 1 if N else 0)

    # representative rows: utilities/feasibility via the same code the dense
    # schedulers use, gathered at each class's first member (bit-identical
    # to the corresponding rows of the full us/feas tensors).
    rep = first_idx
    us = np.asarray(us_tensor(inst))[rep]
    feas = np.asarray(hard_feasible(inst))[rep]
    return AggregateClasses(
        count=count,
        first_idx=first_idx,
        members=members,
        offsets=offsets,
        cover=np.asarray(inst.cover)[rep].astype(np.int64),
        us=us,
        feas=feas,
        v=np.asarray(inst.v)[rep],
        u=np.asarray(inst.u)[rep],
    )


def class_keys(
    cover: np.ndarray,
    service: np.ndarray,
    A: np.ndarray,
    C: np.ndarray,
    size: np.ndarray,
    tq: np.ndarray,
    quant: Optional[QuantizationConfig] = None,
) -> np.ndarray:
    """(n, 6) int64 class keys: (cover, service, rounded A, rounded C,
    payload-size bin, queueing-age bin).

    Every column is a pure per-request function — anchored ``floor(x /
    width)`` bins, no frame-level statistics — so the key assigned to a
    request is invariant to chunking, windowing, and the arrival
    generator's rng mode.  Exposed so tests (and downstream tooling) can
    assert that invariance directly.
    """
    quant = quant or QuantizationConfig()
    return np.column_stack(
        [
            np.asarray(cover).astype(np.int64),
            np.asarray(service).astype(np.int64),
            np.round(
                np.asarray(A, np.float64) * 10.0 ** quant.acc_decimals
            ).astype(np.int64),
            np.round(
                np.asarray(C, np.float64) * 10.0 ** quant.deadline_decimals
            ).astype(np.int64),
            np.floor(
                np.asarray(size, np.float64) / quant.size_bin_bytes
            ).astype(np.int64),
            np.floor(
                np.asarray(tq, np.float64) / quant.tq_bin_ms
            ).astype(np.int64),
        ]
    )


def _unique_inverse_rows(key: np.ndarray) -> np.ndarray:
    """Inverse indices of ``np.unique(key, axis=0)`` via mixed-radix packing.

    Shifting each column to zero and packing most-significant-first keeps
    the scalar order identical to lexicographic row order, so the inverse
    (and therefore every downstream class index) is bit-identical to the
    ``axis=0`` path — just without the void-dtype row sort, which dominates
    aggregation time at 10^5 requests/frame.  Falls back to ``axis=0`` when
    the packed radix would overflow int64 (pathological key ranges).
    """
    lo = key.min(axis=0)
    k = key - lo
    span = k.max(axis=0).astype(object) + 1
    radix = 1
    for s in span:
        radix *= int(s)
    if radix >= np.iinfo(np.int64).max:
        _, inv = np.unique(key, axis=0, return_inverse=True)
        return inv.reshape(-1)
    packed = k[:, 0]
    for c in range(1, key.shape[1]):
        packed = packed * int(span[c]) + k[:, c]
    _, inv = np.unique(packed, return_inverse=True)
    return inv


def aggregate_requests(
    cover: np.ndarray,
    service: np.ndarray,
    A: np.ndarray,
    C: np.ndarray,
    size: np.ndarray,
    tq: np.ndarray,
    quant: Optional[QuantizationConfig] = None,
):
    """Bucket raw request columns into QoS classes (fleet path, no grid).

    Classes key on :func:`class_keys` (covering edge, service, rounded
    ``A``, rounded ``C``, anchored payload-size bin, anchored queueing-age
    bin) per ``quant``.  Returns the grouping arrays plus *count-weighted
    mean* representative columns — ``(count, first_idx, members, offsets,
    rep)`` where ``rep`` is a dict of per-class ``cover``/``service``
    (exact) and ``A``/``C``/``size``/``tq`` (means).  The caller builds the
    ``(n_classes, M, L)`` candidate grid from ``rep`` — dense per-request
    tensors are never materialized.
    """
    quant = quant or QuantizationConfig()
    n = cover.shape[0]
    if n == 0:
        empty = np.zeros(0, np.int64)
        rep = dict(
            cover=empty,
            service=empty,
            A=np.zeros(0),
            C=np.zeros(0),
            size=np.zeros(0),
            tq=np.zeros(0),
        )
        return empty, empty, empty, np.zeros(1, np.int64), rep

    key = class_keys(cover, service, A, C, size, tq, quant)
    inv = _unique_inverse_rows(key)
    n_c = int(inv.max()) + 1
    count, first_idx, members, offsets = _group(inv, n_c)

    fcount = count.astype(np.float64)

    def _mean(x):
        return np.bincount(inv, weights=np.asarray(x, np.float64), minlength=n_c) / fcount

    rep = dict(
        cover=cover.astype(np.int64)[first_idx],
        service=service.astype(np.int64)[first_idx],
        A=_mean(A),
        C=_mean(C),
        size=_mean(size),
        tq=_mean(tq),
    )
    return count, first_idx, members, offsets, rep


#: mirrors ``repro.core.gus.NEG`` — scores below this are "infeasible"
_NEG = -1e30


def hier_assign(
    agg: AggregateClasses,
    gamma: np.ndarray,
    eta: np.ndarray,
    *,
    exact: bool = False,
) -> np.ndarray:
    """Global cloud-contention pass: chunked greedy over class aggregates.

    Merges the per-edge class tables in first-request-index order (the same
    order dense GUS visits their members) and allocates each class in
    chunks: pick the feasible utility-argmax cell (first occurrence on the
    flat ``j * L + l`` axis — GUS's tie-break), fit as many members as the
    shared ``gamma``/``eta`` capacities allow, commit, and re-pick until
    the class is exhausted or nothing fits.  Local cells charge only the
    server's ``gamma``; offload cells also charge the covering edge's
    ``eta`` — the cross-edge coupling this pass exists to reconcile.

    ``exact=True`` consumes members one at a time with float32 capacity
    subtraction, reproducing :func:`repro.core.gus.gus_schedule_np`'s
    arithmetic bit for bit; ``exact=False`` sizes chunks analytically in
    float32 via :func:`hier_cells_np` (the fleet path — one floor-division
    instead of ``count`` updates), with the same IEEE op sequence as the
    XLA and Pallas device backends, so the fleet's host oracle and its
    device program agree bit for bit.

    Returns an ``(n_chunks, 4)`` int64 array of ``(class, j, l, take)`` in
    allocation order.
    """
    if agg.n_classes == 0:
        return np.zeros((0, 4), np.int64)

    if not exact:  # analytic mode: delegate to the (take, start) cell oracle
        order_all = np.argsort(agg.first_idx, kind="stable")
        take, start = hier_cells_np(
            agg.us[order_all], agg.feas[order_all], agg.v[order_all],
            agg.u[order_all], agg.cover[order_all], agg.count[order_all],
            gamma, eta,
        )
        ci, jj, ll = np.nonzero(take > 0)
        if ci.size == 0:
            return np.zeros((0, 4), np.int64)
        # classes allocate strictly in order and within a class ``start`` is
        # the running member offset, so (class position, start) IS the
        # allocation order
        o = np.lexsort((start[ci, jj, ll], ci))
        return np.column_stack(
            [order_all[ci], jj, ll, take[ci, jj, ll]]
        )[o].astype(np.int64)

    gamma = np.asarray(gamma, np.float32).copy()
    eta = np.asarray(eta, np.float32).copy()
    M = gamma.shape[0]
    L = agg.us.shape[-1]
    server = np.arange(M)

    # pass-1 screening: classes infeasible everywhere never enter the queue
    alive = agg.feas.any(axis=(1, 2))
    order = np.argsort(agg.first_idx, kind="stable")
    order = order[alive[order]]

    chunks = []
    for c in order:
        rem = int(agg.count[c])
        s = int(agg.cover[c])
        row_us = agg.us[c]
        row_v = np.asarray(agg.v[c], np.float32)
        row_u = np.asarray(agg.u[c], np.float32)
        local = (server == s)[:, None]
        feas = agg.feas[c]
        while rem > 0:
            ok = feas & (row_v <= gamma[:, None]) & (local | (row_u <= eta[s]))
            if not ok.any():
                break
            flat = int(np.argmax(np.where(ok, row_us, _NEG)))
            j, l = divmod(flat, L)
            vv = row_v[j, l]
            uv = row_u[j, l]
            take = 0
            while take < rem:
                if vv > gamma[j] or (j != s and uv > eta[s]):
                    break
                gamma[j] -= vv
                if j != s:
                    eta[s] -= uv
                take += 1
            if take <= 0:
                break  # float edge: argmax cell passed ``ok`` but fits zero
            chunks.append((int(c), j, l, take))
            rem -= take
    if not chunks:
        return np.zeros((0, 4), np.int64)
    return np.asarray(chunks, np.int64)


def _fit_count_np(budget, cost):
    """``max{t : f32(t * cost) <= budget}`` for f32 scalars, ``cost > 0`` —
    the NumPy twin of :func:`repro.kernels.hier_pallas.fit_count`."""
    one = np.float32(1.0)
    q = np.floor(budget / cost)
    if q * cost > budget:
        q = q - one
    if (q + one) * cost <= budget:
        q = q + one
    return q


def hier_cells_np(
    us: np.ndarray,
    feas: np.ndarray,
    v: np.ndarray,
    u: np.ndarray,
    cover: np.ndarray,
    count: np.ndarray,
    gamma: np.ndarray,
    eta: np.ndarray,
):
    """NumPy oracle for the device hierarchical allocator (analytic mode).

    Classes are processed **in the given order** (callers pre-sort by
    ``first_idx``); ``(take, start)`` are the fixed-shape cell tensors
    described in the module docstring.  All capacity arithmetic is float32
    with the exact op sequence of the XLA scan and the Pallas kernel:
    ``cap`` = the fit count ``max{t : f32(t * cost) <= budget}``
    (:func:`_fit_count_np`), ``take = min(rem, cap_gamma, cap_eta)``,
    ``budget -= f32(take) * cost``.
    Zero-count rows (padding) and classes with no feasible cell are
    skipped without touching the budgets.

    Re-picks of one cell are always consecutive (its utility never changes
    and feasibility is monotone), so accumulated ``take`` spans a
    contiguous member range from its first ``start`` — the property that
    lets a fixed-shape tensor replace the variable-length chunk list.
    """
    us = np.asarray(us, np.float32)
    feas = np.asarray(feas, bool)
    v = np.asarray(v, np.float32)
    u = np.asarray(u, np.float32)
    gamma = np.asarray(gamma, np.float32).copy()
    eta = np.asarray(eta, np.float32).copy()
    C, M, L = us.shape
    take = np.zeros((C, M, L), np.int32)
    start = np.zeros((C, M, L), np.int32)
    server = np.arange(M)
    neg = np.float32(_NEG)
    for c in range(C):
        rem = int(count[c])
        if rem <= 0 or not feas[c].any():
            continue
        s = int(cover[c])
        local = (server == s)[:, None]
        used = 0
        while rem > 0:
            ok = feas[c] & (v[c] <= gamma[:, None]) & (local | (u[c] <= eta[s]))
            if not ok.any():
                break
            flat = int(np.argmax(np.where(ok, us[c], neg)))
            j, l = divmod(flat, L)
            vv = v[c, j, l]
            uv = u[c, j, l]
            t_f = np.float32(rem)
            if vv > 0:
                t_f = min(t_f, _fit_count_np(gamma[j], vv))
            if j != s and uv > 0:
                t_f = min(t_f, _fit_count_np(eta[s], uv))
            t = int(t_f)
            if t < 1:
                break  # float edge: cell passed ``ok`` but fits zero members
            tf32 = np.float32(t)
            gamma[j] = gamma[j] - tf32 * vv
            if j != s:
                eta[s] = eta[s] - tf32 * uv
            if take[c, j, l] == 0:
                start[c, j, l] = used
            take[c, j, l] += t
            used += t
            rem -= t
    return take, start


@jax.jit
def _hier_cells_xla(us, feas, v, u, cover, count, gamma, eta):
    """Jitted XLA implementation of :func:`hier_cells_np`: ``lax.scan``
    over the (pre-sorted, padded) class axis threading the shared budget
    vectors, with an inner ``lax.while_loop`` sizing one chunk per
    iteration.  Bit-identical to the oracle — same f32 op sequence, same
    first-occurrence argmax tie-break."""
    us = jnp.asarray(us, jnp.float32)
    feas = jnp.asarray(feas, bool)
    v = jnp.asarray(v, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    cover = jnp.asarray(cover, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    gamma = jnp.asarray(gamma, jnp.float32)
    eta = jnp.asarray(eta, jnp.float32)
    C, M, L = us.shape
    neg = jnp.float32(_NEG)
    if C == 0:
        z = jnp.zeros((0, M, L), jnp.int32)
        return z, z

    def cls_step(carry, x):
        gamma, eta = carry
        us_c, feas_c, v_c, u_c, s, cnt = x
        is_local = jnp.arange(M, dtype=jnp.int32) == s

        def cond(st):
            return st[-1]

        def body(st):
            rem, gamma, eta, take, start, used, _ = st
            ok = (
                feas_c
                & (v_c <= gamma[:, None])
                & (is_local[:, None] | (u_c <= eta[s]))
            )
            score = jnp.where(ok, us_c, neg).reshape(-1)
            flat = jnp.argmax(score)
            any_ok = score[flat] > neg
            j = (flat // L).astype(jnp.int32)
            l = (flat % L).astype(jnp.int32)
            vv = v_c[j, l]
            uv = u_c[j, l]
            offl = j != s
            rem_f = rem.astype(jnp.float32)
            cap_g = jnp.where(
                vv > 0, fit_count(gamma[j], jnp.where(vv > 0, vv, 1.0)), rem_f
            )
            cap_e = jnp.where(
                offl & (uv > 0),
                fit_count(eta[s], jnp.where(uv > 0, uv, 1.0)),
                rem_f,
            )
            t_f = jnp.minimum(rem_f, jnp.minimum(cap_g, cap_e))
            t = t_f.astype(jnp.int32)
            do = any_ok & (t >= 1)
            tf32 = jnp.where(do, t, 0).astype(jnp.float32)
            gamma = gamma.at[j].add(-(tf32 * vv))
            eta = eta.at[s].add(jnp.where(offl, -(tf32 * uv), 0.0))
            first = take[j, l] == 0
            start = start.at[j, l].set(
                jnp.where(do & first, used, start[j, l])
            )
            take = take.at[j, l].add(jnp.where(do, t, 0))
            used = used + jnp.where(do, t, 0)
            rem = rem - jnp.where(do, t, 0)
            return rem, gamma, eta, take, start, used, do & (rem > 0)

        st0 = (
            cnt,
            gamma,
            eta,
            jnp.zeros((M, L), jnp.int32),
            jnp.zeros((M, L), jnp.int32),
            jnp.int32(0),
            feas_c.any() & (cnt > 0),
        )
        _, gamma, eta, take, start, _, _ = jax.lax.while_loop(cond, body, st0)
        return (gamma, eta), (take, start)

    (_, _), (take, start) = jax.lax.scan(
        cls_step, (gamma, eta), (us, feas, v, u, cover, count)
    )
    return take, start


def _hier_cells_pallas(us, feas, v, u, cover, count, gamma, eta):
    """Fused-Pallas entry: batch-of-1 lift into the hierarchical kernel
    (``vmap`` over the fleet's replication axis lifts it further, exactly
    like the dense GUS kernel).  The interpret flag resolves at trace
    time from the platform, as for the dense kernel."""
    add = lambda x: jnp.asarray(x)[None]  # noqa: E731 — lift to batch of 1
    take, start = hier_cells_pallas(
        add(us), add(feas), add(v), add(u), add(cover), add(count),
        add(gamma), add(eta), interpret=pallas_interpret(),
    )
    return take[0], start[0]


def hier_cells(
    us, feas, v, u, cover, count, gamma, eta, *, backend: Optional[str] = None
):
    """Backend-dispatched analytic allocator over pre-sorted class tensors.

    ``backend`` follows the dense GUS precedence (explicit >
    ``REPRO_GUS_BACKEND`` > ``"xla"``); outputs are bit-identical across
    the NumPy oracle, XLA, and the Pallas kernel (integer tensors, exact
    equality — ``tests/test_hier_parity.py``)."""
    if resolve_gus_backend(backend) == "pallas":
        return _hier_cells_pallas(us, feas, v, u, cover, count, gamma, eta)
    return _hier_cells_xla(us, feas, v, u, cover, count, gamma, eta)


@functools.lru_cache(maxsize=None)
def _hier_backend_impl(resolved: str):
    if resolved == "pallas":
        return partial(hier_cells, backend="pallas")
    return _hier_cells_xla  # the default object existing caches key on


def hier_backend_fn(backend: Optional[str] = None):
    """A stable-identity cells callable for one backend — the hierarchical
    twin of :func:`repro.core.gus.gus_backend_fn`.  The fleet runner's
    compiled-program cache keys on this function's identity, so every
    caller must get the same object per resolved backend."""
    return _hier_backend_impl(resolve_gus_backend(backend))


def deaggregate(agg: AggregateClasses, chunks: np.ndarray, n_requests: int):
    """Map class-level chunks back to per-request ``(j, l)`` assignments.

    Each chunk consumes its class's members in ascending request index —
    the deterministic tie-break that makes hierarchical results reproducible
    and, on lossless classes, identical to dense GUS.  Unallocated members
    stay dropped (``-1``).
    """
    out_j = np.full(n_requests, -1, np.int32)
    out_l = np.full(n_requests, -1, np.int32)
    ptr = agg.offsets[:-1].copy()
    for c, j, l, take in chunks:
        sel = agg.members[ptr[c] : ptr[c] + take]
        out_j[sel] = j
        out_l[sel] = l
        ptr[c] += take
    return out_j, out_l


def make_gus_hier(decimals: Optional[int] = None):
    """A drop-in scheduler callable running GUS over class aggregates.

    ``decimals=None`` (the registered ``gus-hier`` default) keys classes on
    exact row content and allocates in exact mode — bit-parity with dense
    GUS on every frame whose classes are index-contiguous, which includes
    all frames with singleton classes.  Pass ``decimals`` to merge
    near-identical requests (lossy, bounded satisfaction drift).
    """

    def schedule(inst: FlatInstance) -> Assignment:
        n = int(np.asarray(inst.A).shape[0])
        if n == 0:
            z = jnp.zeros(0, jnp.int32)
            return Assignment(z, z)
        agg = aggregate_instance(inst, decimals=decimals)
        chunks = hier_assign(
            agg, np.asarray(inst.gamma), np.asarray(inst.eta), exact=True
        )
        out_j, out_l = deaggregate(agg, chunks, n)
        return Assignment(jnp.asarray(out_j), jnp.asarray(out_l))

    return schedule


def hier_schedule_np(inst: FlatInstance) -> Assignment:
    """Module-level exact-mode entry point (see :func:`make_gus_hier`)."""
    return make_gus_hier()(inst)
