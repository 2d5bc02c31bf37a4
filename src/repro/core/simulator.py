"""Time-slotted edge-cluster simulator — the paper's testbed, virtualized.

Reproduces the Sec. IV testbed protocol:

* users submit requests to their covering edge server's *admission queue*;
* a decision algorithm runs at the end of every time frame (or earlier if the
  queue is full — paper: queue length 4, frame 3000 ms);
* the queuing delay T^q of a request is the measured wait until its frame's
  decision, exactly as in the completion-time model;
* actual communication delays are stochastic (lognormal jitter around
  size/bandwidth — the "wireless channel");
* the scheduler sees only an *estimate* of bandwidth, updated by the paper's
  rule  E[B_{t+1}] = (B_t + B_{t-1}) / 2  from observed transfers;
* per-frame compute/communication capacities (gamma, eta) refresh each frame.

A request is *satisfied* iff its realized completion time <= C_i and the
served variant's accuracy >= A_i (Definition II.1's hard form).

Beyond the paper, four axes are pluggable:

* **workload** — a named :mod:`~repro.core.scenarios` entry shapes arrivals,
  QoS draws, per-frame capacity masks (outages) and mobility;
* **arrival engine** — ``streaming=True`` (or a scenario registered with
  ``streaming=True``) swaps the materialized trace for the bounded-memory
  :class:`~repro.core.streaming.ArrivalStream`, opening long-horizon and
  nonstationary workloads;
* **congestion** — ``SimConfig.congestion``
  (:class:`~repro.core.queueing.CongestionConfig`) makes service times
  load-dependent: over-committed servers carry a backlog across frames,
  realized processing/transfer times inflate with the over-commit ratio,
  and the scheduler sees only the backlog-reduced frame budget.  This is
  the paper's testbed congestion, under which the Happy-* constraint
  relaxations collapse below GUS;
* **decision path** — by default each frame is padded to a fixed shape
  (see :func:`repro.core.instance.pad_instance`) and scheduled by the
  *jitted* ``gus_schedule``; any registered :class:`~repro.core.policies.Policy`
  (GUS variants, the paper's five baselines, the exact ILP oracle) runs on
  the same hot path via ``policy=``; ``gus_schedule_np`` stays available as
  the NumPy parity oracle.

Per-frame policy/simulator state is an explicit
:class:`~repro.core.queueing.PolicyCarry` (PRNG-key chain, per-server
backlogs, EMA load estimates, bandwidth-estimator state) threaded through
``simulate``'s frame loop and — as the ``lax.scan`` carry — through
:func:`simulate_fleet`'s single jitted/vmapped device program.

:func:`simulate_fleet` additionally shards the replication axis across a
1-D ``("rep",)`` device mesh (``devices=``) and can run its frame scan in
bounded-memory windows (``window=``) — both bit-identical to the
single-device, fully-materialized program.  See the function docstring.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import queue as queue_mod
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import QOS_ACC_EDGES, MetricsFrame, MetricsResult
from repro.obs.trace import (
    CAT_BUILD,
    CAT_COMPILE,
    CAT_DISPATCH,
    CAT_GEN,
    CAT_METRICS,
    CAT_SCHED,
    CAT_WAIT,
    Stopwatch,
    instant,
    span,
)

from .gus import Assignment, gus_backend_fn, gus_schedule
from .impairments import (
    AdmissionConfig,
    ImpairmentConfig,
    ResilienceEngine,
    admission_keep,
    apply_queue_cap,
    predicted_inflation,
)
from .instance import FlatInstance, pad_instance, stack_instances
from .options import (
    _UNSET,
    EngineOptions,
    fold_deprecated_kwargs,
    resolve_options,
)
from .policies import Policy, get_policy
from .queueing import (
    CONGESTED_CTIME_LEAVES,
    CongestionConfig,
    comm_inflation,
    committed_loads,
    compute_inflation,
    congested_ctime,
    effective_capacity,
    ema_update,
    fleet_policy_carry,
    frame_metrics,
    init_policy_carry,
    step_backlog,
)
from .satisfaction import (
    SATISFACTION_LEAVES,
    hard_feasible,
    mean_us,
    satisfied_mask,
    us_tensor,
)
from .scenarios import (
    Request,
    RequestColumns,
    Scenario,
    _resolve_rng_mode,
    bucket_arrivals,
    bucket_columns,
    get_scenario,
)
from .streaming import (
    ArrivalStream,
    max_frame_arrivals,
    stream_trace,
    stream_trace_columns,
)

__all__ = [
    "ClusterSpec",
    "SimConfig",
    "SimResult",
    "FleetResult",
    "EngineOptions",
    "simulate",
    "simulate_fleet",
    "demo_cluster_spec",
]


@dataclasses.dataclass
class ClusterSpec:
    """Static cluster description (servers, services, placement, profiles)."""

    n_edge: int
    n_cloud: int
    # per-server
    gamma_frame: np.ndarray       # (M,) compute capacity per frame (chip-ms)
    eta_frame: np.ndarray         # (M,) comm capacity per frame (KB)
    # per (server, service, variant)
    proc_ms: np.ndarray           # (M, K, L) mean processing delay
    placed: np.ndarray            # (M, K, L) bool
    acc: np.ndarray               # (K, L) accuracy (%)
    bandwidth_true: float = 600.0  # bytes/ms, hidden truth the channel jitters around
    cloud_extra_delay: float = 100.0

    @property
    def n_servers(self) -> int:
        return self.n_edge + self.n_cloud

    def is_cloud(self) -> np.ndarray:
        return np.arange(self.n_servers) >= self.n_edge


@dataclasses.dataclass
class SimConfig:
    horizon_ms: float = 120_000.0
    frame_ms: float = 3000.0
    queue_cap: int = 4                # paper: fixed queue length of 4
    arrival_rate_per_s: float = 2.0   # Poisson arrivals per edge server
    # request QoS draws
    acc_req_mean: float = 50.0
    acc_req_std: float = 0.0          # paper testbed: fixed A_i = 50%
    delay_req_ms: float = 53_000.0    # paper testbed: fixed C_i = 53 s
    req_size_lo: float = 20_000.0
    req_size_hi: float = 120_000.0
    channel_sigma: float = 0.25       # lognormal jitter of the wireless channel
    proc_sigma: float = 0.05
    move_prob: float = 0.0            # per-frame user mobility (extensions)
    w_a: float = 1.0
    w_c: float = 1.0
    max_as: float = 100.0
    max_cs: float = 12_000.0
    adapt_max_cs: bool = True         # paper: "we may have to adapt Max_cs"
    bandwidth_init: float = 600.0     # scheduler's initial estimate B_0
    #: load-dependent service times (disabled by default: delays stay
    #: load-independent and every result is bit-identical to the
    #: pre-congestion simulator)
    congestion: CongestionConfig = dataclasses.field(default_factory=CongestionConfig)
    #: network/server fault injection — per-edge link-quality traces and
    #: stochastic MTBF/MTTR server outages (disabled by default: no engine
    #: is built and results are bit-identical to the unimpaired simulator)
    impairments: ImpairmentConfig = dataclasses.field(default_factory=ImpairmentConfig)
    #: admission control — per-server queue caps and deadline-based
    #: shedding (disabled by default, and inert at its defaults even when
    #: enabled; see :class:`repro.core.impairments.AdmissionConfig`)
    admission: AdmissionConfig = dataclasses.field(default_factory=AdmissionConfig)


@dataclasses.dataclass
class SimResult:
    n_requests: int
    n_served: int
    n_satisfied: int
    n_local: int
    n_cloud: int
    n_edge_offload: int
    n_dropped: int
    mean_us: float
    mean_completion_ms: float
    mean_queue_ms: float
    bandwidth_estimates: List[float]
    #: work-accounting of the congestion model (None when disabled):
    #: enqueued/drained/carried chip-ms + KB totals and inflation stats
    congestion_stats: Optional[Dict[str, float]] = None
    #: fault-injection accounting (None unless impairments or admission
    #: control are enabled): requests shed at admission, assignments
    #: refused by the queue cap, frames with a down server
    resilience_stats: Optional[Dict[str, float]] = None
    #: wall-clock seconds per pipeline phase, from the span recorder
    #: (``gen_s`` arrival generation / stream pulls, ``build_s`` frame
    #: instance building, ``sched_s`` scheduler calls, ``realize_s``
    #: realized-delay accounting, ``total_s`` end to end) — the single-run
    #: counterpart of ``FleetResult.gen_s`` / ``dispatch_s``
    timings: Optional[Dict[str, float]] = None
    #: per-decision metric stream (``metrics=True`` only; None otherwise)
    metrics: Optional[MetricsResult] = None

    @property
    def satisfied_pct(self) -> float:
        return 100.0 * self.n_satisfied / max(self.n_requests, 1)

    @property
    def local_pct(self) -> float:
        return 100.0 * self.n_local / max(self.n_requests, 1)

    @property
    def cloud_pct(self) -> float:
        return 100.0 * self.n_cloud / max(self.n_requests, 1)

    @property
    def edge_offload_pct(self) -> float:
        return 100.0 * self.n_edge_offload / max(self.n_requests, 1)

    def as_dict(self) -> Dict[str, float]:
        d = {
            "n_requests": self.n_requests,
            "satisfied_pct": self.satisfied_pct,
            "local_pct": self.local_pct,
            "cloud_pct": self.cloud_pct,
            "edge_offload_pct": self.edge_offload_pct,
            "dropped_pct": 100.0 * self.n_dropped / max(self.n_requests, 1),
            "mean_us": self.mean_us,
            "mean_completion_ms": self.mean_completion_ms,
            "mean_queue_ms": self.mean_queue_ms,
        }
        if self.congestion_stats is not None:
            d["mean_compute_inflation"] = self.congestion_stats["mean_compute_inflation"]
            d["final_backlog_gamma"] = self.congestion_stats["final_backlog_gamma"]
        return d


def _pad_bucket(n: int) -> int:
    """Round the frame's queue length up to a power-of-two bucket (min 4) so
    the jitted scheduler compiles once per bucket, not once per queue length."""
    return max(4, 1 << max(n - 1, 0).bit_length())


def _pad_bucket_fine(n: int) -> int:
    """Bucket schedule for the hierarchical class axis: powers of two up to
    4096, multiples of 1024 above.

    Class counts sit wherever quantization puts them — at 10^5 users/frame
    the mega-city lands near 19k classes, which the power-of-two schedule
    pads to 32768 (≈70% dead rows in every (C, M, L) tensor *and* ≈70%
    dead steps in the allocator's scan over classes).  Above 4096 the waste
    is capped at ~5% instead; the compile-cache cost stays bounded because
    a window's bucket moves only when its class count crosses a 1024
    boundary, and city-scale frames drawn from one arrival process cluster
    tightly."""
    if n <= 4096:
        return max(4, 1 << max(n - 1, 0).bit_length())
    return ((n + 1023) // 1024) * 1024


#: default width of a fleet replication group — the unit of device dispatch
#: in :func:`simulate_fleet`.  One program is compiled per group shape and
#: reused for every group on every device, which is what keeps multi-device
#: results bit-identical to the single-device run.  Fleets with
#: ``n_rep <= FLEET_REP_GROUP`` run as a single group (the legacy layout).
FLEET_REP_GROUP = 8


def _frame_arrays(
    reqs: Sequence[Request], spec: ClusterSpec, cfg: SimConfig, now_ms: float, bw_est: float,
    link=None, lean: bool = False,
) -> Dict[str, np.ndarray]:
    """Numpy request-row tensors for one frame, using the scheduler's
    *estimated* bandwidth for comm delays — shared by
    :func:`_build_frame_instance` and the fleet's batched grid builder.

    ``reqs`` is either a list of :class:`Request` objects or a
    :class:`~repro.core.scenarios.RequestColumns` view (the vectorized
    trace); the columnar branch narrows the same float64 values to float32,
    so the two layouts produce bit-identical tensors from identical draws.

    ``link`` is an optional pair of per-request ``(bandwidth_scale,
    extra_latency_ms)`` arrays from the resilience engine, gathered by each
    request's covering edge: transfer time becomes ``size / (bw * scale) +
    lat``.  ``None`` (impairments off) leaves the formula untouched; at
    amplitude 0 the scale is exactly 1.0 and the latency exactly 0.0, so
    the result is bitwise identical either way.
    """
    M = spec.n_servers
    L = spec.acc.shape[1]
    N = len(reqs)
    is_cloud = spec.is_cloud()

    if isinstance(reqs, RequestColumns):
        cover = reqs.cover.astype(np.int32)
        A = reqs.A.astype(np.float32)
        C = reqs.C.astype(np.float32)
        Tq = (now_ms - reqs.arrival_ms).astype(np.float32)
        size = reqs.size_bytes.astype(np.float32)
        svc = reqs.service.astype(np.int32)
    else:
        cover = np.array([r.cover for r in reqs], np.int32)
        A = np.array([r.A for r in reqs], np.float32)
        C = np.array([r.C for r in reqs], np.float32)
        Tq = np.array([now_ms - r.arrival_ms for r in reqs], np.float32)
        size = np.array([r.size_bytes for r in reqs], np.float32)
        svc = np.array([r.service for r in reqs], np.int32)

    local = cover[:, None] == np.arange(M)[None, :]
    transfer = size[:, None] / bw_est
    if link is not None:
        # bandwidth scales divide the transfer time, extra latency adds;
        # at identity (scale 1.0, lat 0.0) both ops are bitwise no-ops
        bw_scale, extra_lat = link
        transfer = transfer / np.asarray(bw_scale, np.float64)[:, None] \
            + np.asarray(extra_lat, np.float64)[:, None]
    comm = transfer + np.where(is_cloud[None, :], spec.cloud_extra_delay, 0.0)
    comm = np.where(local, 0.0, comm)

    proc = spec.proc_ms[:, svc, :].transpose(1, 0, 2)       # (N, M, L)
    ctime = Tq[:, None, None] + proc + comm[:, :, None]
    if lean:
        # class-grid builder fast path: the candidate gathers/broadcasts
        # (acc, avail, v, u) are pure float32 lookups of spec tensors and
        # are rebuilt on device from these per-row vectors — only ctime's
        # float64 link math must stay host-side to agree bitwise with the
        # request-level paths
        return dict(cover=cover, A=A, C=C, ctime=ctime, svc=svc, size=size)
    avail = spec.placed[:, svc, :].transpose(1, 0, 2)
    # broadcast view, not a copy: every consumer only reads (scatter/slice
    # assignment or jnp.asarray), and skipping the 16MB materialization
    # keeps the producer thread off the critical path
    acc = np.broadcast_to(spec.acc[svc][:, None, :], (N, M, L))
    u = np.where(local[:, :, None], 0.0, (size / 1024.0)[:, None, None])
    return dict(
        cover=cover, A=A, C=C, acc=acc, ctime=ctime, v=proc,
        u=np.broadcast_to(u, (N, M, L)), avail=avail,
    )


def _build_frame_instance(
    reqs: Sequence[Request],
    spec: ClusterSpec,
    cfg: SimConfig,
    now_ms: float,
    bw_est: float,
    max_cs: float,
    gamma=None,
    eta=None,
    link=None,
) -> FlatInstance:
    """FlatInstance for the requests pending in this frame."""
    N = len(reqs)
    arr = _frame_arrays(reqs, spec, cfg, now_ms, bw_est, link=link)
    return FlatInstance(
        cover=jnp.asarray(arr["cover"]),
        A=jnp.asarray(arr["A"]),
        C=jnp.asarray(arr["C"]),
        w_a=jnp.full((N,), cfg.w_a, jnp.float32),
        w_c=jnp.full((N,), cfg.w_c, jnp.float32),
        acc=jnp.asarray(arr["acc"], jnp.float32),
        ctime=jnp.asarray(arr["ctime"], jnp.float32),
        v=jnp.asarray(arr["v"], jnp.float32),
        u=jnp.asarray(arr["u"], jnp.float32),
        avail=jnp.asarray(arr["avail"]),
        gamma=jnp.asarray(spec.gamma_frame if gamma is None else gamma, jnp.float32),
        eta=jnp.asarray(spec.eta_frame if eta is None else eta, jnp.float32),
        max_as=jnp.float32(cfg.max_as),
        max_cs=jnp.float32(max_cs),
    )


def _build_frame_batch(
    frames: List[List[Request]],
    spec: ClusterSpec,
    cfg: SimConfig,
    frame_starts: Sequence[float],
    budgets,
    n_pad: int,
    links=None,
    lean: bool = False,
) -> FlatInstance:
    """Stacked, padded ``FlatInstance`` for a whole grid of frames at once.

    ``links`` (optional, aligned with ``frames`` like ``budgets``) carries
    each frame's per-*server* ``(bandwidth_scale, extra_latency_ms)`` pair
    from the resilience engine; the builder gathers them per request by
    covering edge and hands them to :func:`_frame_arrays`.

    Fills preallocated numpy tensors frame by frame and converts each leaf
    to a device array *once* — the fleet's hot-path grid builder.  With the
    per-frame ``jnp`` round-trips gone, building a 10^3-frame window costs
    milliseconds instead of seconds.  The pad-row fill constants mirror
    :func:`repro.core.instance.pad_instance`, and values are bit-identical
    to stacking ``pad_instance(_build_frame_instance(...), n_pad)`` per
    frame (pinned by the sharded-fleet parity tests through the unchanged
    sequential path).

    A fully columnar grid (every frame a :class:`RequestColumns` — the
    vectorized rng mode) skips the per-frame Python loop: the grid's
    requests are concatenated, :func:`_frame_arrays` runs *once* over all
    of them (its formulas are elementwise given each request's ``now_ms``),
    and one fancy-indexed scatter per leaf writes the real rows — the same
    values the per-frame fill writes, computed by the same elementwise ops.
    """
    F = len(frames)
    M = spec.n_servers
    L = spec.acc.shape[1]
    cover = np.zeros((F, n_pad), np.int32)
    A = np.full((F, n_pad), 1e9, np.float32)     # unreachable accuracy floor
    C = np.full((F, n_pad), -1.0, np.float32)    # already-expired deadline
    w_a = np.zeros((F, n_pad), np.float32)       # padded rows contribute zero US
    w_c = np.zeros((F, n_pad), np.float32)
    # ``lean`` (hierarchical fast path): the four candidate tensors that are
    # pure spec gathers are never materialized on host — (F, 1, 1, 1)
    # dummies hold their slots and the caller rebuilds them on device from
    # the per-row ``svc``/``size`` vectors returned alongside the instance
    big = (F, 1, 1, 1) if lean else (F, n_pad, M, L)
    acc = np.zeros(big, np.float32)
    ctime = np.full((F, n_pad, M, L), 1e9, np.float32)
    v = np.zeros(big, np.float32)
    u = np.zeros(big, np.float32)
    avail = np.zeros(big, bool)
    svc_p = np.zeros((F, n_pad), np.int32) if lean else None
    size_p = np.zeros((F, n_pad), np.float32) if lean else None
    gamma = np.zeros((F, M), np.float32)
    eta = np.zeros((F, M), np.float32)
    for i in range(F):
        g, e = budgets[i]
        gamma[i] = g
        eta[i] = e
    columnar = F > 0 and all(isinstance(b, RequestColumns) for b in frames)
    if columnar:
        lengths = np.fromiter((len(b) for b in frames), np.int64, F)
        nn = int(lengths.sum())
        if nn:
            cat = RequestColumns.concatenate(frames)
            row = np.repeat(np.arange(F), lengths)
            now = np.repeat(
                np.asarray(frame_starts, np.float64) + cfg.frame_ms, lengths
            )
            link = None
            if links is not None:
                cov = cat.cover.astype(np.intp)
                sc = np.stack([l[0] for l in links])  # (F, M)
                la = np.stack([l[1] for l in links])
                link = (sc[row, cov], la[row, cov])
            arr = _frame_arrays(
                cat, spec, cfg, now, spec.bandwidth_true, link=link, lean=lean
            )
            # rows land at columns 0..n_i-1 of their frame by construction
            # (``col`` above is a within-frame arange), so the scatter is
            # really F contiguous slice writes — orders of magnitude fewer
            # index computations than one 12M-element fancy-indexed store
            # when frames hold 10^4+ classes
            starts = np.cumsum(lengths) - lengths
            for i in range(F):
                n_i = int(lengths[i])
                if n_i == 0:
                    continue
                sl = slice(int(starts[i]), int(starts[i]) + n_i)
                cover[i, :n_i] = arr["cover"][sl]
                A[i, :n_i] = arr["A"][sl]
                C[i, :n_i] = arr["C"][sl]
                w_a[i, :n_i] = cfg.w_a
                w_c[i, :n_i] = cfg.w_c
                ctime[i, :n_i] = arr["ctime"][sl]
                if lean:
                    svc_p[i, :n_i] = arr["svc"][sl]
                    size_p[i, :n_i] = arr["size"][sl]
                else:
                    acc[i, :n_i] = arr["acc"][sl]
                    v[i, :n_i] = arr["v"][sl]
                    u[i, :n_i] = arr["u"][sl]
                    avail[i, :n_i] = arr["avail"][sl]
    else:
        for i, (reqs, t0) in enumerate(zip(frames, frame_starts)):
            n = len(reqs)
            if n == 0:
                continue
            link = None
            if links is not None:
                cov = (
                    reqs.cover.astype(np.intp)
                    if isinstance(reqs, RequestColumns)
                    else np.array([r.cover for r in reqs], np.intp)
                )
                sc, la = links[i]
                link = (sc[cov], la[cov])
            arr = _frame_arrays(
                reqs, spec, cfg, t0 + cfg.frame_ms, spec.bandwidth_true,
                link=link, lean=lean,
            )
            cover[i, :n] = arr["cover"]
            A[i, :n] = arr["A"]
            C[i, :n] = arr["C"]
            w_a[i, :n] = cfg.w_a
            w_c[i, :n] = cfg.w_c
            ctime[i, :n] = arr["ctime"]
            if lean:
                svc_p[i, :n] = arr["svc"]
                size_p[i, :n] = arr["size"]
            else:
                acc[i, :n] = arr["acc"]
                v[i, :n] = arr["v"]
                u[i, :n] = arr["u"]
                avail[i, :n] = arr["avail"]
    # numpy leaves on purpose: the fleet slices replication groups on host
    # and device_puts each slice straight onto its target device (jnp ops
    # consume numpy leaves transparently on the metrics path)
    inst = FlatInstance(
        cover=cover,
        A=A,
        C=C,
        w_a=w_a,
        w_c=w_c,
        acc=acc,
        ctime=ctime,
        v=v,
        u=u,
        avail=avail,
        gamma=gamma,
        eta=eta,
        max_as=np.full((F,), cfg.max_as, np.float32),
        max_cs=np.full((F,), cfg.max_cs, np.float32),
    )
    if lean:
        return inst, svc_p, size_p
    return inst


def _apply_mobility_inplace(
    reqs: Sequence[Request], n_edge: int, move_prob: float, rng: np.random.Generator
) -> None:
    """Re-attach each pending request's covering edge with prob ``move_prob``.

    Accepts a Request list or a :class:`RequestColumns` view — the RNG draw
    count (two batches of ``len(reqs)``, nothing when the frame is empty) is
    identical either way, so both trace layouts stay on one draw sequence.
    """
    if move_prob <= 0 or not reqs:
        return
    from .extensions import apply_mobility

    if isinstance(reqs, RequestColumns):
        reqs.cover = apply_mobility(
            reqs.cover.astype(np.int32), n_edge, move_prob, rng
        ).astype(np.int64)
        return
    cov = np.array([r.cover for r in reqs], np.int32)
    cov = apply_mobility(cov, n_edge, move_prob, rng)
    for r, c in zip(reqs, cov):
        r.cover = int(c)


def _frame_budgets(
    spec: ClusterSpec, cfg: SimConfig, scn: Scenario, frame_start_ms: float,
    engine: Optional[ResilienceEngine] = None,
):
    """Fresh per-frame (gamma, eta) budgets, masked by the scenario's
    capacity stream (outages etc.) and — when a resilience engine is active —
    by its stochastic MTBF/MTTR outage stream."""
    g = spec.gamma_frame.astype(np.float64)
    e = spec.eta_frame.astype(np.float64)
    scale = scn.capacity_scale(frame_start_ms, cfg, spec.n_edge, spec.n_servers)
    if scale is not None:
        g = g * scale
        e = e * scale
    if engine is not None:
        up = engine.capacity_scale(int(round(frame_start_ms / cfg.frame_ms)))
        if up is not None:
            g = g * up
            e = e * up
    return g.copy(), e.copy()


def _frame_budgets_batch(
    spec: ClusterSpec, cfg: SimConfig, scn: Scenario,
    frame_starts_ms: np.ndarray,
    engine: Optional[ResilienceEngine] = None,
):
    """Vectorized :func:`_frame_budgets` over a window of frame starts.

    One ``capacity_scale_batch`` call replaces F scalar hook calls — the
    host cost that dominated ``gen_s`` at mega-city frame counts.  Returns
    ``(F, M)`` gamma and eta arrays, bit-identical to per-frame
    :func:`_frame_budgets` calls: the batch hook fills unscaled frames with
    exact ``1.0`` (the f64 multiplicative identity) and the same f64
    multiply order is used either way.
    """
    t = np.asarray(frame_starts_ms, np.float64)
    F = t.size
    g = np.repeat(spec.gamma_frame.astype(np.float64)[None, :], F, axis=0)
    e = np.repeat(spec.eta_frame.astype(np.float64)[None, :], F, axis=0)
    scale = scn.capacity_scale_batch(t, cfg, spec.n_edge, spec.n_servers)
    if scale is not None:
        g = g * scale
        e = e * scale
    if engine is not None:
        for i in range(F):
            up = engine.capacity_scale(int(round(t[i] / cfg.frame_ms)))
            if up is not None:
                g[i] = g[i] * up
                e[i] = e[i] * up
    return g, e


def _resolve_policy(
    scheduler, policy
) -> Optional[Policy]:
    """Normalize the (scheduler, policy) pair to an optional bound Policy.

    Returns the resolved :class:`Policy` when one was requested (by name, as
    a Policy object, or as a name passed positionally through ``scheduler``),
    else ``None`` — meaning "use ``scheduler`` as a raw callable / default".
    """
    if policy is not None:
        if scheduler is not None:
            raise ValueError("pass either scheduler= or policy=, not both")
        return get_policy(policy)
    if isinstance(scheduler, (str, Policy)):
        return get_policy(scheduler)
    return None


def _apply_backend(pol, scheduler, backend):
    """Fold a ``backend=`` request into the (pol, scheduler) pair.

    ``backend`` selects the *implementation* of the default GUS scheduler
    (``"xla"`` jitted loop / ``"pallas"`` fused kernel — bit-identical
    assignments, see :mod:`repro.core.gus`), so it only composes with the
    default scheduler or the explicit ``"gus"`` policy; combining it with a
    different policy or a raw callable is an error, not a silent no-op.
    GUS-cored policies (``happy_*``) follow the ``REPRO_GUS_BACKEND``
    environment variable instead.
    """
    if backend is None:
        return pol, scheduler
    if pol is not None and pol.name != "gus":
        raise ValueError(
            f"backend={backend!r} selects the default GUS scheduler's "
            f"implementation; policy {pol.name!r} does not take it (set "
            "REPRO_GUS_BACKEND to steer GUS-cored policies process-wide)"
        )
    if pol is None and scheduler is not None:
        raise ValueError("pass either scheduler= or backend=, not both")
    return None, gus_backend_fn(backend)


def _fold_hier_scheduler(pol, scheduler, opts, allow_backend=False):
    """Fold ``EngineOptions(scheduler="hierarchical")`` into the (pol,
    scheduler) pair: the hierarchical layout *is* the ``gus-hier`` policy,
    so it composes only with the default scheduler / ``"gus"`` /
    ``"gus-hier"`` — any other policy or a raw callable is an error, not a
    silent override.  ``allow_backend=True`` (the fleet) lets ``backend=``
    through: there it selects the hierarchical allocator's implementation
    (:func:`repro.core.aggregation.hier_backend_fn` — XLA scan or fused
    Pallas kernel, bit-identical cells); :func:`simulate`'s single-frame
    hier path stays host-side, so there it still raises."""
    if pol is None and scheduler is not None:
        raise ValueError(
            "EngineOptions(scheduler='hierarchical') does not compose with "
            "a raw scheduler callable; drop one of the two"
        )
    if opts.backend is not None and not allow_backend:
        raise ValueError(
            f"backend={opts.backend!r} with "
            "EngineOptions(scheduler='hierarchical') selects the device "
            "allocator, which only the fleet path runs — use simulate_fleet "
            "(simulate's hier path is host-side)"
        )
    if pol is not None and pol.name not in ("gus", "gus-hier"):
        raise ValueError(
            "EngineOptions(scheduler='hierarchical') maps to the 'gus-hier' "
            f"policy; it does not compose with policy {pol.name!r}"
        )
    return get_policy("gus-hier"), None


class _ArrivalSource:
    """Uniform pull interface over the two arrival engines.

    *Materialized* (the default) keeps the legacy semantics and RNG
    consumption bit-for-bit: the full trace is drawn up front from the
    simulator's own generator.  *Streaming* wraps an
    :class:`~repro.core.streaming.ArrivalStream` — memory stays bounded and
    ``n_total`` counts submissions as they are emitted.
    """

    def __init__(self, reqs=None, stream: Optional[ArrivalStream] = None,
                 limit: Optional[int] = None):
        self._reqs = reqs
        self._idx = 0
        self._stream = stream
        self._limit = limit
        self._emitted = 0

    def pull(self, t_ms: float) -> List[Request]:
        """All not-yet-pulled arrivals with ``arrival_ms < t_ms``."""
        if self._stream is None:
            out = []
            while self._idx < len(self._reqs) and self._reqs[self._idx].arrival_ms < t_ms:
                out.append(self._reqs[self._idx])
                self._idx += 1
            return out
        if self._limit is not None and self._emitted >= self._limit:
            return []
        out = self._stream.take_until(t_ms)
        if self._limit is not None and self._emitted + len(out) > self._limit:
            out = out[: self._limit - self._emitted]
        self._emitted += len(out)
        return out

    @property
    def exhausted(self) -> bool:
        if self._stream is None:
            return self._idx >= len(self._reqs)
        return self._stream.exhausted or (
            self._limit is not None and self._emitted >= self._limit
        )

    @property
    def n_total(self) -> int:
        """Total submissions (call after the run for the streaming source)."""
        return len(self._reqs) if self._stream is None else self._emitted


def simulate(
    spec: ClusterSpec,
    cfg: SimConfig,
    scheduler: Optional[Callable[[FlatInstance], Assignment]] = None,
    *,
    policy: Union[str, Policy, None] = None,
    scenario: Union[str, Scenario] = "paper-default",
    seed: int = 0,
    n_requests: Optional[int] = None,
    options: Optional[EngineOptions] = None,
    streaming=_UNSET,
    rng_mode=_UNSET,
    backend=_UNSET,
    metrics=_UNSET,
) -> SimResult:
    """Run the virtual testbed.

    ``options`` is the consolidated engine configuration
    (:class:`~repro.core.options.EngineOptions`); the per-call keywords
    below (``streaming`` / ``rng_mode`` / ``backend`` / ``metrics``) are
    *deprecated aliases* that build the same object — they emit a
    :class:`DeprecationWarning` and raise when combined with an explicit
    ``options=``.  Fleet-only fields (``window`` / ``prefetch`` /
    ``devices`` / ``rep_group``) are ignored here, so one options value can
    drive both entry points.  Unset fields resolve along **explicit > env
    var > scenario default** (:func:`~repro.core.options.resolve_options`).

    ``EngineOptions(scheduler="hierarchical")`` swaps the dense per-request
    grid for the class-aggregate path (:mod:`repro.core.aggregation`) — it
    maps to the ``"gus-hier"`` policy and composes with the default
    scheduler / ``policy="gus"`` / ``policy="gus-hier"`` only.

    ``metrics=True`` additionally records one
    :class:`~repro.obs.metrics.MetricsFrame` per scheduling decision
    (per-server utilization/backlog, shed/refusal counts, per-QoS-class
    satisfaction, assignment tiers) into ``SimResult.metrics`` — computed
    from the *same* counters as the aggregate result, so the stream's
    totals match the ``SimResult`` exactly.  Single-run rows report the
    backlog *entering* each decision (the fleet's scan rows report the
    carried backlog after the frame).  With ``metrics=False`` (default)
    nothing extra runs and results are bit-identical to the
    pre-telemetry simulator.  ``SimResult.timings`` always carries the
    span-derived phase timings (generation / build / schedule / realize).

    ``backend`` picks the default GUS scheduler's implementation on the
    padded hot path (``"xla"`` jitted loop — the default — or ``"pallas"``
    fused kernel; assignments are bit-identical, see :mod:`repro.core.gus`).
    It composes only with the default scheduler / the ``"gus"`` policy.

    ``policy`` names a registered :class:`~repro.core.policies.Policy`
    (``"gus"``, ``"gus-ordered"``, the five baselines, ``"ilp"``,
    ``"lp-bound"``, or any custom registration); per-policy state rides an
    explicit :class:`~repro.core.queueing.PolicyCarry` threaded through the
    frame loop — ``random`` gets a fresh PRNG key per decision split from
    the carry's chain (seeded by ``seed``), a ``stateful`` policy receives
    the whole carry (backlogs, EMA load, its own key) and returns an
    updated one, and the ``ilp`` oracle schedules unpadded frames on the
    host.  Alternatively ``scheduler`` passes a raw callable FlatInstance
    -> Assignment (a policy name is also accepted positionally); the
    default is the *jitted* ``gus_schedule``.  Every frame's queue is
    padded to a power-of-two bucket with infeasible rows
    (:func:`pad_instance`), so the jitted path compiles once per bucket and
    returns the same assignments as the NumPy oracle on the real rows.

    ``scenario`` names a registered workload (see
    :func:`repro.core.scenarios.list_scenarios`) shaping arrivals, QoS,
    per-frame capacity masks and mobility; ``"paper-default"`` reproduces the
    paper's Sec. IV workload bit-for-bit.

    ``streaming`` selects the arrival engine: ``None`` defers to
    ``scenario.streaming``, ``True`` forces the bounded-memory
    :class:`~repro.core.streaming.ArrivalStream` (long horizons), ``False``
    forces the legacy materialized trace.

    ``rng_mode`` selects the arrival generator's draw discipline (``None``
    defers to ``scenario.rng_mode``): ``"paper-default"`` is the frozen
    per-request order — every historical trace bit-for-bit —
    ``"vectorized"`` draws the same process in numpy batches (~10x faster,
    different RNG consumption, so distributions match but individual
    traces differ; deterministic given the seed either way).

    With ``cfg.congestion.enabled``, service times become load-dependent:
    each server carries a work backlog across frames, the scheduler sees
    only the backlog-reduced budget, and realized processing/transfer times
    inflate with the over-commit ratio (see :mod:`repro.core.queueing`).

    If ``n_requests`` is given, the arrival process stops after that many
    submissions (the paper's x-axis in Fig. 1(e)-(h) is total #requests).
    """
    opts = fold_deprecated_kwargs(
        options,
        dict(streaming=streaming, rng_mode=rng_mode, backend=backend,
             metrics=metrics),
        caller="simulate",
    )
    scn = get_scenario(scenario)
    opts = resolve_options(opts, scenario=scn)
    metrics = bool(opts.metrics)
    pol = _resolve_policy(scheduler, policy)
    if opts.scheduler == "hierarchical":
        pol, scheduler = _fold_hier_scheduler(pol, scheduler, opts)
    pol, scheduler = _apply_backend(pol, scheduler, opts.backend)
    pad = True
    stateful = False
    needs_key = False
    if pol is not None:
        scheduler = pol.bind(spec.n_edge, spec.n_servers)
        pad = pol.pad
        stateful = pol.stateful
        needs_key = pol.needs_key and not pol.stateful
    elif scheduler is None:
        scheduler = gus_schedule
    ccfg = cfg.congestion
    acfg = cfg.admission
    rng = np.random.default_rng(seed)
    M, K, L = spec.proc_ms.shape
    move_prob = cfg.move_prob if scn.move_prob is None else scn.move_prob
    engine = (
        ResilienceEngine(cfg.impairments, spec.n_edge, M)
        if cfg.impairments.enabled else None
    )

    sw = Stopwatch()
    t_run0 = time.perf_counter()

    # --- arrivals (materialized trace, or bounded-memory stream) -------------
    use_stream = opts.streaming
    mode = opts.rng_mode
    if use_stream:
        source = _ArrivalSource(
            stream=ArrivalStream(scn, seed, spec.n_edge, K, cfg, rng_mode=mode),
            limit=n_requests,
        )
    else:
        with sw.span("sim/generate_trace", CAT_GEN):
            reqs = scn.generate_arrivals(rng, spec.n_edge, K, cfg, rng_mode=mode)
        if n_requests is not None:
            reqs = reqs[:n_requests]
        source = _ArrivalSource(reqs=reqs)

    # --- explicit state carry ------------------------------------------------
    # B_{t-1}, B_t for the EMA bandwidth rule + the congestion backlogs; the
    # PRNG chain for needs_key/stateful policies lives in carry.key.
    carry = init_policy_carry(M, seed=seed, bandwidth_init=cfg.bandwidth_init)
    bw_prev = bw_cur = cfg.bandwidth_init
    bw_log = [bw_cur]
    max_cs = cfg.max_cs

    n_served = n_sat = n_local = n_cloud = n_eo = n_drop = 0
    us_sum = 0.0
    comp_sum = 0.0
    q_sum = 0.0
    pending: List[Request] = []
    buffer: deque = deque()
    t = 0.0
    is_cloud = spec.is_cloud()

    # per-decision metric rows (metrics=True only)
    m_rows: List[MetricsFrame] = []
    m_times: List[float] = []
    m_qos_edges = np.asarray(QOS_ACC_EDGES, np.float64)
    m_nq = len(QOS_ACC_EDGES) + 1

    # congestion state (numpy, float64 like the budgets)
    backlog_g = np.zeros(M)
    backlog_e = np.zeros(M)
    committed_g = np.zeros(M)
    committed_e = np.zeros(M)
    drained_g = drained_e = 0.0
    infl_sum = 0.0
    infl_max = 1.0
    infl_n = 0

    def _drain(backlog, committed, budget):
        """One frame-boundary backlog step; returns (new_backlog, drained).

        Same formula as :func:`repro.core.queueing.step_backlog` (which the
        fleet's scan uses), kept in float64 numpy for the host loop — the
        fleet-vs-sequential parity test pins the two implementations to
        each other."""
        new = np.maximum(backlog + committed - budget * ccfg.drain, 0.0)
        return new, float(np.sum(backlog + committed - new))

    # capacity budgets deplete WITHIN a wall-clock frame (queue-full decisions
    # fire early but do not refresh gamma/eta — they share the frame budget)
    frame_budget_g, frame_budget_e = _frame_budgets(spec, cfg, scn, 0.0, engine=engine)
    rem_gamma = frame_budget_g.copy()
    rem_eta = frame_budget_e.copy()
    frame_boundary = cfg.frame_ms
    n_shed = n_refused = 0
    frames_down = 0

    while t < cfg.horizon_ms + 10 * cfg.frame_ms:
        frame_end = t + cfg.frame_ms
        # admit arrivals in this frame; queue_cap per covering server
        qlen = {e: sum(1 for r in pending if r.cover == e) for e in range(spec.n_edge)}
        early_close = None
        with sw.span("sim/arrival_pull", CAT_GEN):
            buffer.extend(source.pull(frame_end))
        while buffer:
            r = buffer[0]
            if qlen.get(r.cover, 0) >= cfg.queue_cap:
                # queue full -> decision fires early (paper testbed behaviour)
                early_close = r.arrival_ms
                break
            pending.append(buffer.popleft())
            qlen[r.cover] = qlen.get(r.cover, 0) + 1
        decision_time = early_close if early_close is not None else frame_end
        if decision_time >= frame_boundary:  # new wall-clock frame: budgets refresh
            frame_boundary += cfg.frame_ms * np.ceil(
                (decision_time - frame_boundary + 1e-9) / cfg.frame_ms
            )
            if ccfg.enabled:
                ema = ema_update(
                    carry.ema_util, jnp.asarray(committed_g, jnp.float32),
                    jnp.asarray(frame_budget_g, jnp.float32), ccfg,
                )
                backlog_g, dg = _drain(backlog_g, committed_g, frame_budget_g)
                backlog_e, de = _drain(backlog_e, committed_e, frame_budget_e)
                drained_g += dg
                drained_e += de
                committed_g = np.zeros(M)
                committed_e = np.zeros(M)
                carry = dataclasses.replace(
                    carry,
                    backlog_gamma=jnp.asarray(backlog_g, jnp.float32),
                    backlog_eta=jnp.asarray(backlog_e, jnp.float32),
                    ema_util=ema,
                )
            frame_budget_g, frame_budget_e = _frame_budgets(
                spec, cfg, scn, frame_boundary - cfg.frame_ms, engine=engine
            )
            if ccfg.enabled:
                rem_gamma = np.maximum(frame_budget_g - backlog_g, 0.0)
                rem_eta = np.maximum(frame_budget_e - backlog_e, 0.0)
            else:
                rem_gamma = frame_budget_g.copy()
                rem_eta = frame_budget_e.copy()

        if pending:
            _apply_mobility_inplace(pending, spec.n_edge, move_prob, rng)
            bw_est = 0.5 * (bw_cur + bw_prev)  # E[B_{t+1}] = (B_t + B_{t-1})/2
            n_real = len(pending)
            link = None
            link_scale = link_lat = None
            if engine is not None:
                # the wall-clock frame the decision belongs to indexes the
                # impairment streams (early-close decisions share it)
                fi = int(round(frame_boundary / cfg.frame_ms)) - 1
                link_scale, link_lat = engine.link_frame(fi)
                up_now = engine.server_up(fi)
                frames_down += int((up_now < 1.0).any())
                cov = np.array([r.cover for r in pending], np.intp)
                link = (link_scale[cov], link_lat[cov])
                carry = dataclasses.replace(
                    carry,
                    link_bw=jnp.asarray(link_scale, jnp.float32),
                    server_up=jnp.asarray(up_now),
                )
            if metrics:
                # deltas of the run counters across this decision become the
                # MetricsFrame row; backlog is sampled *entering* the decision
                m_shed0, m_ref0 = n_shed, n_refused
                m_served0, m_sat0 = n_served, n_sat
                m_local0, m_cloud0, m_eo0 = n_local, n_cloud, n_eo
                m_us0 = us_sum
                m_backlog_g = backlog_g.astype(np.float32)
                m_backlog_e = backlog_e.astype(np.float32)
                m_qos_cnt = np.zeros(m_nq, np.int32)
                m_qos_sat = np.zeros(m_nq, np.int32)
                m_w = np.zeros(M)
                m_c = np.zeros(M)
            with sw.span("sim/frame_build", CAT_BUILD):
                inst = _build_frame_instance(
                    pending, spec, cfg, decision_time, bw_est, max_cs,
                    gamma=rem_gamma, eta=rem_eta, link=link,
                )
            if acfg.enabled and acfg.shed:
                # deadline shedding against the pre-frame (backlog-only)
                # inflation estimate — full budgets, like the fleet scan
                phi_pc, phi_pe = predicted_inflation(
                    jnp.asarray(backlog_g, jnp.float32),
                    jnp.asarray(backlog_e, jnp.float32),
                    jnp.asarray(frame_budget_g, jnp.float32),
                    jnp.asarray(frame_budget_e, jnp.float32),
                    ccfg,
                )
                tq_arr = jnp.asarray(
                    [decision_time - r.arrival_ms for r in pending], jnp.float32
                )
                keep = admission_keep(inst, tq_arr, phi_pc, phi_pe)
                n_shed += n_real - int(np.asarray(keep).sum())
                inst = dataclasses.replace(
                    inst, avail=inst.avail & keep[:, None, None]
                )
            # fixed-shape hot path: pad to a bucket so jitted schedulers
            # compile once per bucket; padded rows are infeasible -> dropped.
            # Non-padding policies (the ILP oracle) see the raw frame.
            frame_inst = pad_instance(inst, _pad_bucket(n_real)) if pad else inst
            with sw.span("sim/schedule", CAT_SCHED, n=n_real):
                if stateful:
                    assign, carry = scheduler(frame_inst, carry)
                elif needs_key:
                    # split order matches the legacy chain:
                    # (next, sub) = split(key)
                    nxt, sub = jax.random.split(carry.key)
                    carry = dataclasses.replace(carry, key=nxt)
                    assign = scheduler(frame_inst, sub)
                else:
                    assign = scheduler(frame_inst)
                # materialization syncs with the device, so the block times
                # the actual scheduler compute, not just its dispatch
                jv = np.asarray(assign.j)[:n_real]
                lv = np.asarray(assign.l)[:n_real]
            if acfg.enabled:
                # queue cap: refuse assignments to servers whose carried
                # backlog exceeds the cap (full frame budgets, like the
                # fleet scan); with the default inf cap nothing changes
                cov = np.array([r.cover for r in pending], np.intp)
                with np.errstate(invalid="ignore"):
                    over_c = backlog_g >= acfg.queue_cap_mult * frame_budget_g
                    over_e = backlog_e >= acfg.queue_cap_mult * frame_budget_e
                jc = np.maximum(jv, 0)
                refuse = (jv >= 0) & (
                    over_c[jc] | ((jv != cov) & over_e[cov])
                )
                n_refused += int(refuse.sum())
                jv = np.where(refuse, -1, jv)

            with sw.span("sim/realize", CAT_METRICS, n=n_real):
                # pass 1 — capacity commit (shared frame budget + backlog
                # growth)
                for idx, r in enumerate(pending):
                    j, l = int(jv[idx]), int(lv[idx])
                    if j < 0:
                        continue
                    local = j == r.cover
                    rem_gamma[j] -= spec.proc_ms[j, r.service, l]
                    committed_g[j] += spec.proc_ms[j, r.service, l]
                    if metrics:
                        m_w[j] += spec.proc_ms[j, r.service, l]
                    if not local:
                        rem_eta[r.cover] -= r.size_bytes / 1024.0
                        committed_e[r.cover] += r.size_bytes / 1024.0
                        if metrics:
                            m_c[r.cover] += r.size_bytes / 1024.0

                # the whole decision batch shares one inflation factor,
                # computed from the wall-clock frame's committed-so-far load
                # (matches the fleet's frame-synchronous semantics when
                # queue_cap never trips)
                if ccfg.enabled:
                    phi_c = np.asarray(
                        compute_inflation(backlog_g + committed_g, frame_budget_g, ccfg)
                    )
                    phi_e = np.asarray(
                        comm_inflation(backlog_e + committed_e, frame_budget_e, ccfg)
                    )
                    infl_sum += float(phi_c.sum())
                    infl_max = max(infl_max, float(phi_c.max()), float(phi_e.max()))
                    infl_n += M

                # pass 2 — realized delays and stats (RNG draw order
                # unchanged)
                observed_bw = []
                for idx, r in enumerate(pending):
                    j, l = int(jv[idx]), int(lv[idx])
                    if metrics:
                        m_cls = int(np.searchsorted(m_qos_edges, r.A, side="right"))
                        m_qos_cnt[m_cls] += 1
                    if j < 0:
                        n_drop += 1
                        continue
                    n_served += 1
                    local = j == r.cover
                    # realized delays
                    proc = spec.proc_ms[j, r.service, l] * rng.lognormal(0.0, cfg.proc_sigma)
                    if local:
                        comm = 0.0
                    else:
                        bw_real = spec.bandwidth_true * rng.lognormal(0.0, cfg.channel_sigma)
                        extra = 0.0
                        if engine is not None:  # the realized channel is impaired too
                            # plain-float arithmetic keeps the downstream
                            # accumulator dtypes identical to the unimpaired path
                            bw_real = bw_real * float(link_scale[r.cover])
                            extra = float(link_lat[r.cover])
                        comm = r.size_bytes / bw_real + extra + (
                            spec.cloud_extra_delay if is_cloud[j] else 0.0
                        )
                        # the estimator observes the *channel* (uninflated
                        # transfer, net of the link's known extra latency)
                        observed_bw.append(r.size_bytes / max(comm - extra - (spec.cloud_extra_delay if is_cloud[j] else 0.0), 1e-6))
                    if ccfg.enabled:
                        proc = proc * phi_c[j]
                        comm = comm * phi_e[r.cover]
                    tq = decision_time - r.arrival_ms
                    ct = tq + proc + comm
                    acc = spec.acc[r.service, l]
                    sat = (ct <= r.C) and (acc >= r.A)
                    if metrics and sat:
                        m_qos_sat[m_cls] += 1
                    n_sat += int(sat)
                    n_local += int(local)
                    n_cloud += int((not local) and is_cloud[j])
                    n_eo += int((not local) and (not is_cloud[j]))
                    us_sum += cfg.w_a * (acc - r.A) / cfg.max_as + cfg.w_c * (r.C - ct) / max_cs
                    comp_sum += ct
                    q_sum += tq
                    if cfg.adapt_max_cs:
                        max_cs = max(max_cs, ct)
                pending = []
                if observed_bw:
                    bw_prev, bw_cur = bw_cur, float(np.mean(observed_bw))
                    bw_log.append(0.5 * (bw_cur + bw_prev))
                    carry = dataclasses.replace(
                        carry, bw_prev=jnp.float32(bw_prev), bw_cur=jnp.float32(bw_cur)
                    )
            if metrics:
                with np.errstate(invalid="ignore"):
                    m_ug = np.where(
                        frame_budget_g > 0.0,
                        m_w / np.maximum(frame_budget_g, 1e-9), 0.0,
                    )
                    m_ue = np.where(
                        frame_budget_e > 0.0,
                        m_c / np.maximum(frame_budget_e, 1e-9), 0.0,
                    )
                m_rows.append(MetricsFrame(
                    n_arrivals=np.int32(n_real),
                    n_served=np.int32(n_served - m_served0),
                    n_satisfied=np.int32(n_sat - m_sat0),
                    n_shed=np.int32(n_shed - m_shed0),
                    n_refused=np.int32(n_refused - m_ref0),
                    tier_hist=np.array(
                        [n_local - m_local0, n_eo - m_eo0, n_cloud - m_cloud0],
                        np.int32,
                    ),
                    qos_sat=m_qos_sat,
                    qos_count=m_qos_cnt,
                    util_gamma=m_ug.astype(np.float32),
                    util_eta=m_ue.astype(np.float32),
                    backlog_gamma=m_backlog_g,
                    backlog_eta=m_backlog_e,
                    us_sum=np.float32(us_sum - m_us0),
                ))
                m_times.append(decision_time)

        t = decision_time if early_close is not None else frame_end
        if source.exhausted and not buffer and not pending:
            break

    congestion_stats = None
    if ccfg.enabled:
        # flush the last frame's committed work through one more drain step so
        # the conservation identity (enqueued == drained + carried) closes
        backlog_g, dg = _drain(backlog_g, committed_g, frame_budget_g)
        backlog_e, de = _drain(backlog_e, committed_e, frame_budget_e)
        drained_g += dg
        drained_e += de
        congestion_stats = {
            "work_enqueued_gamma": drained_g + float(backlog_g.sum()),
            "work_drained_gamma": drained_g,
            "work_enqueued_eta": drained_e + float(backlog_e.sum()),
            "work_drained_eta": drained_e,
            "final_backlog_gamma": float(backlog_g.sum()),
            "final_backlog_eta": float(backlog_e.sum()),
            "mean_compute_inflation": (infl_sum / infl_n) if infl_n else 1.0,
            "max_inflation": infl_max,
        }

    resilience_stats = None
    if engine is not None or acfg.enabled:
        resilience_stats = {
            "n_shed": float(n_shed),
            "n_refused": float(n_refused),
            "frames_with_down_server": float(frames_down),
        }

    n_total = source.n_total
    timings = {
        "gen_s": sw.total("sim/generate_trace", "sim/arrival_pull"),
        "build_s": sw.total("sim/frame_build"),
        "sched_s": sw.total("sim/schedule"),
        "realize_s": sw.total("sim/realize"),
        "total_s": time.perf_counter() - t_run0,
    }
    mres = None
    if metrics:
        mres = MetricsResult.from_rows(
            m_rows, m_times, spec.n_edge, cfg.frame_ms
        )
    return SimResult(
        n_requests=n_total,
        n_served=n_served,
        n_satisfied=n_sat,
        n_local=n_local,
        n_cloud=n_cloud,
        n_edge_offload=n_eo,
        n_dropped=n_total - n_served,
        mean_us=us_sum / max(n_total, 1),
        mean_completion_ms=comp_sum / max(n_served, 1),
        mean_queue_ms=q_sum / max(n_served, 1),
        bandwidth_estimates=bw_log,
        congestion_stats=congestion_stats,
        resilience_stats=resilience_stats,
        timings=timings,
        metrics=mres,
    )


# ---------------------------------------------------------------------------
# Vectorized Monte-Carlo fleet runner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FleetResult:
    """Aggregate of R independent replications scheduled in one device program."""

    n_rep: int
    n_frames: int                  # frames per replication
    n_requests: int                # total across all replications
    n_served: int
    satisfied_per_rep: np.ndarray  # (R,) satisfied-% per replication
    mean_us_per_rep: np.ndarray    # (R,) mean US over that replication's requests
    #: (R, M) carried compute backlog after the last frame (None when the
    #: congestion model is disabled)
    final_backlog_per_rep: Optional[np.ndarray] = None
    #: mean compute-inflation factor across (rep, frame, server) cells
    mean_compute_inflation: float = 1.0
    #: devices the replication axis was sharded across (1 = unsharded)
    n_devices: int = 1
    #: frames per scan window (== n_frames when fully materialized)
    window: Optional[int] = None
    #: wall-clock seconds spent inside the jitted fleet programs (group
    #: dispatch + device compute + result materialization) — the phase
    #: device sharding accelerates; host-side arrival generation and
    #: metrics are excluded
    dispatch_s: float = 0.0
    #: wall-clock seconds the pipeline was *blocked* on host-side arrival
    #: generation + frame-grid building: the up-front trace/pre-pass cost
    #: plus, per window, either the inline build time (``prefetch=0``) or
    #: the time spent waiting on the producer's queue (``prefetch>0`` —
    #: build work hidden behind device compute never shows up here)
    gen_s: float = 0.0
    #: producer-queue depth the run used (0 = serial single-thread build)
    prefetch: int = 0
    #: per-span wall-clock totals (float seconds) from the run's
    #: :class:`~repro.obs.trace.Stopwatch` — ``gen_s``/``dispatch_s`` above
    #: are derived from these same spans, so the two views can never
    #: disagree — plus its counters, which are integer counts, not seconds:
    #: keys ending ``_bytes`` (``fleet/h2d_bytes``,
    #: ``fleet/accounting_h2d_bytes``) and ``/rows`` (``fleet/rows``)
    timings: Optional[Dict[str, Union[float, int]]] = None
    #: per-(rep, frame) metric stream (``metrics=True`` only; None otherwise)
    metrics: Optional[MetricsResult] = None

    @property
    def satisfied_pct(self) -> float:
        return float(np.mean(self.satisfied_per_rep))

    @property
    def satisfied_std(self) -> float:
        return float(np.std(self.satisfied_per_rep))

    @property
    def mean_us(self) -> float:
        return float(np.mean(self.mean_us_per_rep))

    def as_dict(self) -> Dict[str, float]:
        d = {
            "n_rep": self.n_rep,
            "n_requests": self.n_requests,
            "n_devices": self.n_devices,
            "satisfied_pct": self.satisfied_pct,
            "satisfied_std": self.satisfied_std,
            "served_pct": 100.0 * self.n_served / max(self.n_requests, 1),
            "mean_us": self.mean_us,
        }
        if self.final_backlog_per_rep is not None:
            d["mean_compute_inflation"] = self.mean_compute_inflation
            d["final_backlog_gamma"] = float(self.final_backlog_per_rep.sum(-1).mean())
        return d


def _resolve_fleet_devices(devices: Optional[int], n_rep: int) -> int:
    """Resolve ``simulate_fleet``'s ``devices=`` argument to a shard count.

    ``None`` uses every local device (capped at ``n_rep``: a mesh longer
    than the replication axis only schedules padding).  Asking for more
    devices than ``jax.local_device_count()`` is an error, never a silent
    single-device fallback.
    """
    avail = jax.local_device_count()
    if devices is None:
        return max(1, min(avail, n_rep))
    devices = int(devices)
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if devices > avail:
        raise ValueError(
            f"simulate_fleet requested devices={devices} but only {avail} "
            "local device(s) are visible; launch with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for virtual "
            "CPU devices, or lower devices="
        )
    return devices


class _RepFrameSource:
    """One replication's per-frame request buckets, materialized or lazy.

    *Materialized* reproduces the legacy fleet generation bit-for-bit: one
    ``default_rng(seed + rep)`` drives ``generate_arrivals`` (or the trace
    comes from :func:`stream_trace`) and then the per-frame mobility draws.
    *Lazy* holds an :class:`ArrivalStream` and draws each frame's bucket on
    demand, so a windowed fleet never materializes more than one window of
    requests — the stream's chunking invariance makes the buckets (and the
    mobility draw order) identical either way.

    In ``rng_mode="vectorized"`` the materialized trace stays columnar
    (:class:`RequestColumns` buckets) end to end — the grid builder fills
    frames from array slices and per-request Python objects never exist;
    the lazy stream uses the chunk-buffered vectorized engine.  Columnar
    and lazy buckets carry the same values for the same seed (one chunk
    code path underneath), so windowed==materialized holds in both modes.
    """

    def __init__(
        self, scn, rep_seed, n_edge, n_services, cfg, T, use_stream, lazy,
        rng_mode="paper-default",
    ):
        self.cfg = cfg
        self.n_edge = n_edge
        self.move_prob = cfg.move_prob if scn.move_prob is None else scn.move_prob
        self.rng = np.random.default_rng(rep_seed)
        self.stream: Optional[ArrivalStream] = None
        self.buckets = None  # List[List[Request]] | List[RequestColumns]
        vectorized = rng_mode == "vectorized"
        if lazy:
            self.stream = ArrivalStream(
                scn, rep_seed, n_edge, n_services, cfg, rng_mode=rng_mode
            )
        elif vectorized:
            if use_stream:
                cols = stream_trace_columns(scn, rep_seed, n_edge, n_services, cfg)
            else:
                cols = scn.generate_arrivals_columns(self.rng, n_edge, n_services, cfg)
            self.buckets = bucket_columns(cols, cfg.frame_ms, T)
        else:
            if use_stream:
                reqs = stream_trace(scn, rep_seed, n_edge, n_services, cfg)
            else:
                reqs = scn.generate_arrivals(self.rng, n_edge, n_services, cfg)
            self.buckets = bucket_arrivals(reqs, cfg.frame_ms, T)
        self._next = 0

    @property
    def max_bucket(self) -> int:
        """Largest per-frame bucket (materialized sources only)."""
        return max((len(b) for b in self.buckets), default=0)

    def take(self, upto_frame: int) -> List[List[Request]]:
        """Buckets for frames ``[next, upto_frame)``, mobility applied in
        frame order (the rep's single rng keeps the legacy draw sequence)."""
        out = []
        for tf in range(self._next, upto_frame):
            if self.buckets is not None:
                b = self.buckets[tf]
            else:
                b = self.stream.take_until((tf + 1) * self.cfg.frame_ms)
            _apply_mobility_inplace(b, self.n_edge, self.move_prob, self.rng)
            out.append(b)
        self._next = upto_frame
        return out


@functools.lru_cache(maxsize=None)
def _bound_policy_impl(pol: Policy, n_edge: int, n_servers: int):
    return pol.bind(n_edge, n_servers)


def _bound_policy(pol: Policy, n_edge: int, n_servers: int):
    """``pol.bind`` with a stable identity across ``simulate_fleet`` calls —
    the bound function keys the compiled-runner cache below, so repeated
    fleet calls (benchmark sweeps!) reuse the compiled program instead of
    re-tracing and re-compiling every time.  A cache miss drops an instant
    event on an active trace: binds front-run a jit compile, so the marks
    line up with the slow first window."""
    before = _bound_policy_impl.cache_info().misses
    fn = _bound_policy_impl(pol, n_edge, n_servers)
    if _bound_policy_impl.cache_info().misses > before:
        instant("compile/bind_policy", CAT_COMPILE, policy=pol.name)
    return fn


@functools.lru_cache(maxsize=128)
def _fleet_runner_impl(
    fn, stateful: bool, needs_key: bool, ccfg: CongestionConfig,
    acfg: AdmissionConfig, impaired: bool, metrics: bool, n_edge: int,
):
    """The fleet's jitted vmap-over-reps-of-scan-over-frames runner, cached
    by (schedule fn, policy mode, congestion/admission config, impairment
    flag, metrics flag).  jax's own jit cache then holds one executable per
    (group shape, device).

    Scan inputs per frame: the padded instance, the PRNG key, the queueing
    delays, and the resilience engine's per-frame link/up vectors (all-ones
    dummies when ``impaired`` is False — never read then, so XLA drops
    them).  Admission control runs inside the step: deadline shedding masks
    ``avail`` *before* the policy (against the pre-frame backlog-only
    inflation estimate), the queue cap refuses assignments *after* it and
    before the committed work enters the backlog.

    ``metrics=True`` threads the per-frame real-request count as one more
    scan input and emits a :class:`~repro.obs.metrics.MetricsFrame` as one
    more scan output, stacked on device across the window.  With
    ``metrics=False`` the step's traced program is exactly the pre-metrics
    one — same inputs, same outputs, same jaxpr — which is what the
    bitwise-parity tests pin (fusion changes can flip greedy argmax
    near-ties, see ``docs/architecture.md`` section 6)."""

    def step(carry, x):
        if metrics:
            inst, key, tq, link_bw, up, n_real_t = x
        else:
            inst, key, tq, link_bw, up = x
        if impaired:  # policy-visible network state rides the carry
            carry = dataclasses.replace(carry, link_bw=link_bw, server_up=up)
        if ccfg.enabled:
            run_inst = dataclasses.replace(
                inst,
                gamma=effective_capacity(inst.gamma, carry.backlog_gamma),
                eta=effective_capacity(inst.eta, carry.backlog_eta),
            )
        else:
            run_inst = inst
        keep = None
        if acfg.enabled and acfg.shed:
            phi_pc, phi_pe = predicted_inflation(
                carry.backlog_gamma, carry.backlog_eta, inst.gamma, inst.eta, ccfg
            )
            keep = admission_keep(inst, tq, phi_pc, phi_pe)
            run_inst = dataclasses.replace(
                run_inst, avail=run_inst.avail & keep[:, None, None]
            )
        if stateful:
            a, carry = fn(run_inst, carry)
        elif needs_key:
            a = fn(run_inst, key)
        else:
            a = fn(run_inst)
        n_refused = None
        if acfg.enabled:
            j_cap = apply_queue_cap(
                a.j, inst, carry.backlog_gamma, carry.backlog_eta, acfg
            )
            if metrics:
                real = jnp.arange(a.j.shape[0]) < n_real_t
                n_refused = jnp.sum(real & (a.j >= 0) & (j_cap < 0))
            a = Assignment(j_cap, a.l)
        if ccfg.enabled:
            w, c = committed_loads(inst, a.j, a.l)
            pc = compute_inflation(carry.backlog_gamma + w, inst.gamma, ccfg)
            pe = comm_inflation(carry.backlog_eta + c, inst.eta, ccfg)
            carry = dataclasses.replace(
                carry,
                backlog_gamma=step_backlog(carry.backlog_gamma, w, inst.gamma, ccfg),
                backlog_eta=step_backlog(carry.backlog_eta, c, inst.eta, ccfg),
                ema_util=ema_update(carry.ema_util, w, inst.gamma, ccfg),
            )
        else:
            pc = jnp.ones_like(inst.gamma)
            pe = jnp.ones_like(inst.eta)
        if not metrics:
            return carry, (a.j, a.l, pc, pe)
        real = jnp.arange(a.j.shape[0]) < n_real_t
        n_shed = (
            jnp.sum(real & ~keep) if keep is not None else jnp.int32(0)
        )
        mf = frame_metrics(
            inst, a.j, a.l, tq, pc, pe, n_real_t, n_edge, carry,
            n_shed, n_refused if n_refused is not None else jnp.int32(0),
        )
        return carry, (a.j, a.l, pc, pe, mf)

    if metrics:
        def per_rep(c0, inst_seq, key_seq, tq_seq, link_seq, up_seq, nreal_seq):
            return jax.lax.scan(
                step, c0,
                (inst_seq, key_seq, tq_seq, link_seq, up_seq, nreal_seq),
            )
    else:
        def per_rep(c0, inst_seq, key_seq, tq_seq, link_seq, up_seq):
            return jax.lax.scan(
                step, c0, (inst_seq, key_seq, tq_seq, link_seq, up_seq)
            )

    return jax.jit(jax.vmap(per_rep))


def _fleet_runner(
    fn, stateful: bool, needs_key: bool, ccfg: CongestionConfig,
    acfg: AdmissionConfig, impaired: bool,
    metrics: bool = False, n_edge: int = 0,
):
    """Cached-runner lookup that marks cache misses on an active trace —
    each miss front-runs a fresh trace + XLA compile of the fleet program,
    which is exactly the cliff a profile reader wants flagged."""
    before = _fleet_runner_impl.cache_info().misses
    run = _fleet_runner_impl(
        fn, stateful, needs_key, ccfg, acfg, impaired, metrics, n_edge
    )
    if _fleet_runner_impl.cache_info().misses > before:
        instant("compile/fleet_runner", CAT_COMPILE, metrics=metrics)
    return run


def _pad_reps(tree, pad_r: int):
    """Pad the leading replication axis with copies of replication 0 so it
    divides the group width; the padded rows are dropped after the run.
    Works on numpy and jax leaves alike (numpy stays numpy)."""
    def pad(x):
        xp = np if isinstance(x, np.ndarray) else jnp
        return xp.concatenate([x, xp.repeat(x[:1], pad_r, axis=0)])

    return jax.tree.map(pad, tree)


def simulate_fleet(
    spec: ClusterSpec,
    cfg: SimConfig,
    scheduler: Optional[Callable[[FlatInstance], Assignment]] = None,
    *,
    policy: Union[str, Policy, None] = None,
    scenario: Union[str, Scenario] = "paper-default",
    n_rep: int = 16,
    seed: int = 0,
    options: Optional[EngineOptions] = None,
    streaming=_UNSET,
    devices=_UNSET,
    window=_UNSET,
    rep_group=_UNSET,
    rng_mode=_UNSET,
    prefetch=_UNSET,
    backend=_UNSET,
    metrics=_UNSET,
) -> FleetResult:
    """Monte-Carlo fleet: R independent replications, one device program.

    ``options`` is the consolidated engine configuration
    (:class:`~repro.core.options.EngineOptions`); the per-call engine
    keywords below are *deprecated aliases* that build the same object —
    they emit a :class:`DeprecationWarning` and raise when combined with an
    explicit ``options=``.  The two call styles resolve to the same
    :class:`EngineOptions` and return bit-identical ``FleetResult``s
    (pinned in ``tests/test_options.py``).  Unset fields resolve along
    **explicit > env var > scenario default**
    (:func:`~repro.core.options.resolve_options`).

    ``EngineOptions(scheduler="hierarchical")`` routes the fleet to the
    class-aggregate path (:mod:`repro.core.aggregation`): every frame's
    requests are bucketed into QoS classes, the padded class grid is
    allocated by the *device-resident* analytic allocator
    (:func:`repro.core.aggregation.hier_cells` — jitted XLA scan or the
    fused Pallas kernel, selected by ``backend=`` / ``REPRO_GUS_BACKEND``)
    inside the same vmap-over-R / scan-over-T / prefetch pipeline as the
    dense path, and satisfaction is accounted *per member* at
    deaggregation — memory and schedule time scale with the number of
    *classes*, not requests, which is what sustains 10^5+ users per frame
    (``mega-city``).  The path composes with congestion, impairments
    (per-member link draws at deaggregation), admission control
    (class-level shedding + queue caps, exact on singleton/duplicate
    classes), streaming, windowed arrivals, and metrics; ``devices`` shards
    the class-tensor precompute over the mesh
    (:func:`_hier_class_tensors`).  ``REPRO_HIER_HOST_LOOP=1`` falls back
    to the PR-9 host loop for baseline comparisons.

    ``metrics=True`` adds a per-frame :class:`~repro.obs.metrics.MetricsFrame`
    output to the scan — stacked on device across each window, drained with
    the window's other outputs (no per-frame host sync) — and returns the
    stream as ``FleetResult.metrics``.  Rows report the *post-frame* carried
    backlog (the scan carry); :func:`simulate` rows report the backlog
    entering each decision.  With ``metrics=False`` (default) the traced
    program and every result field are bit-identical to a build without the
    telemetry layer.

    Every (replication, frame) pair becomes one fixed-shape padded
    ``FlatInstance``; the fleet is laid out as an ``(R, T)`` grid and
    scheduled by a single jitted program — ``vmap`` over the R replications
    of a ``lax.scan`` over the T frames, with the per-replication
    :class:`~repro.core.queueing.PolicyCarry` (congestion backlogs, EMA
    load, policy state) as the scan carry.  This is the throughput path for
    scenario sweeps (the paper runs 20 000 repetitions); with the
    congestion model disabled the carry is inert and results are
    bit-identical to scheduling all R*T frames in one flat vmap.

    ``devices`` shards the replication axis across the 1-D ``("rep",)``
    device mesh of :func:`repro.launch.mesh.make_fleet_mesh`: replications
    are cut into fixed-width groups of ``rep_group`` (default
    :data:`FLEET_REP_GROUP`; ``n_rep`` is padded up with throwaway
    replications and sliced back), and each group's slice of the instance
    grid, the PRNG-key chain, and the carry pytree is placed on the next
    mesh device round-robin.  Every group runs the *same* compiled
    vmap-over-group-of-``lax.scan`` program — only its device changes — and
    jax's async dispatch overlaps the groups across devices.  Replications
    never communicate, so sharded results are **bit-identical** to the
    single-device run.  (An SPMD ``shard_map`` layout was measured and
    rejected here: the partitioner compiles a different fusion of the
    scheduler per device count, and greedy argmax/argsort decisions amplify
    1-ulp differences into different assignments — see
    ``docs/architecture.md`` section 6.)  ``devices=None`` uses every local
    device, which with one visible device is exactly the single-device
    path; asking for more than ``jax.local_device_count()`` raises.
    ``rep_group`` must be held fixed when comparing runs across device
    counts; fleets with ``n_rep <= rep_group`` run as one group (the
    legacy single-program layout).  ``rep_group > n_rep`` clamps to
    ``n_rep`` — the group width can never exceed the replication count, and
    the clamped run is bit-identical to ``rep_group=n_rep`` (pinned in
    ``tests/test_options.py``); ``rep_group < 1`` raises.

    ``window`` bounds memory on long horizons: the (R, T) grid is built and
    scanned ``window`` frames at a time, threading the carry between
    chunks, instead of materializing all T frames' instance tensors at
    once.  On a ``streaming`` scenario the arrivals themselves are drawn
    one window at a time from each replication's
    :class:`~repro.core.streaming.ArrivalStream` (a count-only pre-pass
    fixes the padding bucket), so memory stays bounded at 10^5-frame
    horizons.  Windowed results are bit-identical to the materialized run.

    ``prefetch`` overlaps the host with the devices: a single producer
    thread builds window ``k+1``'s arrivals and instance grid (the same
    work, in the same order, as the serial loop — all host-side RNG lives
    in the producer, so results are **bit-identical**) while window ``k``'s
    replication groups compute, with a bounded queue of depth ``prefetch``
    applying backpressure.  ``prefetch=0`` degrades to the serial
    build-then-dispatch loop (the pre-overlap pipeline, and the reference
    the parity tests compare against); the default of 1 double-buffers.
    A builder exception propagates to the caller, and an early exit (or a
    caller-side error) drains and joins the producer — no hung threads.
    ``FleetResult.gen_s`` reports how long the pipeline actually *blocked*
    on host-side generation + building; hiding that time is the point.

    ``rng_mode`` (``None`` defers to ``scenario.rng_mode``) selects the
    arrival generator: ``"paper-default"`` keeps the frozen per-request
    draw order, ``"vectorized"`` generates in numpy batches and keeps the
    whole trace columnar (:class:`~repro.core.scenarios.RequestColumns`) so
    the grid builder fills frames from array slices — ~10x faster host
    generation, different (equally distributed, seed-deterministic) traces.

    ``policy`` names a registered :class:`~repro.core.policies.Policy`; a
    ``needs_key`` policy (``random``) receives one PRNG key per
    (replication, frame) pair split from ``seed`` (fed through the scan as
    inputs, preserving the legacy key chain), a ``stateful`` policy carries
    its own state in the scan carry, and a non-vmappable policy (the
    ``ilp`` / ``lp-bound`` oracles) falls back to a host-side loop over the
    *unpadded* frames — threading the same carry — feeding the same masked
    metrics path (``devices`` other than ``None``/1 raises there;
    ``window`` does not apply).

    Frame semantics are *frame-synchronous*: one decision per frame at the
    frame boundary (no queue-cap early closes), per-frame budgets refresh
    through the scenario's capacity stream, and the scheduler sees the true
    mean bandwidth.  Satisfaction is evaluated on the modeled completion
    times (like the paper's numerical Monte-Carlo) — inflated by the
    congestion factors when ``cfg.congestion.enabled``.  Use
    :func:`simulate` for stochastic channel realizations and the EMA
    bandwidth estimator.

    ``backend`` picks the default GUS scheduler's implementation for the
    whole grid (``"xla"`` / ``"pallas"``, bit-identical assignments — the
    Pallas kernel schedules one grid program per (replication, frame)
    inside the same vmapped scan); it composes only with the default
    scheduler / the ``"gus"`` policy.
    """
    opts = fold_deprecated_kwargs(
        options,
        dict(streaming=streaming, devices=devices, window=window,
             rep_group=rep_group, rng_mode=rng_mode, prefetch=prefetch,
             backend=backend, metrics=metrics),
        caller="simulate_fleet",
    )
    scn = get_scenario(scenario)
    opts = resolve_options(opts, scenario=scn)
    metrics = bool(opts.metrics)
    devices = opts.devices
    hier = opts.scheduler == "hierarchical"
    pol = _resolve_policy(scheduler, policy)
    if hier:
        # backend= now selects the hierarchical allocator's implementation
        # (XLA scan / fused Pallas kernel); admission control composes —
        # class-level shed + queue caps run inside the jitted hier runner
        pol, scheduler = _fold_hier_scheduler(
            pol, scheduler, opts, allow_backend=True
        )
    else:
        pol, scheduler = _apply_backend(pol, scheduler, opts.backend)
    ccfg = cfg.congestion
    acfg = cfg.admission
    T = max(1, int(np.ceil(cfg.horizon_ms / cfg.frame_ms)))
    K = spec.proc_ms.shape[1]
    M = spec.n_servers
    use_stream = opts.streaming
    host_side = (not hier) and pol is not None and (not pol.vmappable or not pol.pad)
    if hier:
        n_dev = _resolve_fleet_devices(devices, n_rep)
    elif host_side:
        if devices is not None and devices != 1:
            _resolve_fleet_devices(devices, n_rep)  # impossible counts error first
            raise ValueError(
                f"policy {pol.name!r} schedules host-side; devices={devices} "
                f"of {jax.local_device_count()} visible device(s) does not "
                "apply — the host-side loop drives exactly one device (use "
                "devices=None or 1)"
            )
        n_dev = 1
    else:
        n_dev = _resolve_fleet_devices(devices, n_rep)
    W = T if opts.window is None else max(1, min(int(opts.window), T))
    # lazy per-window arrival generation needs the stream's chunking
    # invariance; a materialized trace is bucketed up front either way.
    # The hierarchical path is windowed by construction, so it keeps the
    # stream lazy.
    lazy = use_stream and W < T and (hier or not host_side)
    mode = opts.rng_mode
    prefetch = opts.prefetch

    sw = Stopwatch()
    t_run0 = time.perf_counter()
    with sw.span("fleet/generate_traces", CAT_GEN, n_rep=n_rep):
        sources = [
            _RepFrameSource(
                scn, seed + rep, spec.n_edge, K, cfg, T, use_stream, lazy,
                rng_mode=mode,
            )
            for rep in range(n_rep)
        ]
        if hier:
            n_pad = 0  # the aggregated path never pads a request grid
        elif lazy:
            # count-only pre-pass: the global max bucket, in bounded memory —
            # one padding bucket for every window, identical to materialized
            n_max = max(
                max_frame_arrivals(
                    scn, seed + rep, spec.n_edge, K, cfg, T, rng_mode=mode
                )
                for rep in range(n_rep)
            )
            n_pad = _pad_bucket(n_max)
        else:
            n_max = max(src.max_bucket for src in sources)
            n_pad = _pad_bucket(n_max)
    # trace generation + padding pre-pass; per-window blocking adds to this
    gen_s = sw.total("fleet/generate_traces")
    # the resilience engine is replication-independent (same network
    # weather for every rep) and frame-indexed, so its traces tile across
    # the rep axis and extend prefix-stable window by window — what keeps
    # windowed/prefetched/sharded runs bitwise identical to serial
    engine = (
        ResilienceEngine(cfg.impairments, spec.n_edge, M)
        if cfg.impairments.enabled else None
    )

    if hier:
        return _simulate_fleet_hier(
            spec, cfg, scn, sources, n_rep=n_rep, T=T, W=W, opts=opts,
            n_dev=n_dev, gen_s=gen_s, engine=engine, metrics=metrics, sw=sw,
            t_run0=t_run0,
        )

    if host_side:
        return _simulate_fleet_host(
            spec, cfg, scn, pol, sources, n_rep=n_rep, T=T, n_pad=n_pad, seed=seed,
            gen_s=gen_s, engine=engine, metrics=metrics, sw=sw, t_run0=t_run0,
        )

    if pol is not None:
        fn = _bound_policy(pol, spec.n_edge, spec.n_servers)
        needs_key = pol.needs_key and not pol.stateful
        stateful = pol.stateful
    else:
        fn = gus_schedule if scheduler is None else scheduler
        needs_key = False
        stateful = False
    run = _fleet_runner(
        fn, stateful, needs_key, ccfg, acfg, engine is not None,
        metrics, spec.n_edge,
    )

    if needs_key:
        keys_all = np.asarray(jax.random.split(
            jax.random.PRNGKey(seed), n_rep * T
        )).reshape(n_rep, T, -1)
    else:  # dummy inputs keep the scan signature uniform
        keys_all = np.zeros((n_rep, T, 2), np.uint32)
    carry = fleet_policy_carry(n_rep, M, seed=seed, bandwidth_init=spec.bandwidth_true)

    # --- fixed-width replication groups, round-robined across the mesh ------
    # Every group of G replications runs the SAME jitted program (same
    # shapes, same HLO) no matter how many devices are in play — only the
    # device each group is placed on changes.  That is what makes sharded
    # results bit-identical to the single-device run: an SPMD partitioner
    # (shard_map) or a device-count-dependent batch width recompiles the
    # scheduler with different fusion, and greedy argmax/argsort decisions
    # amplify 1-ulp differences into different assignments.  jax dispatch
    # is async, so the per-group calls overlap across devices.
    # rep_group < 1 was rejected by resolve_options; > n_rep clamps (a group
    # can never be wider than the replication axis), documented above
    G = min(FLEET_REP_GROUP if opts.rep_group is None else int(opts.rep_group), n_rep)
    pad_r = (-n_rep) % G
    n_groups = (n_rep + pad_r) // G
    if n_dev > 1:
        from repro.launch.mesh import make_fleet_mesh

        group_devices = list(make_fleet_mesh(n_dev).devices.ravel())
    else:
        group_devices = [None]  # jax.device_put's default device

    # worker threads drive the devices concurrently (XLA releases the GIL
    # during execution); more workers than physical cores only adds
    # contention on a CPU host, so cap there — device placement still
    # round-robins over the full mesh
    n_workers = min(n_dev, os.cpu_count() or 1)
    executor = ThreadPoolExecutor(max_workers=n_workers) if n_workers > 1 else None
    if pad_r:
        carry = _pad_reps(carry, pad_r)
        keys_all = _pad_reps(keys_all, pad_r)
    carries = [
        jax.device_put(
            jax.tree.map(lambda x: x[g * G:(g + 1) * G], carry),
            group_devices[g % n_dev],
        )
        for g in range(n_groups)
    ]

    # per-(rep, frame) stores; the final reductions below see the same
    # values in the same order no matter how the frames were windowed
    sat_frames = np.zeros((n_rep, T), np.int64)
    served_frames = np.zeros((n_rep, T), np.int64)
    us_frames = np.zeros((n_rep, T), np.float32)
    n_real_frames = np.zeros((n_rep, T), np.int32)
    phi_frames = np.ones((n_rep, T, M), np.float32) if ccfg.enabled else None

    def build_window(t0: int):
        """Host-side build of one window: pull every replication's buckets,
        fill the queueing-delay rows, and assemble the padded instance grid.
        Pure numpy + the sources' own RNGs, so it runs unchanged — same
        work, same draw order — inline (``prefetch=0``) or on the producer
        thread (``prefetch>0``); that is the whole bit-identity argument."""
        t1 = min(t0 + W, T)
        Tc = t1 - t0
        frames: List = []
        frame_starts: List[float] = []
        n_real = np.zeros((n_rep, Tc), np.int32)
        tq_flat = np.zeros((n_rep * Tc, n_pad), np.float32)
        i = 0
        with sw.span("fleet/arrivals", CAT_GEN, t0=t0):
            for rep, src in enumerate(sources):
                for k, bucket in enumerate(src.take(t1)):
                    frame_start = (t0 + k) * cfg.frame_ms
                    frames.append(bucket)
                    frame_starts.append(frame_start)
                    nb = len(bucket)
                    n_real[rep, k] = nb
                    if nb:
                        if isinstance(bucket, RequestColumns):
                            tq_flat[i, :nb] = (
                                frame_start + cfg.frame_ms - bucket.arrival_ms
                            )
                        else:
                            tq_flat[i, :nb] = [
                                frame_start + cfg.frame_ms - r.arrival_ms
                                for r in bucket
                            ]
                    i += 1
        with sw.span("fleet/grid_build", CAT_BUILD, t0=t0):
            # per-frame budgets are replication-independent: one *batched*
            # capacity-stream call per window, reused across the R reps
            gb, eb = _frame_budgets_batch(
                spec, cfg, scn, (t0 + np.arange(Tc)) * cfg.frame_ms, engine=engine,
            )
            budgets_by_k = [(gb[k], eb[k]) for k in range(Tc)]
            R_pad = n_rep + pad_r
            if engine is not None:
                links_by_k = [engine.link_frame(t0 + k) for k in range(Tc)]
                links_arg = links_by_k * n_rep
                link_rt = np.broadcast_to(
                    np.stack([l[0] for l in links_by_k]).astype(np.float32),
                    (R_pad, Tc, M),
                )
                up_rt = np.broadcast_to(
                    np.stack([engine.server_up(t0 + k) for k in range(Tc)]),
                    (R_pad, Tc, M),
                )
            else:  # dummy xs keep the scan signature uniform (never read)
                links_arg = None
                link_rt = up_rt = np.broadcast_to(
                    np.ones((1, 1, M), np.float32), (R_pad, Tc, M)
                )
            batch = _build_frame_batch(
                frames, spec, cfg, frame_starts, budgets_by_k * n_rep, n_pad,
                links=links_arg,
            )  # leading axis: n_rep * Tc frames
            batch_rt = jax.tree.map(
                lambda x: x.reshape((n_rep, Tc) + x.shape[1:]), batch
            )
            tq_rt = tq_flat.reshape(n_rep, Tc, n_pad)
            nreal_rt = n_real
            if pad_r:
                batch_rt = _pad_reps(batch_rt, pad_r)
                tq_rt = _pad_reps(tq_rt, pad_r)
                nreal_rt = _pad_reps(nreal_rt, pad_r)
        return (t0, t1, Tc, batch, batch_rt, n_real, tq_flat, tq_rt,
                link_rt, up_rt, nreal_rt)

    window_starts = list(range(0, T, W))
    prod_thread = None
    if prefetch > 0 and len(window_starts) > 0:
        # bounded producer: builds windows ahead of the consumer, at most
        # `prefetch` in flight.  Timeout-polling puts let it notice a
        # consumer that stopped pulling (early exit / error) and unwind.
        work_q: queue_mod.Queue = queue_mod.Queue(maxsize=prefetch)
        stop_producer = threading.Event()

        def _offer(item) -> bool:
            while not stop_producer.is_set():
                try:
                    work_q.put(item, timeout=0.05)
                    return True
                except queue_mod.Full:
                    continue
            return False

        def _produce():
            try:
                for t0 in window_starts:
                    if not _offer(build_window(t0)):
                        return
            except BaseException as e:  # delivered to the consumer's get()
                _offer(e)

        prod_thread = threading.Thread(
            target=_produce, name="fleet-window-producer", daemon=True
        )
        prod_thread.start()

    def next_window(t0: int):
        """The consumer's pull: inline build when serial, else a queue get
        whose wait time is exactly the un-hidden host cost (gen_s)."""
        if prod_thread is None:
            return build_window(t0)
        item = work_q.get()
        if isinstance(item, BaseException):
            raise item
        return item

    m_acc: Optional[Dict[str, np.ndarray]] = None
    try:
        for wi, wi_t0 in enumerate(window_starts):
            with sw.span("fleet/window_wait", CAT_WAIT, window=wi):
                (t0, t1, Tc, batch, batch_rt, n_real, tq_flat,
                 tq_rt, link_rt, up_rt, nreal_rt) = next_window(wi_t0)
            keys_rt = keys_all[:, t0:t1]

            def run_group(g):
                sl = slice(g * G, (g + 1) * G)
                host = [
                    jax.tree.map(lambda x: x[sl], batch_rt),
                    keys_rt[sl],
                    tq_rt[sl],
                    np.ascontiguousarray(link_rt[sl]),
                    np.ascontiguousarray(up_rt[sl]),
                ]
                if metrics:
                    host.append(nreal_rt[sl])
                # explicit host->device placement, on the group's device
                # (the default one when single-device)
                with sw.span("fleet/put", CAT_DISPATCH, group=g):
                    argv = jax.device_put(host, group_devices[g % n_dev])
                sw.count("fleet/h2d_bytes",
                         sum(x.nbytes for x in jax.tree.leaves(host)))
                c, out = run(carries[g], *argv)
                # materialize here (XLA releases the GIL while computing,
                # so worker threads overlap groups across devices); the
                # carry stays device-resident for the next window
                with sw.span("fleet/drain", CAT_DISPATCH, group=g):
                    return c, jax.tree.map(np.asarray, out)

            with sw.span(
                "fleet/dispatch", CAT_DISPATCH, step=wi, window=wi, n_groups=n_groups
            ):
                if executor is None:
                    results = [run_group(g) for g in range(n_groups)]
                else:
                    results = list(executor.map(run_group, range(n_groups)))
            for g, (c, _) in enumerate(results):
                carries[g] = c
            sw.count("fleet/rows", n_rep * Tc * n_pad)
            with sw.span("fleet/window_metrics", CAT_METRICS, window=wi):
                jv, lv, pc, pe = (
                    np.concatenate([r[1][part] for r in results])[:n_rep]
                    for part in range(4)
                )
                # one explicit upload of every host array the device
                # accounting below reads
                leaves = dict.fromkeys(
                    SATISFACTION_LEAVES
                    + (CONGESTED_CTIME_LEAVES if ccfg.enabled else ())
                )
                host = {f: getattr(batch, f) for f in leaves}
                host["j"] = jv.reshape(n_rep * Tc, n_pad)
                host["l"] = lv.reshape(n_rep * Tc, n_pad)
                if ccfg.enabled:
                    host.update(
                        tq=tq_flat,
                        phi_c=pc.reshape(n_rep * Tc, M),
                        phi_e=pe.reshape(n_rep * Tc, M),
                    )
                sw.count("fleet/accounting_h2d_bytes",
                         sum(x.nbytes for x in host.values()))
                dev = jax.device_put(host)
                mbatch = dataclasses.replace(batch, **{f: dev[f] for f in leaves})
                if ccfg.enabled:
                    mbatch = dataclasses.replace(
                        mbatch,
                        ctime=congested_ctime(
                            mbatch, dev["tq"], dev["phi_c"], dev["phi_e"]
                        ),
                    )
                    phi_frames[:, t0:t1] = pc

                sat = np.asarray(satisfied_mask(mbatch, dev["j"], dev["l"]))
                us = np.asarray(mean_us(mbatch, dev["j"], dev["l"]))
                real = np.arange(n_pad)[None, :] < n_real.reshape(-1)[:, None]
                served = (host["j"] >= 0) & real
                sat = sat & real
                sat_frames[:, t0:t1] = sat.sum(-1).reshape(n_rep, Tc)
                served_frames[:, t0:t1] = served.sum(-1).reshape(n_rep, Tc)
                us_frames[:, t0:t1] = us.reshape(n_rep, Tc)
                n_real_frames[:, t0:t1] = n_real
                if metrics:
                    # scan-stacked MetricsFrame leaves arrive as
                    # (G, Tc, ...) per group — stitch the rep axis back
                    mfw = jax.tree.map(
                        lambda *xs: np.concatenate(xs)[:n_rep],
                        *[r[1][4] for r in results],
                    )
                    if m_acc is None:
                        m_acc = {
                            f: np.zeros(
                                (n_rep, T) + getattr(mfw, f).shape[2:],
                                getattr(mfw, f).dtype,
                            )
                            for f in MetricsFrame._fields
                        }
                    for f in MetricsFrame._fields:
                        m_acc[f][:, t0:t1] = getattr(mfw, f)

    finally:
        if prod_thread is not None:
            # early exit or error: unblock the producer (it polls the stop
            # event between put attempts), drain whatever it queued, join
            stop_producer.set()
            while prod_thread.is_alive():
                try:
                    work_q.get_nowait()
                except queue_mod.Empty:
                    pass
                prod_thread.join(timeout=0.05)
            prod_thread.join()

    if executor is not None:
        executor.shutdown(wait=False)
    final_backlog = np.concatenate(
        [np.asarray(c.backlog_gamma) for c in carries]
    )[:n_rep]
    reqs_per_rep = n_real_frames.sum(1)
    sat_per_rep = sat_frames.sum(1)
    # mean_us averages over n_pad rows (padded rows contribute 0); recover the
    # per-rep sum (exact: n_pad is a power of two) and renormalize by the
    # rep's true request count
    us_sum_per_rep = (us_frames * n_pad).sum(1)
    gen_s += sw.total("fleet/window_wait")
    timings = sw.as_dict()
    timings["total_s"] = time.perf_counter() - t_run0
    mres = None
    if metrics and m_acc is not None:
        mres = MetricsResult.from_stacked(
            MetricsFrame(**m_acc),
            t_ms=(np.arange(T) + 1.0) * cfg.frame_ms,
            n_edge=spec.n_edge,
            frame_ms=cfg.frame_ms,
        )
    return FleetResult(
        n_rep=n_rep,
        n_frames=T,
        n_requests=int(reqs_per_rep.sum()),
        n_served=int(served_frames.sum()),
        satisfied_per_rep=100.0 * sat_per_rep / np.maximum(reqs_per_rep, 1),
        mean_us_per_rep=us_sum_per_rep / np.maximum(reqs_per_rep, 1),
        final_backlog_per_rep=final_backlog if ccfg.enabled else None,
        mean_compute_inflation=float(np.mean(phi_frames)) if ccfg.enabled else 1.0,
        n_devices=n_dev,
        window=W,
        dispatch_s=sw.total("fleet/dispatch"),
        gen_s=gen_s,
        prefetch=prefetch if prod_thread is not None else 0,
        timings=timings,
        metrics=mres,
    )


def _simulate_fleet_host(
    spec: ClusterSpec,
    cfg: SimConfig,
    scn: Scenario,
    pol: Policy,
    sources: List[_RepFrameSource],
    *,
    n_rep: int,
    T: int,
    n_pad: int,
    seed: int,
    gen_s: float = 0.0,
    engine: Optional[ResilienceEngine] = None,
    metrics: bool = False,
    sw: Optional[Stopwatch] = None,
    t_run0: Optional[float] = None,
) -> FleetResult:
    """Host-side fleet path for non-vmappable / non-padding policies (the
    ILP / LP-bound oracles): schedule each *unpadded* frame in a Python
    loop — threading the per-replication carry frame by frame — then re-pad
    the assignments with drops so the masked metrics tail is shared with
    the vmapped policies.  Impairments and admission control mirror the
    scan step exactly (same helpers, same order)."""
    ccfg = cfg.congestion
    acfg = cfg.admission
    M = spec.n_servers
    if sw is None:
        sw = Stopwatch()
    if t_run0 is None:
        t_run0 = time.perf_counter()
    fleet_frames: List[List[Request]] = []
    with sw.span("fleet/arrivals", CAT_GEN):
        for src in sources:
            fleet_frames.extend(src.take(T))
    raw_insts = []
    n_real = np.array([len(b) for b in fleet_frames], np.int32)
    tq_flat = np.zeros((len(fleet_frames), n_pad), np.float32)
    with sw.span("fleet/grid_build", CAT_BUILD):
        for i, bucket in enumerate(fleet_frames):
            frame_start = (i % T) * cfg.frame_ms
            gamma, eta = _frame_budgets(spec, cfg, scn, frame_start, engine=engine)
            link = None
            if engine is not None and len(bucket):
                sc, la = engine.link_frame(i % T)
                cov = (
                    bucket.cover.astype(np.intp)
                    if isinstance(bucket, RequestColumns)
                    else np.array([r.cover for r in bucket], np.intp)
                )
                link = (sc[cov], la[cov])
            raw_insts.append(_build_frame_instance(
                bucket, spec, cfg, frame_start + cfg.frame_ms,
                spec.bandwidth_true, cfg.max_cs, gamma=gamma, eta=eta, link=link,
            ))
            if bucket:
                if isinstance(bucket, RequestColumns):
                    tq_flat[i, : len(bucket)] = (
                        frame_start + cfg.frame_ms - bucket.arrival_ms
                    )
                else:
                    tq_flat[i, : len(bucket)] = [
                        frame_start + cfg.frame_ms - r.arrival_ms for r in bucket
                    ]
        batch = stack_instances([pad_instance(r, n_pad) for r in raw_insts])

    fn = pol.bind(spec.n_edge, spec.n_servers)
    keys = (
        jax.random.split(jax.random.PRNGKey(seed), len(raw_insts))
        if pol.needs_key and not pol.stateful else None
    )
    jv = np.full((len(raw_insts), n_pad), -1, np.int32)
    lv = np.full((len(raw_insts), n_pad), -1, np.int32)
    phi_c = np.ones((len(raw_insts), M), np.float32)
    phi_e = np.ones((len(raw_insts), M), np.float32)
    final_backlog = np.zeros((n_rep, M), np.float32)
    if metrics:
        m_shed = np.zeros(len(raw_insts), np.int32)
        m_refused = np.zeros(len(raw_insts), np.int32)
        m_w = np.zeros((len(raw_insts), M), np.float32)
        m_c = np.zeros((len(raw_insts), M), np.float32)
        m_bg = np.zeros((len(raw_insts), M), np.float32)
        m_be = np.zeros((len(raw_insts), M), np.float32)
    with sw.span("fleet/schedule_host", CAT_SCHED, n_rep=n_rep):
        for rep in range(n_rep):
            carry = init_policy_carry(
                M, seed=seed + rep, bandwidth_init=spec.bandwidth_true
            )
            for tf in range(T):
                i = rep * T + tf
                inst, n = raw_insts[i], n_real[i]
                if engine is not None:
                    carry = dataclasses.replace(
                        carry,
                        link_bw=jnp.asarray(engine.link_frame(tf)[0], jnp.float32),
                        server_up=jnp.asarray(engine.server_up(tf)),
                    )
                if ccfg.enabled:
                    run_inst = dataclasses.replace(
                        inst,
                        gamma=effective_capacity(inst.gamma, carry.backlog_gamma),
                        eta=effective_capacity(inst.eta, carry.backlog_eta),
                    )
                else:
                    run_inst = inst
                if acfg.enabled and acfg.shed and n:
                    phi_pc, phi_pe = predicted_inflation(
                        carry.backlog_gamma, carry.backlog_eta,
                        inst.gamma, inst.eta, ccfg,
                    )
                    keep = admission_keep(
                        inst, jnp.asarray(tq_flat[i, :n]), phi_pc, phi_pe
                    )
                    run_inst = dataclasses.replace(
                        run_inst, avail=run_inst.avail & keep[:, None, None]
                    )
                    if metrics:
                        m_shed[i] = int(n) - int(np.asarray(keep).sum())
                if pol.stateful:
                    a, carry = fn(run_inst, carry)
                elif keys is not None:
                    a = fn(run_inst, keys[i])
                else:
                    a = fn(run_inst)
                if acfg.enabled and n:
                    j_cap = apply_queue_cap(
                        a.j, inst, carry.backlog_gamma, carry.backlog_eta, acfg
                    )
                    if metrics:
                        m_refused[i] = int(np.sum(
                            (np.asarray(a.j) >= 0) & (np.asarray(j_cap) < 0)
                        ))
                    a = Assignment(j_cap, a.l)
                jv[i, :n] = np.asarray(a.j)
                lv[i, :n] = np.asarray(a.l)
                if ccfg.enabled or metrics:
                    w, c = committed_loads(
                        inst, jnp.asarray(a.j), jnp.asarray(a.l)
                    )
                    if metrics:
                        m_w[i] = np.asarray(w, np.float32)
                        m_c[i] = np.asarray(c, np.float32)
                if ccfg.enabled:
                    phi_c[i] = np.asarray(
                        compute_inflation(carry.backlog_gamma + w, inst.gamma, ccfg)
                    )
                    phi_e[i] = np.asarray(
                        comm_inflation(carry.backlog_eta + c, inst.eta, ccfg)
                    )
                    carry = dataclasses.replace(
                        carry,
                        backlog_gamma=step_backlog(
                            carry.backlog_gamma, w, inst.gamma, ccfg
                        ),
                        backlog_eta=step_backlog(
                            carry.backlog_eta, c, inst.eta, ccfg
                        ),
                        ema_util=ema_update(carry.ema_util, w, inst.gamma, ccfg),
                    )
                if metrics:  # post-frame carried backlog, like the scan rows
                    m_bg[i] = np.asarray(carry.backlog_gamma, np.float32)
                    m_be[i] = np.asarray(carry.backlog_eta, np.float32)
            final_backlog[rep] = np.asarray(carry.backlog_gamma)
    assign = Assignment(jv, lv)

    if ccfg.enabled:
        mbatch = dataclasses.replace(
            batch,
            ctime=congested_ctime(
                batch, jnp.asarray(tq_flat), jnp.asarray(phi_c), jnp.asarray(phi_e)
            ),
        )
    else:
        mbatch = batch

    sat = np.asarray(satisfied_mask(mbatch, assign.j, assign.l))  # (R*T, n_pad)
    us = np.asarray(mean_us(mbatch, assign.j, assign.l))          # (R*T,)
    real = np.arange(n_pad)[None, :] < n_real[:, None]
    served = (np.asarray(assign.j) >= 0) & real
    sat = sat & real

    mres = None
    if metrics:
        # vectorized post-pass over the padded grid — same definitions as
        # the scan's frame_metrics rows (served/sat masked to real rows,
        # utilization against the full frame budgets)
        with sw.span("fleet/window_metrics", CAT_METRICS):
            jb = np.asarray(assign.j)
            local = served & (jb == np.asarray(batch.cover))
            cloudm = served & (jb >= spec.n_edge)
            eo = served & ~local & ~cloudm
            tier = np.stack(
                [local.sum(-1), eo.sum(-1), cloudm.sum(-1)], -1
            ).astype(np.int32)
            edges = np.asarray(QOS_ACC_EDGES, np.float32)
            cls = (np.asarray(batch.A)[..., None] >= edges).sum(-1)
            nq = len(QOS_ACC_EDGES) + 1
            oh = cls[..., None] == np.arange(nq)
            qos_cnt = (oh & real[..., None]).sum(1).astype(np.int32)
            qos_sat = (oh & sat[..., None]).sum(1).astype(np.int32)
            gam = np.asarray(batch.gamma, np.float64)
            eta_b = np.asarray(batch.eta, np.float64)
            with np.errstate(invalid="ignore"):
                ug = np.where(gam > 0.0, m_w / np.maximum(gam, 1e-9), 0.0)
                ue = np.where(eta_b > 0.0, m_c / np.maximum(eta_b, 1e-9), 0.0)

            def rt(x):
                return x.reshape((n_rep, T) + x.shape[1:])

            mres = MetricsResult.from_stacked(
                MetricsFrame(
                    n_arrivals=rt(n_real.astype(np.int32)),
                    n_served=rt(served.sum(-1).astype(np.int32)),
                    n_satisfied=rt(sat.sum(-1).astype(np.int32)),
                    n_shed=rt(m_shed),
                    n_refused=rt(m_refused),
                    tier_hist=rt(tier),
                    qos_sat=rt(qos_sat),
                    qos_count=rt(qos_cnt),
                    util_gamma=rt(ug.astype(np.float32)),
                    util_eta=rt(ue.astype(np.float32)),
                    backlog_gamma=rt(m_bg),
                    backlog_eta=rt(m_be),
                    us_sum=rt((us * n_pad).astype(np.float32)),
                ),
                t_ms=(np.arange(T) + 1.0) * cfg.frame_ms,
                n_edge=spec.n_edge,
                frame_ms=cfg.frame_ms,
            )

    timings = sw.as_dict()
    timings["total_s"] = time.perf_counter() - t_run0
    reqs_per_rep = n_real.reshape(n_rep, T).sum(1)
    sat_per_rep = sat.reshape(n_rep, T, n_pad).sum((1, 2))
    us_sum_per_rep = (us * n_pad).reshape(n_rep, T).sum(1)
    return FleetResult(
        n_rep=n_rep,
        n_frames=T,
        n_requests=int(reqs_per_rep.sum()),
        n_served=int(served.sum()),
        satisfied_per_rep=100.0 * sat_per_rep / np.maximum(reqs_per_rep, 1),
        mean_us_per_rep=us_sum_per_rep / np.maximum(reqs_per_rep, 1),
        final_backlog_per_rep=final_backlog if ccfg.enabled else None,
        mean_compute_inflation=float(np.mean(phi_c)) if ccfg.enabled else 1.0,
        n_devices=1,
        window=T,
        gen_s=gen_s,
        timings=timings,
        metrics=mres,
    )


@jax.jit
def _us_feas_fused(batch: FlatInstance):
    """Fused single-dispatch ``(us_tensor, hard_feasible)`` over a class
    grid.  Same elementwise expression graph as the eager calls (bitwise
    identical values) but one H2D transfer per field and one fused XLA
    computation instead of a dozen eager dispatches with host temporaries —
    this runs on the producer thread at city scale, where it sits on the
    pipeline's critical path."""
    return us_tensor(batch).astype(jnp.float32), hard_feasible(batch)


@jax.jit
def _us_feas_lean(ctime, A, C, w_a, w_c, max_as, max_cs, cover, svc, size,
                  acc_sl, placed_t, proc_t):
    """Lean-build twin of :func:`_us_feas_fused`: reconstructs the candidate
    gathers (``acc``, ``avail``, ``v``, ``u``) on device from per-class
    vectors plus the (S, M, L)-transposed spec tensors, then evaluates the
    same elementwise expressions as ``us_tensor`` / ``hard_feasible``.
    Every rebuilt tensor is a pure float32 gather / select, so real rows
    are bitwise identical to the host-materialized versions; padded rows
    (``svc``/``size``/``cover`` zero, ``A`` 1e9, ``C`` -1) gather service
    0's values instead of zeros, which no output can see — their ``us`` is
    an exact 0 (zero weights), their ``feas`` an exact False (the 1e9
    accuracy floor), and the allocator never takes from an infeasible
    zero-count class."""
    acc_b = acc_sl[svc][..., None, :]                     # (F, Cp, 1, L)
    avail = placed_t[svc]                                 # (F, Cp, M, L)
    acc_term = (acc_b - A[..., None, None]) / max_as[..., None, None, None]
    time_term = (C[..., None, None] - ctime) / max_cs[..., None, None, None]
    us = w_a[..., None, None] * acc_term + w_c[..., None, None] * time_term
    feas = (
        avail & (acc_b >= A[..., None, None]) & (ctime <= C[..., None, None])
    )
    v = proc_t[svc]                                       # (F, Cp, M, L)
    local = cover[..., None] == jnp.arange(v.shape[-2])[None, None, :]
    u = jnp.where(local[..., None], 0.0, (size / 1024.0)[..., None, None])
    return (us.astype(jnp.float32), feas, v, jnp.broadcast_to(u, v.shape))


def _hier_class_tensors(batch: FlatInstance, n_dev: int):
    """Utility / feasibility tensors for a window's class grid, with the
    *class axis* sharded over the ``("rep",)`` device mesh when more than
    one device is visible.

    ``us_tensor`` / ``hard_feasible`` are elementwise per class row, so
    cutting the padded class axis into ``n_dev`` contiguous slabs and
    computing each slab on its own mesh device produces bit-identical
    values to the single-device call (no cross-class reduction exists to
    re-associate) — this is the one hierarchical tensor big enough at
    city-scale frames (``F x Cp x M x L``) to be worth spreading, and the
    allocator itself stays a sequential scan over classes (the budgets are
    a carry), so sharding lives here, not in the kernel.
    """
    if n_dev <= 1:
        # one fused jit call instead of eager op-by-op dispatch, and the
        # outputs stay on device: the runner consumes them next, and a host
        # round-trip of two (F, Cp, M, L) tensors at city scale costs more
        # than the allocator's whole scan
        return _us_feas_fused(batch)
    from repro.launch.mesh import make_fleet_mesh

    devs = list(make_fleet_mesh(n_dev).devices.ravel())
    Cp = batch.A.shape[1]
    cuts = np.linspace(0, Cp, n_dev + 1).astype(int)
    per_class = ("cover", "A", "C", "w_a", "w_c", "acc", "ctime", "v", "u",
                 "avail")
    us_p, fe_p = [], []
    for d, dev in enumerate(devs):
        lo, hi = int(cuts[d]), int(cuts[d + 1])
        if lo == hi:
            continue
        sub = dataclasses.replace(batch, **{
            f: jax.device_put(getattr(batch, f)[:, lo:hi], dev)
            for f in per_class
        })
        us_p.append(np.asarray(us_tensor(sub), np.float32))
        fe_p.append(np.asarray(hard_feasible(sub)))
    return np.concatenate(us_p, axis=1), np.concatenate(fe_p, axis=1)


@functools.lru_cache(maxsize=32)
def _hier_runner_impl(
    cells_fn, ccfg: CongestionConfig, acfg: AdmissionConfig,
    keep_pre: bool = False,
):
    """The hierarchical fleet's jitted vmap-over-reps-of-scan-over-frames
    runner, cached by (allocator backend fn, congestion config, admission
    config) — the hier twin of :func:`_fleet_runner_impl`.

    Scan inputs per frame: the padded *class* instance, the precomputed
    utility/feasibility tensors, the class queueing delays, and the member
    counts.  Admission control mirrors the dense step's order at class
    granularity: deadline shedding masks feasibility *before* the
    allocator (against the pre-frame backlog-only inflation estimate,
    evaluated on the count-weighted class representative), the queue cap
    refuses allocated cells *after* it and before the committed work
    enters the backlog — exact per-request semantics whenever classes are
    singletons or exact duplicates (the parity tests' scenarios), a
    representative approximation otherwise.

    ``keep_pre`` (only valid with congestion off): the keep mask is
    carry-independent (unit inflation makes admission's candidate test
    bitwise ``hard_feasible``), so the window builder reduces it from the
    feas tensor up front and ships it in the ``tq`` slot — the step
    then never touches ``inst.acc``/``ctime``/``avail``/``A``/``C``, and
    the caller passes slim dummies for them instead of transferring three
    ``(R, T, Cp, M, L)`` tensors per window.
    """
    shed = acfg.enabled and acfg.shed
    if keep_pre and ccfg.enabled:
        raise ValueError("keep_pre requires the congestion model off")

    def step(carry, x):
        bg, be = carry
        inst, us, feas, tq_c, count = x
        if ccfg.enabled:  # the allocator sees backlog-reduced budgets
            g_run = effective_capacity(inst.gamma, bg)
            e_run = effective_capacity(inst.eta, be)
        else:
            g_run, e_run = inst.gamma, inst.eta
        keep = None
        if shed:
            if keep_pre:  # tq slot carries the precomputed mask
                keep = tq_c
            else:
                phi_pc, phi_pe = predicted_inflation(
                    bg, be, inst.gamma, inst.eta, ccfg
                )
                keep = admission_keep(inst, tq_c, phi_pc, phi_pe)
            feas = feas & keep[:, None, None]
        take, start = cells_fn(
            us, feas, inst.v, inst.u, inst.cover, count, g_run, e_run
        )
        n_refused = jnp.int32(0)
        if acfg.enabled:  # queue cap: refuse cells on over-backlogged servers
            M = inst.gamma.shape[0]
            over_c = bg >= acfg.queue_cap_mult * inst.gamma
            over_e = be >= acfg.queue_cap_mult * inst.eta
            offl = jnp.arange(M)[None, :, None] != inst.cover[:, None, None]
            refuse = (take > 0) & (
                over_c[None, :, None] | (offl & over_e[inst.cover][:, None, None])
            )
            n_refused = jnp.sum(jnp.where(refuse, take, 0))
            take = jnp.where(refuse, 0, take)
        n_shed = (
            jnp.sum(jnp.where(keep, 0, count)) if keep is not None
            else jnp.int32(0)
        )
        tf = take.astype(jnp.float32)
        w = jnp.sum(tf * inst.v, axis=(0, 2))          # (M,) committed compute
        # inst.u is zero at local cells, so the per-class sum is exactly the
        # offloaded communication charged to the covering edge
        c_load = jnp.zeros_like(w).at[inst.cover].add(
            jnp.sum(tf * inst.u, axis=(1, 2))
        )
        if ccfg.enabled:
            pc = compute_inflation(bg + w, inst.gamma, ccfg)
            pe = comm_inflation(be + c_load, inst.eta, ccfg)
            bg = step_backlog(bg, w, inst.gamma, ccfg)
            be = step_backlog(be, c_load, inst.eta, ccfg)
        else:
            pc = jnp.ones_like(inst.gamma)
            pe = jnp.ones_like(inst.eta)
        return (bg, be), (take, start, pc, pe, w, c_load, n_shed, n_refused,
                          bg, be)

    def per_rep(c0, inst_seq, us_seq, feas_seq, tq_seq, cnt_seq):
        return jax.lax.scan(
            step, c0, (inst_seq, us_seq, feas_seq, tq_seq, cnt_seq)
        )

    return jax.jit(jax.vmap(per_rep))


def _simulate_fleet_hier(
    spec: ClusterSpec,
    cfg: SimConfig,
    scn: Scenario,
    sources: List[_RepFrameSource],
    *,
    n_rep: int,
    T: int,
    W: int,
    opts: EngineOptions,
    n_dev: int = 1,
    gen_s: float = 0.0,
    engine: Optional[ResilienceEngine] = None,
    metrics: bool = False,
    sw: Optional[Stopwatch] = None,
    t_run0: Optional[float] = None,
) -> FleetResult:
    """Class-aggregate fleet path for ``EngineOptions(scheduler="hierarchical")``.

    Never materializes a dense ``N x M x L`` request grid: each frame's
    arrivals are bucketed into QoS classes
    (:func:`repro.core.aggregation.aggregate_requests`), the count-weighted
    class representatives become one padded ``Cp x M x L`` candidate grid
    per (replication, frame), and the analytic allocator
    (:func:`repro.core.aggregation.hier_cells` — jitted XLA scan or the
    fused Pallas kernel, per ``opts.backend`` / ``REPRO_GUS_BACKEND``) runs
    *inside* the same vmap-over-R / ``lax.scan``-over-T / prefetch pipeline
    as the dense path, with the congestion backlog as the scan carry and
    class-level admission control (deadline shedding + queue caps) inside
    the jitted step.  ``REPRO_HIER_HOST_LOOP=1`` routes to the retained
    PR-9 per-window host loop (:func:`_simulate_fleet_hier_host`), the
    baseline the scaling benchmark compares against.

    Satisfaction is accounted **per member** on the host after each window:
    the fixed-shape ``(take, start)`` cells deaggregate deterministically
    (ascending member index within each class), and every allocated
    member's realized accuracy / completion time is recomputed with its
    *own* size, queueing delay, and — when impairments are on — the frame's
    per-edge link draw, using the exact op sequence of
    :func:`_frame_arrays`; the class mean only ever steers the allocation,
    never the accounting.  Memory and schedule time still scale with the
    class count, which is what sustains 10^5+ users per frame.
    """
    if os.environ.get("REPRO_HIER_HOST_LOOP", "0") not in ("0", "", "false", "False"):
        return _simulate_fleet_hier_host(
            spec, cfg, scn, sources, n_rep=n_rep, T=T, W=W, gen_s=gen_s,
            engine=engine, metrics=metrics, sw=sw, t_run0=t_run0,
        )
    from .aggregation import QuantizationConfig, aggregate_requests, hier_backend_fn

    ccfg = cfg.congestion
    acfg = cfg.admission
    M = spec.n_servers
    n_edge = spec.n_edge
    prefetch = opts.prefetch
    if sw is None:
        sw = Stopwatch()
    if t_run0 is None:
        t_run0 = time.perf_counter()
    quant = QuantizationConfig()
    edges_q = np.asarray(QOS_ACC_EDGES, np.float64)
    nq = len(QOS_ACC_EDGES) + 1
    cells_fn = hier_backend_fn(opts.backend)
    # congestion off -> the shed mask is carry-independent: precompute it in
    # the (overlappable) window build and dispatch a slim instance
    keep_pre = acfg.enabled and acfg.shed and not ccfg.enabled
    run = _hier_runner_impl(cells_fn, ccfg, acfg, keep_pre)
    # lean grid build: skip host-materializing the spec-gather candidate
    # tensors and rebuild them on device (valid whenever the runner's keep
    # mask is precomputable and the class axis is not host-sharded)
    lean = keep_pre and n_dev <= 1
    if lean:
        spec_acc_j = jnp.asarray(spec.acc, jnp.float32)
        spec_placed_tj = jnp.asarray(np.transpose(spec.placed, (1, 0, 2)))
        spec_proc_tj = jnp.asarray(
            np.transpose(spec.proc_ms, (1, 0, 2)), jnp.float32
        )

    reqs_per_rep = np.zeros(n_rep, np.int64)
    served_per_rep = np.zeros(n_rep, np.int64)
    sat_per_rep = np.zeros(n_rep, np.int64)
    us_sum_per_rep = np.zeros(n_rep, np.float64)
    phi_sum = 0.0
    phi_cnt = 0
    m_acc: Optional[Dict[str, np.ndarray]] = None
    if metrics:
        m_acc = {
            "n_arrivals": np.zeros((n_rep, T), np.int32),
            "n_served": np.zeros((n_rep, T), np.int32),
            "n_satisfied": np.zeros((n_rep, T), np.int32),
            "n_shed": np.zeros((n_rep, T), np.int32),
            "n_refused": np.zeros((n_rep, T), np.int32),
            "tier_hist": np.zeros((n_rep, T, 3), np.int32),
            "qos_sat": np.zeros((n_rep, T, nq), np.int32),
            "qos_count": np.zeros((n_rep, T, nq), np.int32),
            "util_gamma": np.zeros((n_rep, T, M), np.float32),
            "util_eta": np.zeros((n_rep, T, M), np.float32),
            "backlog_gamma": np.zeros((n_rep, T, M), np.float32),
            "backlog_eta": np.zeros((n_rep, T, M), np.float32),
            "us_sum": np.zeros((n_rep, T), np.float32),
        }

    def build_window(t0: int):
        """Host-side build of one window: aggregate every (rep, frame) into
        sorted classes, assemble the padded class grid, and precompute the
        class tensors.  Pure numpy + the sources' own RNGs, so it runs
        unchanged inline (``prefetch=0``) or on the producer thread."""
        t1 = min(t0 + W, T)
        Tc = t1 - t0
        with sw.span("fleet/hier_build", CAT_BUILD, t0=t0):
            gb, eb = _frame_budgets_batch(
                spec, cfg, scn, (t0 + np.arange(Tc)) * cfg.frame_ms, engine=engine,
            )
            budgets_by_k = [(gb[k], eb[k]) for k in range(Tc)]
            links_by_k = (
                [engine.link_frame(t0 + k) for k in range(Tc)]
                if engine is not None else None
            )
        frames_rc: List[RequestColumns] = []
        frame_starts: List[float] = []
        infos: List[List[Optional[dict]]] = []
        n_arr = np.zeros((n_rep, Tc), np.int32)
        n_cls = np.zeros((n_rep, Tc), np.int32)
        for rep, src in enumerate(sources):
            with sw.span("fleet/arrivals", CAT_GEN, t0=t0, rep=rep):
                buckets = src.take(t1)
            rep_infos: List[Optional[dict]] = []
            for k, bucket in enumerate(buckets):
                frame_start = (t0 + k) * cfg.frame_ms
                frame_end = frame_start + cfg.frame_ms
                frame_starts.append(frame_start)
                n = len(bucket)
                n_arr[rep, k] = n
                if not n:
                    z = np.zeros(0)
                    frames_rc.append(RequestColumns(
                        arrival_ms=z, cover=np.zeros(0, np.int64),
                        service=np.zeros(0, np.int64), A=z, C=z, size_bytes=z,
                    ))
                    rep_infos.append(None)
                    continue
                if isinstance(bucket, RequestColumns):
                    cov, svc = bucket.cover, bucket.service
                    A_r, C_r = bucket.A, bucket.C
                    size = bucket.size_bytes
                    arr_ms = bucket.arrival_ms
                else:
                    cov = np.array([r.cover for r in bucket], np.int64)
                    svc = np.array([r.service for r in bucket], np.int64)
                    A_r = np.array([r.A for r in bucket], np.float64)
                    C_r = np.array([r.C for r in bucket], np.float64)
                    size = np.array([r.size_bytes for r in bucket], np.float64)
                    arr_ms = np.array([r.arrival_ms for r in bucket], np.float64)
                with sw.span("fleet/hier_aggregate", CAT_BUILD, frame=t0 + k):
                    tq = frame_end - np.asarray(arr_ms, np.float64)
                    count, first_idx, members, offsets, repc = (
                        aggregate_requests(cov, svc, A_r, C_r, size, tq, quant)
                    )
                    # allocation order is by first member index — sort once
                    # here so the device allocator walks classes in order
                    order = np.argsort(first_idx, kind="stable")
                    n_c = count.shape[0]
                    rank = np.empty(n_c, np.int64)
                    rank[order] = np.arange(n_c)
                    cls_of_member = np.repeat(np.arange(n_c), count)
                    members_s = members[
                        np.argsort(rank[cls_of_member], kind="stable")
                    ]
                    count_s = count[order]
                    frames_rc.append(RequestColumns(
                        arrival_ms=frame_end - repc["tq"][order],
                        cover=repc["cover"][order],
                        service=repc["service"][order],
                        A=repc["A"][order],
                        C=repc["C"][order],
                        size_bytes=repc["size"][order],
                    ))
                    n_cls[rep, k] = n_c
                    rep_infos.append(dict(
                        members_s=members_s,
                        off_s=np.concatenate([[0], np.cumsum(count_s)]),
                        count_s=count_s,
                        tq_s=repc["tq"][order],
                        cov=cov, svc=svc, A=A_r, C=C_r, size=size, tq=tq,
                    ))
            infos.append(rep_infos)
        Cp = _pad_bucket_fine(int(n_cls.max())) if frames_rc else 4
        with sw.span("fleet/grid_build", CAT_BUILD, t0=t0):
            built = _build_frame_batch(
                frames_rc, spec, cfg, frame_starts, budgets_by_k * n_rep, Cp,
                links=None if links_by_k is None else links_by_k * n_rep,
                lean=lean,
            )  # leading axis: n_rep * Tc class frames
            if lean:
                batch, svc_p, size_p = built
                us_w, feas_w, v_w, u_w = _us_feas_lean(
                    batch.ctime, batch.A, batch.C, batch.w_a, batch.w_c,
                    batch.max_as, batch.max_cs, batch.cover, svc_p, size_p,
                    spec_acc_j, spec_placed_tj, spec_proc_tj,
                )
            else:
                batch = built
                us_w, feas_w = _hier_class_tensors(batch, n_dev)
            batch_rt = jax.tree.map(
                lambda x: np.asarray(x).reshape((n_rep, Tc) + x.shape[1:]), batch
            )
            us_rt = us_w.reshape(n_rep, Tc, Cp, M, -1)
            feas_rt = feas_w.reshape(n_rep, Tc, Cp, M, -1)
            cnt_rt = np.zeros((n_rep, Tc, Cp), np.int32)
            tq_rt = np.zeros((n_rep, Tc, Cp), np.float32)
            for rep in range(n_rep):
                for k in range(Tc):
                    info = infos[rep][k]
                    if info is None:
                        continue
                    nc = info["count_s"].shape[0]
                    cnt_rt[rep, k, :nc] = info["count_s"]
                    tq_rt[rep, k, :nc] = info["tq_s"]
            if keep_pre:
                # at unit inflation admission's candidate test is exactly
                # hard_feasible (the phi-1 additions in congested_ctime are
                # exact zeros), so the shed mask is a free reduction of the
                # feas tensor already in hand; slim the dispatched instance:
                # the runner never reads the per-cell candidate tensors, so
                # their H2D transfer would be pure waste
                tq_rt = feas_rt.any(axis=(-1, -2))
                slim5 = np.zeros((n_rep, Tc, 1, 1, 1), np.float32)
                slim3 = np.zeros((n_rep, Tc, 1), np.float32)
                batch_rt = dataclasses.replace(
                    batch_rt,
                    acc=slim5, ctime=slim5,
                    avail=np.zeros((n_rep, Tc, 1, 1, 1), bool),
                    A=slim3, C=slim3, w_a=slim3, w_c=slim3,
                )
                if lean:  # the allocator's load tensors, rebuilt on device
                    batch_rt = dataclasses.replace(
                        batch_rt,
                        v=v_w.reshape(n_rep, Tc, Cp, M, -1),
                        u=u_w.reshape(n_rep, Tc, Cp, M, -1),
                    )
            if n_dev <= 1:
                # commit the runner's inputs on the producer side so the
                # dispatch thread never pays the H2D copies for the big
                # class tensors — with prefetch they land here, overlapped
                batch_rt = jax.tree.map(jnp.asarray, batch_rt)
                cnt_rt = jnp.asarray(cnt_rt)
                tq_rt = jnp.asarray(tq_rt)
        return (t0, t1, Tc, batch_rt, us_rt, feas_rt, tq_rt, cnt_rt, infos,
                gb, eb, links_by_k, n_arr)

    window_starts = list(range(0, T, W))
    prod_thread = None
    if prefetch > 0 and len(window_starts) > 0:
        work_q: queue_mod.Queue = queue_mod.Queue(maxsize=prefetch)
        stop_producer = threading.Event()

        def _offer(item) -> bool:
            while not stop_producer.is_set():
                try:
                    work_q.put(item, timeout=0.05)
                    return True
                except queue_mod.Full:
                    continue
            return False

        def _produce():
            try:
                for t0 in window_starts:
                    if not _offer(build_window(t0)):
                        return
            except BaseException as e:  # delivered to the consumer's get()
                _offer(e)

        prod_thread = threading.Thread(
            target=_produce, name="fleet-hier-producer", daemon=True
        )
        prod_thread.start()

    def next_window(t0: int):
        if prod_thread is None:
            return build_window(t0)
        item = work_q.get()
        if isinstance(item, BaseException):
            raise item
        return item

    carry = (jnp.zeros((n_rep, M), jnp.float32), jnp.zeros((n_rep, M), jnp.float32))
    bw_true = spec.bandwidth_true
    try:
        def _post_window(wi, t0, Tc, infos, gb, eb, links_by_k, n_arr, outs):
            """Host-side accounting for one dispatched window.  Called one
            window *behind* the dispatch loop: ``outs`` are still-async
            device futures at enqueue time, and draining them here — after
            the next window's computation has already been issued — keeps
            the device busy while the host deaggregates members."""
            nonlocal phi_sum, phi_cnt, reqs_per_rep
            with sw.span("fleet/hier_drain", CAT_DISPATCH, window=wi):
                (take_a, start_a, pc_a, pe_a, w_a_, c_a, shed_a, ref_a,
                 bg_a, be_a) = jax.tree.map(np.asarray, outs)
            with sw.span("fleet/hier_deaggregate", CAT_METRICS, window=wi):
                if ccfg.enabled:
                    phi_sum += float(pc_a.sum())
                    phi_cnt += pc_a.size
                reqs_per_rep += n_arr.sum(1)
                for rep in range(n_rep):
                    for k in range(Tc):
                        tf_idx = t0 + k
                        info = infos[rep][k]
                        g_full, e_full = gb[k], eb[k]
                        if metrics:
                            m_acc["n_arrivals"][rep, tf_idx] = n_arr[rep, k]
                            m_acc["n_shed"][rep, tf_idx] = shed_a[rep, k]
                            m_acc["n_refused"][rep, tf_idx] = ref_a[rep, k]
                            with np.errstate(invalid="ignore"):
                                m_acc["util_gamma"][rep, tf_idx] = np.where(
                                    g_full > 0.0,
                                    w_a_[rep, k] / np.maximum(g_full, 1e-9), 0.0,
                                )
                                m_acc["util_eta"][rep, tf_idx] = np.where(
                                    e_full > 0.0,
                                    c_a[rep, k] / np.maximum(e_full, 1e-9), 0.0,
                                )
                            m_acc["backlog_gamma"][rep, tf_idx] = bg_a[rep, k]
                            m_acc["backlog_eta"][rep, tf_idx] = be_a[rep, k]
                        if info is None:
                            continue
                        if metrics:
                            q_all = (info["A"][:, None] >= edges_q).sum(-1)
                            np.add.at(m_acc["qos_count"][rep, tf_idx], q_all, 1)
                        take = take_a[rep, k]
                        ci, jj, ll = np.nonzero(take)
                        if ci.size == 0:
                            continue
                        st = start_a[rep, k][ci, jj, ll]
                        lens = take[ci, jj, ll]
                        tot = int(lens.sum())
                        cellid = np.repeat(np.arange(ci.size), lens)
                        intra = np.arange(tot) - np.repeat(
                            np.cumsum(lens) - lens, lens
                        )
                        base = info["off_s"][ci] + st
                        midx = info["members_s"][base[cellid] + intra]
                        jm = jj[cellid].astype(np.int64)
                        lm = ll[cellid].astype(np.int64)
                        # --- per-member realized accounting: the exact op
                        # sequence of _frame_arrays at the chosen cells, so
                        # every member's channel draw, size, and queueing
                        # delay are its own (not the class mean's)
                        svc_m = info["svc"][midx]
                        cov_m = info["cov"][midx]
                        A_m = info["A"][midx].astype(np.float32)
                        C_m = info["C"][midx].astype(np.float32)
                        Tq_m = info["tq"][midx].astype(np.float32)
                        size_m = info["size"][midx].astype(np.float32)
                        acc_m = spec.acc[svc_m, lm]
                        proc_m = spec.proc_ms[jm, svc_m, lm]
                        local_m = jm == cov_m
                        transfer = size_m / bw_true
                        if links_by_k is not None:  # per-member link draw
                            sc, la = links_by_k[k]
                            transfer = (
                                transfer / np.asarray(sc, np.float64)[cov_m]
                                + np.asarray(la, np.float64)[cov_m]
                            )
                        comm = transfer + np.where(
                            jm >= n_edge, spec.cloud_extra_delay, 0.0
                        )
                        comm = np.where(local_m, 0.0, comm)
                        ct = ((Tq_m + proc_m) + comm).astype(np.float32)
                        if ccfg.enabled:  # congested_ctime, per member
                            pc_k = pc_a[rep, k]
                            pe_k = pe_a[rep, k]
                            comm_f = ct - proc_m - Tq_m
                            ct = (
                                ct
                                + proc_m * (pc_k[jm] - 1.0)
                                + comm_f * (pe_k[cov_m] - 1.0)
                            )
                        sat_m = (acc_m >= A_m) & (ct <= C_m)
                        us_m = (
                            cfg.w_a * (acc_m - A_m) / cfg.max_as
                            + cfg.w_c * (C_m - ct) / cfg.max_cs
                        )
                        served_per_rep[rep] += tot
                        sat_per_rep[rep] += int(sat_m.sum())
                        us_sum_per_rep[rep] += float(us_m.sum())
                        if metrics:
                            m_acc["n_served"][rep, tf_idx] = tot
                            m_acc["n_satisfied"][rep, tf_idx] = int(sat_m.sum())
                            cloud_m = (jm >= n_edge) & ~local_m
                            eo_m = ~local_m & ~cloud_m
                            m_acc["tier_hist"][rep, tf_idx] = (
                                int(local_m.sum()), int(eo_m.sum()),
                                int(cloud_m.sum()),
                            )
                            q_m = (A_m[:, None].astype(np.float64) >= edges_q).sum(-1)
                            np.add.at(
                                m_acc["qos_sat"][rep, tf_idx], q_m,
                                sat_m.astype(np.int64),
                            )
                            m_acc["us_sum"][rep, tf_idx] = float(us_m.sum())

        pending = None
        for wi, wi_t0 in enumerate(window_starts):
            with sw.span("fleet/window_wait", CAT_WAIT, window=wi):
                (t0, t1, Tc, batch_rt, us_rt, feas_rt, tq_rt, cnt_rt, infos,
                 gb, eb, links_by_k, n_arr) = next_window(wi_t0)
            with sw.span("fleet/dispatch", CAT_DISPATCH, step=wi, window=wi):
                carry, outs = run(
                    carry, batch_rt, us_rt, feas_rt, tq_rt, cnt_rt
                )
            if pending is not None:
                _post_window(*pending)
            pending = (wi, t0, Tc, infos, gb, eb, links_by_k, n_arr, outs)
        if pending is not None:
            _post_window(*pending)
    finally:
        if prod_thread is not None:
            stop_producer.set()
            while prod_thread.is_alive():
                try:
                    work_q.get_nowait()
                except queue_mod.Empty:
                    pass
                prod_thread.join(timeout=0.05)
            prod_thread.join()

    final_bg = np.asarray(carry[0])
    # window_wait wraps the inline build (serial) or the producer-queue get
    # (prefetch>0), so it already covers arrivals + aggregation blocking
    gen_s += sw.total("fleet/window_wait")
    timings = sw.as_dict()
    timings["total_s"] = time.perf_counter() - t_run0
    mres = None
    if metrics:
        mres = MetricsResult.from_stacked(
            MetricsFrame(**m_acc),
            t_ms=(np.arange(T) + 1.0) * cfg.frame_ms,
            n_edge=spec.n_edge,
            frame_ms=cfg.frame_ms,
        )
    return FleetResult(
        n_rep=n_rep,
        n_frames=T,
        n_requests=int(reqs_per_rep.sum()),
        n_served=int(served_per_rep.sum()),
        satisfied_per_rep=100.0 * sat_per_rep / np.maximum(reqs_per_rep, 1),
        mean_us_per_rep=us_sum_per_rep / np.maximum(reqs_per_rep, 1),
        final_backlog_per_rep=final_bg if ccfg.enabled else None,
        mean_compute_inflation=(
            phi_sum / phi_cnt if ccfg.enabled and phi_cnt else 1.0
        ),
        n_devices=n_dev,
        window=W,
        dispatch_s=sw.total("fleet/dispatch"),
        gen_s=gen_s,
        prefetch=prefetch if prod_thread is not None else 0,
        timings=timings,
        metrics=mres,
    )


def _simulate_fleet_hier_host(
    spec: ClusterSpec,
    cfg: SimConfig,
    scn: Scenario,
    sources: List[_RepFrameSource],
    *,
    n_rep: int,
    T: int,
    W: int,
    gen_s: float = 0.0,
    engine: Optional[ResilienceEngine] = None,
    metrics: bool = False,
    sw: Optional[Stopwatch] = None,
    t_run0: Optional[float] = None,
) -> FleetResult:
    """The PR-9 per-window *host loop* for the class-aggregate fleet, kept
    as the device pipeline's reference baseline (``REPRO_HIER_HOST_LOOP=1``
    routes here; ``benchmarks/fleet_scale.py`` uses it for the wall-time
    comparison).  Satisfaction is accounted *class-level* from the
    count-weighted representatives, admission control is not evaluated, and
    scheduling runs one frame at a time on the host — the three things
    :func:`_simulate_fleet_hier` fixes.

    Congestion mirrors the scan step in the same order: the scheduler sees
    the backlog-reduced budgets, inflation factors come from committed +
    carried load against the *full* budgets, realized completion times are
    inflated per :func:`repro.core.queueing.congested_ctime`'s formula at
    the chosen cells, and the backlog drains every frame.  Arrivals stream
    window by window (``W`` frames at a time), so long horizons stay
    bounded-memory end to end.
    """
    from .aggregation import AggregateClasses, QuantizationConfig, aggregate_requests, hier_assign

    ccfg = cfg.congestion
    M = spec.n_servers
    n_edge = spec.n_edge
    if sw is None:
        sw = Stopwatch()
    if t_run0 is None:
        t_run0 = time.perf_counter()
    quant = QuantizationConfig()
    edges_q = np.asarray(QOS_ACC_EDGES, np.float64)
    nq = len(QOS_ACC_EDGES) + 1

    reqs_per_rep = np.zeros(n_rep, np.int64)
    served_per_rep = np.zeros(n_rep, np.int64)
    sat_per_rep = np.zeros(n_rep, np.int64)
    us_sum_per_rep = np.zeros(n_rep, np.float64)
    bg = np.zeros((n_rep, M))  # carried compute backlog, f64 like the budgets
    be = np.zeros((n_rep, M))
    phi_sum = 0.0
    phi_cnt = 0
    m_acc: Optional[Dict[str, np.ndarray]] = None
    if metrics:
        m_acc = {
            "n_arrivals": np.zeros((n_rep, T), np.int32),
            "n_served": np.zeros((n_rep, T), np.int32),
            "n_satisfied": np.zeros((n_rep, T), np.int32),
            "n_shed": np.zeros((n_rep, T), np.int32),
            "n_refused": np.zeros((n_rep, T), np.int32),
            "tier_hist": np.zeros((n_rep, T, 3), np.int32),
            "qos_sat": np.zeros((n_rep, T, nq), np.int32),
            "qos_count": np.zeros((n_rep, T, nq), np.int32),
            "util_gamma": np.zeros((n_rep, T, M), np.float32),
            "util_eta": np.zeros((n_rep, T, M), np.float32),
            "backlog_gamma": np.zeros((n_rep, T, M), np.float32),
            "backlog_eta": np.zeros((n_rep, T, M), np.float32),
            "us_sum": np.zeros((n_rep, T), np.float32),
        }

    for t0 in range(0, T, W):
        t1 = min(t0 + W, T)
        Tc = t1 - t0
        with sw.span("fleet/hier_build", CAT_BUILD, t0=t0):
            gb, eb = _frame_budgets_batch(
                spec, cfg, scn, (t0 + np.arange(Tc)) * cfg.frame_ms, engine=engine,
            )
            links = (
                [engine.link_frame(t0 + k) for k in range(Tc)]
                if engine is not None else None
            )
        for rep, src in enumerate(sources):
            with sw.span("fleet/arrivals", CAT_GEN, t0=t0, rep=rep):
                buckets = src.take(t1)
            for k, bucket in enumerate(buckets):
                tf = t0 + k
                n = len(bucket)
                reqs_per_rep[rep] += n
                frame_end = (tf + 1) * cfg.frame_ms
                g_full, e_full = gb[k], eb[k]
                w_load = np.zeros(M)
                c_load = np.zeros(M)
                chunks = np.zeros((0, 4), np.int64)
                if n:
                    if isinstance(bucket, RequestColumns):
                        cov, svc = bucket.cover, bucket.service
                        A_r, C_r = bucket.A, bucket.C
                        size = bucket.size_bytes
                        arr_ms = bucket.arrival_ms
                    else:
                        cov = np.array([r.cover for r in bucket], np.int64)
                        svc = np.array([r.service for r in bucket], np.int64)
                        A_r = np.array([r.A for r in bucket], np.float64)
                        C_r = np.array([r.C for r in bucket], np.float64)
                        size = np.array([r.size_bytes for r in bucket], np.float64)
                        arr_ms = np.array([r.arrival_ms for r in bucket], np.float64)
                    with sw.span("fleet/hier_aggregate", CAT_BUILD, frame=tf):
                        tq = frame_end - np.asarray(arr_ms, np.float64)
                        count, first_idx, members, offsets, repc = (
                            aggregate_requests(cov, svc, A_r, C_r, size, tq, quant)
                        )
                        rc = RequestColumns(
                            arrival_ms=frame_end - repc["tq"],
                            cover=repc["cover"],
                            service=repc["service"],
                            A=repc["A"],
                            C=repc["C"],
                            size_bytes=repc["size"],
                        )
                        link = None
                        if links is not None:
                            sc, la = links[k]
                            link = (sc[repc["cover"]], la[repc["cover"]])
                        if ccfg.enabled:  # scheduler sees effective capacity
                            g_sched = np.maximum(g_full - bg[rep], 0.0)
                            e_sched = np.maximum(e_full - be[rep], 0.0)
                        else:
                            g_sched, e_sched = g_full, e_full
                        cls_inst = _build_frame_instance(
                            rc, spec, cfg, frame_end, spec.bandwidth_true,
                            cfg.max_cs, gamma=g_sched, eta=e_sched, link=link,
                        )
                        agg = AggregateClasses(
                            count=count, first_idx=first_idx, members=members,
                            offsets=offsets, cover=repc["cover"],
                            us=np.asarray(us_tensor(cls_inst)),
                            feas=np.asarray(hard_feasible(cls_inst)),
                            v=np.asarray(cls_inst.v),
                            u=np.asarray(cls_inst.u),
                        )
                    with sw.span("fleet/schedule_hier", CAT_SCHED, frame=tf):
                        chunks = hier_assign(agg, g_sched, e_sched, exact=False)
                    if len(chunks):
                        cc, jj, ll, take = (chunks[:, i] for i in range(4))
                        vv = agg.v[cc, jj, ll].astype(np.float64)
                        uu = agg.u[cc, jj, ll].astype(np.float64)
                        np.add.at(w_load, jj, take * vv)
                        off_m = jj != agg.cover[cc]
                        if off_m.any():
                            np.add.at(
                                c_load, agg.cover[cc][off_m], (take * uu)[off_m]
                            )
                # inflation from committed + carried load vs the FULL budgets
                # (the scan's order: schedule, commit, inflate, drain)
                if ccfg.enabled:
                    phi_c = np.asarray(
                        compute_inflation(bg[rep] + w_load, g_full, ccfg), np.float64
                    )
                    phi_e = np.asarray(
                        comm_inflation(be[rep] + c_load, e_full, ccfg), np.float64
                    )
                    phi_sum += float(phi_c.sum())
                    phi_cnt += M
                if len(chunks):
                    ct = np.asarray(cls_inst.ctime, np.float64)[cc, jj, ll]
                    acc_c = np.asarray(cls_inst.acc, np.float64)[cc, jj, ll]
                    if ccfg.enabled:  # congested_ctime's formula, class-level
                        comm = ct - vv - repc["tq"][cc]
                        ct = (
                            ct
                            + vv * (phi_c[jj] - 1.0)
                            + comm * (phi_e[agg.cover[cc]] - 1.0)
                        )
                    A_c = repc["A"][cc]
                    C_c = repc["C"][cc]
                    sat_c = (acc_c >= A_c) & (ct <= C_c)
                    us_c = (
                        cfg.w_a * (acc_c - A_c) / cfg.max_as
                        + cfg.w_c * (C_c - ct) / cfg.max_cs
                    )
                    served_per_rep[rep] += int(take.sum())
                    sat_per_rep[rep] += int((take * sat_c).sum())
                    us_sum_per_rep[rep] += float((take * us_c).sum())
                if ccfg.enabled:  # backlog conservation: see step_backlog
                    bg[rep] = np.maximum(
                        bg[rep] + w_load - g_full * ccfg.drain, 0.0
                    )
                    be[rep] = np.maximum(
                        be[rep] + c_load - e_full * ccfg.drain, 0.0
                    )
                if metrics:
                    m_acc["n_arrivals"][rep, tf] = n
                    if n:
                        cls_q = (repc["A"][:, None] >= edges_q).sum(-1)
                        np.add.at(m_acc["qos_count"][rep, tf], cls_q, count)
                    if len(chunks):
                        m_acc["n_served"][rep, tf] = int(take.sum())
                        m_acc["n_satisfied"][rep, tf] = int((take * sat_c).sum())
                        local_m = jj == agg.cover[cc]
                        cloud_m = (jj >= n_edge) & ~local_m
                        eo_m = ~local_m & ~cloud_m
                        m_acc["tier_hist"][rep, tf] = (
                            int(take[local_m].sum()),
                            int(take[eo_m].sum()),
                            int(take[cloud_m].sum()),
                        )
                        np.add.at(
                            m_acc["qos_sat"][rep, tf], cls_q[cc],
                            (take * sat_c).astype(np.int64),
                        )
                        m_acc["us_sum"][rep, tf] = float((take * us_c).sum())
                    with np.errstate(invalid="ignore"):
                        m_acc["util_gamma"][rep, tf] = np.where(
                            g_full > 0.0, w_load / np.maximum(g_full, 1e-9), 0.0
                        )
                        m_acc["util_eta"][rep, tf] = np.where(
                            e_full > 0.0, c_load / np.maximum(e_full, 1e-9), 0.0
                        )
                    m_acc["backlog_gamma"][rep, tf] = bg[rep]
                    m_acc["backlog_eta"][rep, tf] = be[rep]

    gen_s += sw.total("fleet/arrivals")
    timings = sw.as_dict()
    timings["total_s"] = time.perf_counter() - t_run0
    mres = None
    if metrics:
        mres = MetricsResult.from_stacked(
            MetricsFrame(**m_acc),
            t_ms=(np.arange(T) + 1.0) * cfg.frame_ms,
            n_edge=spec.n_edge,
            frame_ms=cfg.frame_ms,
        )
    return FleetResult(
        n_rep=n_rep,
        n_frames=T,
        n_requests=int(reqs_per_rep.sum()),
        n_served=int(served_per_rep.sum()),
        satisfied_per_rep=100.0 * sat_per_rep / np.maximum(reqs_per_rep, 1),
        mean_us_per_rep=us_sum_per_rep / np.maximum(reqs_per_rep, 1),
        final_backlog_per_rep=bg.astype(np.float32) if ccfg.enabled else None,
        mean_compute_inflation=(
            phi_sum / phi_cnt if ccfg.enabled and phi_cnt else 1.0
        ),
        n_devices=1,
        window=W,
        dispatch_s=sw.total("fleet/schedule_hier"),
        gen_s=gen_s,
        timings=timings,
        metrics=mres,
    )


def demo_cluster_spec(
    n_edge: int = 4,
    n_cloud: int = 1,
    n_services: int = 3,
    n_variants: int = 3,
    seed: int = 0,
) -> ClusterSpec:
    """A small heterogeneous cluster for examples, sweeps and smoke tests.

    Edges run the cheaper variants of every service at ~1 s latencies (the
    paper's RPi-class boxes); the cloud runs everything ~4x faster but costs
    a backhaul hop.  Deterministic given ``seed``.
    """
    rng = np.random.default_rng(seed)
    M = n_edge + n_cloud
    K, L = n_services, n_variants

    rel = np.geomspace(0.3, 1.0, L)                    # variant cost ladder
    acc = np.linspace(55.0, 85.0, L)[None, :] + rng.normal(0.0, 1.5, (K, L))
    acc = np.clip(np.sort(acc, axis=1), 1.0, 99.0).astype(np.float32)

    proc = np.empty((M, K, L), np.float32)
    placed = np.zeros((M, K, L), bool)
    for j in range(M):
        is_cloud = j >= n_edge
        base = 300.0 if is_cloud else rng.uniform(900.0, 1400.0)
        proc[j] = base * rel[None, :] * rng.uniform(0.95, 1.05, (K, L))
        placed[j] = True
        if not is_cloud and L > 1:
            placed[j, :, L - 1] = False  # biggest variant is cloud-only

    gamma = np.where(np.arange(M) >= n_edge, 12_000.0, 3900.0).astype(np.float32)
    eta = np.where(np.arange(M) >= n_edge, 3500.0, 350.0).astype(np.float32)
    return ClusterSpec(
        n_edge=n_edge,
        n_cloud=n_cloud,
        gamma_frame=gamma,
        eta_frame=eta,
        proc_ms=proc,
        placed=placed,
        acc=acc,
    )
