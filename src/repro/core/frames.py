"""The frame layer: arrival sources, padding buckets and per-frame grids.

Everything between a scenario's arrival stream and a scheduler's padded
``FlatInstance`` lives here: the sources that hand out each frame's
requests (:class:`_ArrivalSource` for :func:`~repro.core.simulator.simulate`,
:class:`_RepFrameSource` per fleet replication), the padding buckets, the
per-frame request tensors and the fleet's batched grid builder, and the
per-frame (gamma, eta) budgets.  Every entry point and fleet path in
:mod:`repro.core.simulator` calls into this module; it calls none of them.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .impairments import ResilienceEngine
from .instance import FlatInstance
from .scenarios import (
    Request,
    RequestColumns,
    Scenario,
    _scalar_hook_is_newer,
    bucket_arrivals,
    bucket_columns,
)
from .streaming import ArrivalStream, stream_trace, stream_trace_columns

if TYPE_CHECKING:
    from .simulator import ClusterSpec, SimConfig


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


class _RepFrameSource:
    """One replication's per-frame request buckets, materialized or lazy.

    *Materialized* reproduces the legacy fleet generation bit-for-bit: one
    ``default_rng(seed + rep)`` drives ``generate_arrivals_columns`` (or the trace
    comes from :func:`stream_trace`) and then the per-frame mobility draws.
    *Lazy* holds an :class:`ArrivalStream` and draws each frame's bucket on
    demand, so a windowed fleet never materializes more than one window of
    requests — the stream's chunking invariance makes the buckets (and the
    mobility draw order) identical either way.

    The materialized trace from the replication's own generator stays
    columnar (:class:`RequestColumns` buckets) end to end in both RNG
    modes — the grid builder fills frames from array slices and
    per-request Python objects never exist.  Two traces stay
    :class:`Request` lists: the paper-default stream's, and that of a
    scenario overriding :meth:`~repro.core.scenarios.Scenario.generate_arrivals`
    (its objects are its trace).  In ``rng_mode="vectorized"`` the lazy
    stream uses the chunk-buffered vectorized engine; columnar and lazy
    buckets carry the same values for the same seed (one chunk code path
    underneath), so windowed==materialized holds in both modes.
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
        if lazy:
            self.stream = ArrivalStream(
                scn, rep_seed, n_edge, n_services, cfg, rng_mode=rng_mode
            )
        elif use_stream:
            if rng_mode == "vectorized":
                cols = stream_trace_columns(scn, rep_seed, n_edge, n_services, cfg)
                self.buckets = bucket_columns(cols, cfg.frame_ms, T)
            else:
                reqs = stream_trace(scn, rep_seed, n_edge, n_services, cfg)
                self.buckets = bucket_arrivals(reqs, cfg.frame_ms, T)
        elif _scalar_hook_is_newer(
            type(scn), "generate_arrivals", "generate_arrivals_columns"
        ):
            # a scenario that reshapes the object trace is honoured as such
            reqs = scn.generate_arrivals(
                self.rng, n_edge, n_services, cfg, rng_mode=rng_mode
            )
            self.buckets = bucket_arrivals(reqs, cfg.frame_ms, T)
        else:
            cols = scn.generate_arrivals_columns(
                self.rng, n_edge, n_services, cfg, rng_mode=rng_mode
            )
            self.buckets = bucket_columns(cols, cfg.frame_ms, T)
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
