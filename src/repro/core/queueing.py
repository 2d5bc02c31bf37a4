"""Congestion subsystem — load-dependent service times and the policy carry.

The paper's headline result (GUS beats every baseline by >= 50% satisfied
users) only emerges on the *testbed*, where over-committed servers slow
down: the Happy-Computation / Happy-Communication relaxations, which ignore
a capacity constraint, collapse under real congestion.  The numerical model
treats processing delay as load-independent, so those two policies act as
unreachable upper bounds instead.  This module closes that gap with a
capacity-overcommit inflation model shared by both simulators:

* every server *j* carries a **backlog** ``b_j`` of unfinished work
  (chip-ms for compute, KB for communication) across frames;
* a frame that commits work ``w_j`` against budget ``g_j`` runs at
  utilization ``rho_j = (b_j + w_j) / g_j``; realized processing and
  transfer times inflate by ``phi = 1 + slope * max(0, rho - 1) ** power``
  (capped at ``max_inflation``) — at or below budget nothing slows down;
* the backlog then **drains** at the frame budget:
  ``b' = max(0, b + w - g * drain)``;
* the *scheduler* sees the congestion only through a reduced frame budget
  ``max(g - b, 0)`` — capacity-honoring policies adapt, the Happy-*
  relaxations keep over-committing and spiral.

Every function is pure ``jax.numpy`` and shape-polymorphic, so the same
code runs in the sequential testbed's host loop and inside
``simulate_fleet``'s ``lax.scan`` (the backlog is the scan carry).  With
``CongestionConfig(enabled=False)`` (the default) the simulators skip the
model entirely and results are bit-identical to the congestion-free path.

:class:`PolicyCarry` generalizes the simulator's per-frame PRNG-key
threading into an explicit state object threaded through ``simulate``'s
frame loop and ``simulate_fleet``'s scan: the key chain, the per-server
backlogs, an EMA load estimate, and the paper's bandwidth-estimator state.
A :class:`~repro.core.policies.Policy` registered with ``stateful=True``
receives the whole carry and returns an updated one — the hook for
learned/adaptive schedulers (the backlog and bandwidth fields stay
simulator-owned; ``ema_util`` and ``key`` are policy-usable).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import QOS_ACC_EDGES, MetricsFrame

from .instance import FlatInstance
from .satisfaction import mean_us, satisfied_mask

__all__ = [
    "CongestionConfig",
    "PolicyCarry",
    "init_policy_carry",
    "fleet_policy_carry",
    "compute_inflation",
    "comm_inflation",
    "step_backlog",
    "committed_loads",
    "ema_update",
    "effective_capacity",
    "congested_ctime",
    "CONGESTED_CTIME_LEAVES",
    "frame_utilization",
    "frame_metrics",
]

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class CongestionConfig:
    """Parameters of the capacity-overcommit inflation model.

    ``enabled=False`` (the default) turns the whole subsystem off: the
    simulators skip every congestion computation and results are
    bit-identical to the pre-congestion code paths.
    """

    enabled: bool = False
    #: inflation slope per unit of compute over-commit (rho - 1)
    compute_slope: float = 4.0
    #: inflation slope per unit of communication over-commit
    comm_slope: float = 4.0
    #: exponent on the over-commit ratio; the default 2 is superlinear, the
    #: M/G/1 flavour — mild over-commit costs little, deep over-commit spirals
    power: float = 2.0
    #: fraction of the frame budget available to drain carried backlog
    drain: float = 1.0
    #: hard cap on the inflation factor (keeps a dead server finite)
    max_inflation: float = 100.0
    #: smoothing of the per-server EMA utilization estimate in the carry
    ema_alpha: float = 0.2


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PolicyCarry:
    """Explicit per-replication state threaded across frames.

    Fields (``M`` = number of servers):

    * ``key`` — ``jax.random`` key chain.  Simulator-owned for ``needs_key``
      policies (one subkey split per frame decision); a ``stateful`` policy
      owns it and splits for itself.
    * ``backlog_gamma`` — ``(M,)`` carried compute backlog (chip-ms).
    * ``backlog_eta`` — ``(M,)`` carried communication backlog (KB).
    * ``ema_util`` — ``(M,)`` EMA of per-server committed compute
      utilization (policy-readable load estimate).
    * ``bw_prev`` / ``bw_cur`` — the paper's bandwidth-estimator state
      ``B_{t-1}``, ``B_t`` (sequential testbed only; the fleet schedules
      with the true mean bandwidth).
    * ``link_bw`` — ``(M,)`` this frame's per-edge link bandwidth scale
      from the resilience engine (:mod:`repro.core.impairments`); all ones
      when impairments are disabled.  Simulator-owned, policy-readable.
    * ``server_up`` — ``(M,)`` this frame's up/down vector from the outage
      stream (1.0 = up); all ones when disabled.  Simulator-owned,
      policy-readable (the hook ``gus-adaptive`` uses to route around
      down servers).
    """

    key: jnp.ndarray
    backlog_gamma: jnp.ndarray
    backlog_eta: jnp.ndarray
    ema_util: jnp.ndarray
    bw_prev: jnp.ndarray
    bw_cur: jnp.ndarray
    link_bw: jnp.ndarray
    server_up: jnp.ndarray


def init_policy_carry(
    n_servers: int, *, seed: int = 0, bandwidth_init: float = 0.0
) -> PolicyCarry:
    """A fresh carry: empty backlogs, zero EMA, key chain seeded by ``seed``."""
    return PolicyCarry(
        key=jax.random.PRNGKey(seed),
        backlog_gamma=jnp.zeros((n_servers,), jnp.float32),
        backlog_eta=jnp.zeros((n_servers,), jnp.float32),
        ema_util=jnp.zeros((n_servers,), jnp.float32),
        bw_prev=jnp.float32(bandwidth_init),
        bw_cur=jnp.float32(bandwidth_init),
        link_bw=jnp.ones((n_servers,), jnp.float32),
        server_up=jnp.ones((n_servers,), jnp.float32),
    )


def fleet_policy_carry(
    n_rep: int, n_servers: int, *, seed: int = 0, bandwidth_init: float = 0.0
) -> PolicyCarry:
    """A batched carry for ``simulate_fleet``: one :class:`PolicyCarry` per
    replication, stacked on a leading ``(R,)`` axis.

    Replication ``r``'s key chain is ``fold_in(PRNGKey(seed), r)`` — the
    fleet's legacy per-replication chain — and the leading axis is exactly
    the axis the sharded fleet places across its ``("rep",)`` device mesh,
    so the whole carry pytree shards with ``PartitionSpec("rep")``.
    """
    return PolicyCarry(
        key=jax.vmap(lambda r: jax.random.fold_in(jax.random.PRNGKey(seed), r))(
            jnp.arange(n_rep)
        ),
        backlog_gamma=jnp.zeros((n_rep, n_servers), jnp.float32),
        backlog_eta=jnp.zeros((n_rep, n_servers), jnp.float32),
        ema_util=jnp.zeros((n_rep, n_servers), jnp.float32),
        bw_prev=jnp.full((n_rep,), bandwidth_init, jnp.float32),
        bw_cur=jnp.full((n_rep,), bandwidth_init, jnp.float32),
        link_bw=jnp.ones((n_rep, n_servers), jnp.float32),
        server_up=jnp.ones((n_rep, n_servers), jnp.float32),
    )


def _inflation(load, budget, slope, cfg: CongestionConfig):
    """Service-time inflation ``phi``: 1 at or below budget, then
    ``1 + slope * (rho - 1) ** power`` capped at ``max_inflation``."""
    rho = load / jnp.maximum(budget, _EPS)
    over = jnp.maximum(rho - 1.0, 0.0)
    phi = 1.0 + slope * over ** cfg.power
    return jnp.minimum(phi, cfg.max_inflation)


def compute_inflation(load, budget, cfg: CongestionConfig):
    """(M,) processing-time inflation from committed+carried compute load."""
    return _inflation(load, budget, cfg.compute_slope, cfg)


def comm_inflation(load, budget, cfg: CongestionConfig):
    """(M,) transfer-time inflation from committed+carried comm load."""
    return _inflation(load, budget, cfg.comm_slope, cfg)


def step_backlog(backlog, committed, budget, cfg: CongestionConfig):
    """Next frame's carried backlog: ``max(0, b + w - g * drain)``.

    Conservation: ``b + w == drained + b'`` with
    ``drained = min(b + w, g * drain)`` — work is never created or lost,
    only served this frame or carried to the next.
    """
    return jnp.maximum(backlog + committed - budget * cfg.drain, 0.0)


def effective_capacity(budget, backlog):
    """The budget the *scheduler* sees: ``max(budget - backlog, 0)``.

    A server still working off yesterday's queue offers less fresh
    capacity this frame.  With an empty backlog this is ``budget`` exactly
    (bitwise), which is what keeps the disabled path bit-identical.
    """
    return jnp.maximum(budget - backlog, 0.0)


def committed_loads(
    inst: FlatInstance, assign_j, assign_l
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-server work committed by one frame's assignment.

    Returns ``(w, c)``: ``w[j]`` is the compute (chip-ms, from ``inst.v``)
    scheduled on server *j*; ``c[e]`` is the communication (KB, from
    ``inst.u``) charged against covering edge *e* by offloaded requests.
    Dropped rows (``j < 0``) — including padded rows — contribute nothing.
    """
    M = inst.gamma.shape[-1]
    served = assign_j >= 0
    j = jnp.maximum(assign_j, 0)
    l = jnp.maximum(assign_l, 0)
    idx = jnp.arange(assign_j.shape[-1])
    v_picked = inst.v[idx, j, l]
    u_picked = inst.u[idx, j, l]
    offloaded = served & (assign_j != inst.cover)
    w = jnp.zeros((M,), jnp.float32).at[j].add(jnp.where(served, v_picked, 0.0))
    c = jnp.zeros((M,), jnp.float32).at[inst.cover].add(
        jnp.where(offloaded, u_picked, 0.0)
    )
    return w, c


def ema_update(ema, committed, budget, cfg: CongestionConfig):
    """EMA of per-server committed utilization (``committed / budget``)."""
    util = committed / jnp.maximum(budget, _EPS)
    return (1.0 - cfg.ema_alpha) * ema + cfg.ema_alpha * util


#: the ``FlatInstance`` leaves :func:`congested_ctime` reads
CONGESTED_CTIME_LEAVES = ("ctime", "v", "cover")


def congested_ctime(inst: FlatInstance, tq, phi_c, phi_e) -> jnp.ndarray:
    """Realized completion-time tensor under congestion.

    ``ctime = Tq + proc + comm`` was built load-free; this inflates the
    processing part (``inst.v``) by the serving server's ``phi_c`` and the
    communication part (``ctime - v - Tq``, which includes the cloud
    backhaul constant) by the covering edge's ``phi_e``:

    ``ct' = ctime + v * (phi_c[j] - 1) + comm * (phi_e[cover] - 1)``

    With ``phi == 1`` everywhere this is ``ctime`` bitwise (the additions
    are exact zeros), so one metrics path serves both modes.

    Shapes: ``inst`` leaves ``(N, M, L)``, ``tq`` ``(N,)``, ``phi_c`` /
    ``phi_e`` ``(M,)``; every argument may carry matching leading batch axes.
    """
    comm = inst.ctime - inst.v - tq[..., :, None, None]
    phi_e_cover = jnp.take_along_axis(phi_e, inst.cover, axis=-1)
    return (
        inst.ctime
        + inst.v * (phi_c[..., None, :, None] - 1.0)
        + comm * (phi_e_cover[..., :, None, None] - 1.0)
    )


def frame_utilization(committed, budget) -> jnp.ndarray:
    """Per-server committed-work / frame-budget ratio, 0 where the budget
    is zero (a fully-down server under an outage mask).  Overcommitting
    policies exceed 1 — that *is* the signal the calibration item needs."""
    return jnp.where(budget > 0.0, committed / jnp.maximum(budget, _EPS), 0.0)


def frame_metrics(
    inst: FlatInstance,
    assign_j,
    assign_l,
    tq,
    phi_c,
    phi_e,
    n_real,
    n_edge: int,
    carry: PolicyCarry,
    n_shed,
    n_refused,
    qos_edges: Tuple[float, ...] = QOS_ACC_EDGES,
) -> MetricsFrame:
    """One decision's :class:`~repro.obs.metrics.MetricsFrame`, pure jnp.

    Runs unbatched inside ``simulate_fleet``'s scan step (the scan stacks
    the frame axis, ``vmap`` the replication axis) on the *same* operands
    the result metrics use — ``congested_ctime`` with the step's
    inflation factors (bitwise ``inst.ctime`` when they are all ones), so
    the stream's satisfaction counts match ``FleetResult`` exactly.
    ``n_real`` masks the padded rows; ``carry`` supplies the post-step
    backlogs (the series the Fig. 1(e)-(h) calibration fits against).
    """
    N = assign_j.shape[-1]
    real = jnp.arange(N) < n_real
    served = (assign_j >= 0) & real
    minst = dataclasses.replace(
        inst, ctime=congested_ctime(inst, tq, phi_c, phi_e)
    )
    sat = satisfied_mask(minst, assign_j, assign_l) & real
    local = served & (assign_j == inst.cover)
    cloud = served & (assign_j >= n_edge)
    tier = jnp.stack(
        [local.sum(), (served & ~local & ~cloud).sum(), cloud.sum()]
    ).astype(jnp.int32)
    edges = jnp.asarray(qos_edges, jnp.float32)
    cls = jnp.sum(inst.A[..., :, None] >= edges, axis=-1)
    nq = len(qos_edges) + 1
    qos_count = jnp.zeros((nq,), jnp.int32).at[cls].add(real.astype(jnp.int32))
    qos_sat = jnp.zeros((nq,), jnp.int32).at[cls].add(sat.astype(jnp.int32))
    w, c = committed_loads(inst, assign_j, assign_l)
    return MetricsFrame(
        n_arrivals=jnp.asarray(n_real, jnp.int32),
        n_served=served.sum().astype(jnp.int32),
        n_satisfied=sat.sum().astype(jnp.int32),
        n_shed=jnp.asarray(n_shed, jnp.int32),
        n_refused=jnp.asarray(n_refused, jnp.int32),
        tier_hist=tier,
        qos_sat=qos_sat,
        qos_count=qos_count,
        util_gamma=frame_utilization(w, inst.gamma),
        util_eta=frame_utilization(c, inst.eta),
        backlog_gamma=carry.backlog_gamma,
        backlog_eta=carry.backlog_eta,
        us_sum=(mean_us(minst, assign_j, assign_l) * N).astype(jnp.float32),
    )
