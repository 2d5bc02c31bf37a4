"""Problem-instance model for the MUS (Maximal User Satisfaction) problem.

The paper indexes decisions X_{ijkl} over requests i, servers j, services k and
model variants l.  Each request asks for exactly one service k_i, so we store
the *flattened* per-request view: every (i, j, l) tensor below has already been
gathered at k = k_i.  This loses no generality and keeps GUS/ILP tensors at
(N, M, L) instead of (N, M, K, L).

All arrays are plain numpy in the generator and converted to a jax pytree
(`FlatInstance`) so the GUS scheduler can jit/vmap over batches of instances.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import CAT_BUILD, span

__all__ = [
    "FlatInstance",
    "GeneratorConfig",
    "generate_instance",
    "generate_batch",
    "stack_instances",
    "pad_instance",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlatInstance:
    """One MUS problem instance, flattened to (N, M, L) request-major tensors.

    Shapes (unbatched):
      cover:  (N,)  int32   covering edge server s_i of request i
      A:      (N,)  f32     requested accuracy floor (same units as `acc`)
      C:      (N,)  f32     requested deadline (ms)
      w_a:    (N,)  f32     accuracy weight in the US metric
      w_c:    (N,)  f32     latency weight in the US metric
      acc:    (N, M, L) f32 accuracy delivered by variant l of service k_i on j
      ctime:  (N, M, L) f32 completion time  T^q_i + T^proc_{j,k_i,l} (+ T^comm)
      v:      (N, M, L) f32 computation cost charged against gamma_j
      u:      (N, M, L) f32 communication cost charged against eta_{s_i} if offloaded
      avail:  (N, M, L) bool service k_i / variant l placed on server j
      gamma:  (M,)  f32     computation capacity per server
      eta:    (M,)  f32     communication capacity per server
      max_as: ()    f32     normalizer: max accuracy in the system
      max_cs: ()    f32     normalizer: worst-case completion time in the system
    """

    cover: jnp.ndarray
    A: jnp.ndarray
    C: jnp.ndarray
    w_a: jnp.ndarray
    w_c: jnp.ndarray
    acc: jnp.ndarray
    ctime: jnp.ndarray
    v: jnp.ndarray
    u: jnp.ndarray
    avail: jnp.ndarray
    gamma: jnp.ndarray
    eta: jnp.ndarray
    max_as: jnp.ndarray
    max_cs: jnp.ndarray

    @property
    def n_requests(self) -> int:
        return self.A.shape[-1]

    @property
    def n_servers(self) -> int:
        return self.gamma.shape[-1]

    @property
    def n_variants(self) -> int:
        return self.acc.shape[-1]

    def is_local(self) -> jnp.ndarray:
        """(N, M) bool: True where server j is the covering server of i."""
        return self.cover[..., :, None] == jnp.arange(self.n_servers)


@dataclasses.dataclass(frozen=True)
class GeneratorConfig:
    """Defaults reproduce the paper's numerical setup (Sec. IV).

    9 heterogeneous edge servers + 1 cloud; |N|=100 requests, |K|=100 services,
    |L|=10 variants; edge T_proc ~ U[950, 1300] ms, cloud 300 ms;
    A_i ~ N(45, 10) [%], C_i ~ N(1000, 4000) ms; T^q ~ U[0, 50] ms;
    Max_as = 100 %, Max_cs = 12000 ms; mean bandwidth 600 bytes/ms.
    """

    n_requests: int = 100
    n_edge: int = 9
    n_cloud: int = 1
    n_services: int = 100
    n_variants: int = 10

    # Requested-QoS distributions (paper Sec. IV).
    acc_req_mean: float = 45.0
    acc_req_std: float = 10.0
    delay_req_mean: float = 1000.0
    delay_req_std: float = 4000.0
    queue_delay_max: float = 50.0
    w_a: float = 1.0
    w_c: float = 1.0

    # System-wide normalizers.
    max_as: float = 100.0
    max_cs: float = 12000.0

    # Processing-delay model: edge ~ U[proc_edge_lo, proc_edge_hi] for the
    # *largest* variant, cheaper variants scale down; cloud is proc_cloud.
    proc_edge_lo: float = 950.0
    proc_edge_hi: float = 1300.0
    proc_cloud: float = 300.0

    # Variant ladder: variant l has relative cost cost_ratio**(L-1-l) and an
    # accuracy that rises with cost (diminishing returns).  Variant L-1 is the
    # biggest/most accurate.
    acc_top: float = 92.0
    acc_bottom: float = 35.0

    # Communication: mean bandwidth (bytes/ms) between servers, request sizes.
    bandwidth: float = 600.0
    req_size_lo: float = 20_000.0   # bytes (e.g. a JPEG)
    req_size_hi: float = 120_000.0
    cloud_extra_delay: float = 100.0  # backhaul ms to reach the cloud tier

    # Capacities.  Three edge hardware classes (paper: "three types of edge
    # servers").  Units: compute = chip-ms per frame, comm = KB per frame.
    edge_compute_classes: tuple = (2600.0, 3900.0, 5200.0)
    edge_comm_classes: tuple = (400.0, 600.0, 800.0)
    cloud_compute: float = 26_000.0
    cloud_comm: float = 6000.0

    # Service placement: edge servers hold a random subset of services whose
    # size depends on their class; cloud holds everything (paper Sec. II).
    edge_services_frac: tuple = (0.25, 0.5, 0.75)
    # Not every variant fits on an edge box; the cheapest `edge_variants`
    # variants are placed on edges, all variants on the cloud.
    edge_variants: int = 6


def _variant_ladder(cfg: GeneratorConfig, rng: np.random.Generator):
    """Per-(service, variant) accuracy and relative cost.

    Accuracy follows a saturating curve in relative model cost with per-service
    jitter, mirroring how e.g. SqueezeNet/GoogleNet trade params for top-1.
    """
    L, K = cfg.n_variants, cfg.n_services
    rel_cost = np.geomspace(0.12, 1.0, L)  # variant 0 cheapest
    # saturating accuracy vs cost + per-service jitter
    base = cfg.acc_bottom + (cfg.acc_top - cfg.acc_bottom) * (
        1.0 - np.exp(-3.0 * rel_cost)
    ) / (1.0 - np.exp(-3.0))
    acc = base[None, :] + rng.normal(0.0, 2.0, size=(K, L))
    acc = np.clip(np.sort(acc, axis=1), 1.0, cfg.max_as)  # monotone in l
    return acc.astype(np.float32), rel_cost.astype(np.float32)


def generate_instance(
    seed: int,
    cfg: Optional[GeneratorConfig] = None,
    *,
    as_numpy: bool = False,
):
    """Draw one MUS instance per the paper's numerical setup."""
    cfg = cfg or GeneratorConfig()
    rng = np.random.default_rng(seed)
    N = cfg.n_requests
    M = cfg.n_edge + cfg.n_cloud
    K, L = cfg.n_services, cfg.n_variants
    is_cloud = np.arange(M) >= cfg.n_edge

    # --- servers -----------------------------------------------------------
    edge_class = rng.integers(0, len(cfg.edge_compute_classes), size=cfg.n_edge)
    gamma = np.empty(M, np.float32)
    eta = np.empty(M, np.float32)
    svc_frac = np.empty(M, np.float32)
    for j in range(M):
        if is_cloud[j]:
            gamma[j] = cfg.cloud_compute
            eta[j] = cfg.cloud_comm
            svc_frac[j] = 1.0
        else:
            c = edge_class[j]
            gamma[j] = cfg.edge_compute_classes[c]
            eta[j] = cfg.edge_comm_classes[c]
            svc_frac[j] = cfg.edge_services_frac[c]

    # --- services / variants ----------------------------------------------
    acc_kl, rel_cost = _variant_ladder(cfg, rng)

    # placement (M, K, L)
    placed = np.zeros((M, K, L), bool)
    for j in range(M):
        if is_cloud[j]:
            placed[j] = True
        else:
            ks = rng.random(K) < svc_frac[j]
            placed[j, ks, : cfg.edge_variants] = True

    # processing delay (M, K, L): per-server speed * per-variant relative cost
    proc = np.empty((M, K, L), np.float32)
    for j in range(M):
        base = (
            cfg.proc_cloud
            if is_cloud[j]
            else rng.uniform(cfg.proc_edge_lo, cfg.proc_edge_hi)
        )
        proc[j] = base * rel_cost[None, :] * rng.uniform(0.95, 1.05, size=(K, L))

    # --- requests -----------------------------------------------------------
    service = rng.integers(0, K, size=N)
    cover = rng.integers(0, cfg.n_edge, size=N)  # users attach to edges only
    A = np.clip(rng.normal(cfg.acc_req_mean, cfg.acc_req_std, N), 1.0, 99.0)
    C = np.clip(rng.normal(cfg.delay_req_mean, cfg.delay_req_std, N), 50.0, None)
    Tq = rng.uniform(0.0, cfg.queue_delay_max, N)
    size = rng.uniform(cfg.req_size_lo, cfg.req_size_hi, N)

    # --- pairwise comm delay (cover -> j) -----------------------------------
    # delay = size / bandwidth (+ backhaul if crossing to the cloud tier)
    comm_delay = size[:, None] / cfg.bandwidth + np.where(
        is_cloud[None, :], cfg.cloud_extra_delay, 0.0
    )
    local = cover[:, None] == np.arange(M)[None, :]
    comm_delay = np.where(local, 0.0, comm_delay)

    # --- flatten to (N, M, L) ------------------------------------------------
    acc_nml = np.broadcast_to(acc_kl[service][:, None, :], (N, M, L)).copy()
    proc_nml = proc[:, service, :].transpose(1, 0, 2)  # (N, M, L)
    ctime = Tq[:, None, None] + proc_nml + comm_delay[:, :, None]
    avail = placed[:, service, :].transpose(1, 0, 2)

    # computation cost: chip-ms actually consumed on the serving box;
    # communication cost: KB shipped off the covering box when offloading.
    v = proc_nml.copy()
    u = np.where(local[:, :, None], 0.0, (size / 1024.0)[:, None, None])
    u = np.broadcast_to(u, (N, M, L)).copy()

    arrays = dict(
        cover=cover.astype(np.int32),
        A=A.astype(np.float32),
        C=C.astype(np.float32),
        w_a=np.full(N, cfg.w_a, np.float32),
        w_c=np.full(N, cfg.w_c, np.float32),
        acc=acc_nml.astype(np.float32),
        ctime=ctime.astype(np.float32),
        v=v.astype(np.float32),
        u=u.astype(np.float32),
        avail=avail,
        gamma=gamma.astype(np.float32),
        eta=eta.astype(np.float32),
        max_as=np.float32(cfg.max_as),
        max_cs=np.float32(cfg.max_cs),
    )
    if as_numpy:
        return FlatInstance(**arrays)
    return FlatInstance(**{k: jnp.asarray(val) for k, val in arrays.items()})


def pad_instance(inst: FlatInstance, n_pad: int) -> FlatInstance:
    """Pad the request axis of an (unbatched) instance to ``n_pad`` rows.

    This is the fixed-shape contract the jitted schedulers rely on: padded
    rows are *infeasible everywhere* (``avail`` False) and *free* (zero
    v/u and zero US weights), so every scheduler that honors feasibility —
    ``gus_schedule``, ``gus_schedule_np``, all baselines — drops them
    (j = l = -1) without touching any capacity.  Because GUS processes
    requests by ascending index and padded rows sit at the end, the first
    ``N`` assignments are identical to running on the unpadded instance.

    Server-axis leaves (gamma, eta) and scalars (max_as, max_cs) pass
    through untouched.
    """
    N = inst.A.shape[-1]
    if n_pad == N:
        return inst
    if n_pad < N:
        raise ValueError(f"cannot pad {N} requests down to {n_pad}")
    p = n_pad - N

    def _pad(x, fill):
        x = jnp.asarray(x)
        return jnp.concatenate([x, jnp.full((p,) + x.shape[1:], fill, x.dtype)])

    with span("gus/pad", CAT_BUILD, n=N, n_pad=n_pad):
        return FlatInstance(
            cover=_pad(inst.cover, 0),
            A=_pad(inst.A, 1e9),        # unreachable accuracy floor
            C=_pad(inst.C, -1.0),       # already-expired deadline
            w_a=_pad(inst.w_a, 0.0),    # padded rows contribute zero US
            w_c=_pad(inst.w_c, 0.0),
            acc=_pad(inst.acc, 0.0),
            ctime=_pad(inst.ctime, 1e9),
            v=_pad(inst.v, 0.0),
            u=_pad(inst.u, 0.0),
            avail=_pad(inst.avail, False),
            gamma=inst.gamma,
            eta=inst.eta,
            max_as=inst.max_as,
            max_cs=inst.max_cs,
        )


def generate_batch(seed: int, n: int, cfg: Optional[GeneratorConfig] = None):
    """A batch of `n` instances stacked on a leading axis (for vmap)."""
    insts = [generate_instance(seed + i, cfg, as_numpy=True) for i in range(n)]
    return stack_instances(insts)


def stack_instances(insts):
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *insts)
