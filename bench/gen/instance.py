"""The paper's Sec. IV instance generator (arXiv:2011.08381), NumPy only.

A copy of ``repro.core.instance.generate_instance`` on its NumPy path, kept
with the benchmark so that what a cell feeds the scheduler does not move
when the program's generator does.  Draw order, dtypes and values are the
same: one ``default_rng(seed)`` per instance.
"""
from __future__ import annotations

import numpy as np

#: ``GeneratorConfig()``: 9 edges + 1 cloud, N = 100 requests, K = 100
#: services, L = 10 variants, and the paper's QoS distributions.
SEC4 = dict(
    n_requests=100, n_edge=9, n_cloud=1, n_services=100, n_variants=10,
    acc_req_mean=45.0, acc_req_std=10.0, delay_req_mean=1000.0,
    delay_req_std=4000.0, queue_delay_max=50.0, w_a=1.0, w_c=1.0,
    max_as=100.0, max_cs=12000.0, proc_edge_lo=950.0, proc_edge_hi=1300.0,
    proc_cloud=300.0, acc_top=92.0, acc_bottom=35.0, bandwidth=600.0,
    req_size_lo=20_000.0, req_size_hi=120_000.0, cloud_extra_delay=100.0,
    edge_compute_classes=(2600.0, 3900.0, 5200.0),
    edge_comm_classes=(400.0, 600.0, 800.0), cloud_compute=26_000.0,
    cloud_comm=6000.0, edge_services_frac=(0.25, 0.5, 0.75), edge_variants=6,
)

#: leaves of one instance, in ``FlatInstance`` field order
FIELDS = ("cover", "A", "C", "w_a", "w_c", "acc", "ctime", "v", "u", "avail",
          "gamma", "eta", "max_as", "max_cs")


def _cluster(rng, c: dict) -> dict:
    """The cluster part of an instance, drawn first from ``rng``: per-server
    budgets, each service's variant ladder, placement and processing delays."""
    M = c["n_edge"] + c["n_cloud"]
    K, L = c["n_services"], c["n_variants"]
    is_cloud = np.arange(M) >= c["n_edge"]

    edge_class = rng.integers(0, len(c["edge_compute_classes"]), size=c["n_edge"])
    gamma = np.empty(M, np.float32)
    eta = np.empty(M, np.float32)
    svc_frac = np.empty(M, np.float32)
    for j in range(M):
        if is_cloud[j]:
            gamma[j], eta[j], svc_frac[j] = c["cloud_compute"], c["cloud_comm"], 1.0
        else:
            k = edge_class[j]
            gamma[j] = c["edge_compute_classes"][k]
            eta[j] = c["edge_comm_classes"][k]
            svc_frac[j] = c["edge_services_frac"][k]

    rel_cost = np.geomspace(0.12, 1.0, L)
    base = c["acc_bottom"] + (c["acc_top"] - c["acc_bottom"]) * (
        1.0 - np.exp(-3.0 * rel_cost)) / (1.0 - np.exp(-3.0))
    acc_kl = base[None, :] + rng.normal(0.0, 2.0, size=(K, L))
    acc_kl = np.clip(np.sort(acc_kl, axis=1), 1.0, c["max_as"]).astype(np.float32)
    rel_cost = rel_cost.astype(np.float32)

    placed = np.zeros((M, K, L), bool)
    for j in range(M):
        if is_cloud[j]:
            placed[j] = True
        else:
            ks = rng.random(K) < svc_frac[j]
            placed[j, ks, : c["edge_variants"]] = True

    proc = np.empty((M, K, L), np.float32)
    for j in range(M):
        b = c["proc_cloud"] if is_cloud[j] else rng.uniform(
            c["proc_edge_lo"], c["proc_edge_hi"])
        proc[j] = b * rel_cost[None, :] * rng.uniform(0.95, 1.05, size=(K, L))
    return dict(n_edge=c["n_edge"], n_cloud=c["n_cloud"], gamma=gamma, eta=eta,
                acc=acc_kl, placed=placed, proc=proc, bandwidth=c["bandwidth"],
                cloud_extra_delay=c["cloud_extra_delay"])


def sec4_cluster(seed: int, **overrides) -> dict:
    """One draw of the Sec. IV cluster alone (the first draws of the instance
    of the same seed), as the fleet's deployment: ``gamma``/``eta`` per frame,
    ``acc`` (K, L), ``placed`` and ``proc`` (M, K, L)."""
    return _cluster(np.random.default_rng(seed), {**SEC4, **overrides})


def generate_instance(seed: int, **overrides) -> dict:
    """One instance as a dict of NumPy leaves (``FIELDS``)."""
    c = {**SEC4, **overrides}
    rng = np.random.default_rng(seed)
    N = c["n_requests"]
    M = c["n_edge"] + c["n_cloud"]
    K, L = c["n_services"], c["n_variants"]
    is_cloud = np.arange(M) >= c["n_edge"]
    cl = _cluster(rng, c)
    gamma, eta, acc_kl, placed, proc = (cl[k] for k in ("gamma", "eta", "acc", "placed", "proc"))

    service = rng.integers(0, K, size=N)
    cover = rng.integers(0, c["n_edge"], size=N)
    A = np.clip(rng.normal(c["acc_req_mean"], c["acc_req_std"], N), 1.0, 99.0)
    C = np.clip(rng.normal(c["delay_req_mean"], c["delay_req_std"], N), 50.0, None)
    Tq = rng.uniform(0.0, c["queue_delay_max"], N)
    size = rng.uniform(c["req_size_lo"], c["req_size_hi"], N)

    comm = size[:, None] / c["bandwidth"] + np.where(
        is_cloud[None, :], c["cloud_extra_delay"], 0.0)
    local = cover[:, None] == np.arange(M)[None, :]
    comm = np.where(local, 0.0, comm)

    acc = np.broadcast_to(acc_kl[service][:, None, :], (N, M, L))
    proc_nml = proc[:, service, :].transpose(1, 0, 2)
    ctime = Tq[:, None, None] + proc_nml + comm[:, :, None]
    avail = placed[:, service, :].transpose(1, 0, 2)
    u = np.where(local[:, :, None], 0.0, (size / 1024.0)[:, None, None])
    return dict(
        cover=cover.astype(np.int32),
        A=A.astype(np.float32),
        C=C.astype(np.float32),
        w_a=np.full(N, c["w_a"], np.float32),
        w_c=np.full(N, c["w_c"], np.float32),
        acc=np.ascontiguousarray(acc, np.float32),
        ctime=ctime.astype(np.float32),
        v=proc_nml.astype(np.float32),
        u=np.ascontiguousarray(np.broadcast_to(u, (N, M, L)), np.float32),
        avail=np.ascontiguousarray(avail),
        gamma=gamma,
        eta=eta,
        max_as=np.float32(c["max_as"]),
        max_cs=np.float32(c["max_cs"]),
    )
