"""Plain reference of one Monte-Carlo replication of ``simulate_fleet``.

A replication is regenerated from its seed with the benchmark's own copies
(``bench/gen``) and scheduled frame by frame, frame-synchronously, with the
true mean bandwidth, as the fleet's contract states: every request of a
frame on the N x M x L grid, scheduled by GUS (``refs/gus.py``).

A request is satisfied when it is served by a variant at or above its
accuracy floor and its modeled completion time is within its deadline.
Returns, per replication, the requests, the served and satisfied counts
and the sum of Eq. (1) utilities over served requests.  Congestion is not
modeled, nor mobility: a configuration that turns either on is refused.
"""
from __future__ import annotations

import numpy as np

from bench.gen.arrivals import buckets
from bench.refs import gus

F32 = np.float32


def _ctime(spec, cover, svc, Tq, size):
    """Completion time ``T^q + T^proc + T^comm`` of each row on each
    (server, variant), float64 transfer math narrowed to float32 at the end,
    as the program's frame builder forms it."""
    M = spec["gamma"].shape[0]
    is_cloud = np.arange(M) >= spec["n_edge"]
    local = cover[:, None] == np.arange(M)[None, :]
    comm = size[:, None] / spec["bandwidth"] + np.where(
        is_cloud[None, :], spec["cloud_extra_delay"], 0.0)
    comm = np.where(local, 0.0, comm)
    proc = spec["proc"][:, svc, :].transpose(1, 0, 2)
    return (Tq[:, None, None] + proc + comm[:, :, None]).astype(F32), proc, local


def _accounting(spec, sim, svc, cov, A, C, Tq, size, jm, lm):
    """Per served row: (satisfied, utility), float32, with the row's own
    inputs."""
    acc_m = spec["acc"][svc, lm]
    proc_m = spec["proc"][jm, svc, lm]
    local_m = jm == cov
    comm = size / spec["bandwidth"] + np.where(jm >= spec["n_edge"], spec["cloud_extra_delay"], 0.0)
    comm = np.where(local_m, 0.0, comm)
    ct = ((Tq + proc_m) + comm).astype(F32)
    sat = (acc_m >= A) & (ct <= C)
    us = (F32(sim["w_a"]) * ((acc_m - A) / F32(sim["max_as"]))
          + F32(sim["w_c"]) * ((C - ct) / F32(sim["max_cs"])))
    return sat, us


def replication(spec, sim, params, seed, gen, dt=F32):
    if (sim.get("congestion") or {}).get("enabled"):
        raise ValueError("the fleet reference does not model congestion")
    if sim.get("move_prob", 0.0):
        raise ValueError("the fleet reference does not model mobility")
    T = max(1, int(np.ceil(sim["horizon_ms"] / sim["frame_ms"])))
    tr = gen(seed, spec["n_edge"], spec["proc"].shape[1], sim, params)
    out = dict(requests=0, served=0, satisfied=0, us_sum=0.0)
    for k, b in enumerate(buckets(tr, sim["frame_ms"], T)):
        n = b["arrival_ms"].size
        out["requests"] += n
        if not n:
            continue
        now = k * sim["frame_ms"] + sim["frame_ms"]
        cov = b["cover"].astype(np.int32)
        svc = b["service"].astype(np.int32)
        A, C = b["A"].astype(F32), b["C"].astype(F32)
        Tq = (now - b["arrival_ms"]).astype(F32)
        size = b["size"].astype(F32)
        ctime, proc, local = _ctime(spec, cov, svc, Tq, size)
        M, L = spec["gamma"].shape[0], spec["acc"].shape[1]
        inst = dict(
            cover=cov, A=A, C=C, w_a=np.full(n, sim["w_a"], F32),
            w_c=np.full(n, sim["w_c"], F32),
            acc=np.broadcast_to(spec["acc"][svc][:, None, :], (n, M, L)),
            ctime=ctime, v=proc,
            u=np.broadcast_to(np.where(local[:, :, None], 0.0, (size / 1024.0)[:, None, None]),
                              (n, M, L)).astype(F32),
            avail=spec["placed"][:, svc, :].transpose(1, 0, 2),
            gamma=spec["gamma"], eta=spec["eta"],
            max_as=F32(sim["max_as"]), max_cs=F32(sim["max_cs"]))
        if dt is not F32:
            inst = {k2: (x.astype(dt) if np.asarray(x).dtype == F32 else x)
                    for k2, x in inst.items()}
        j, l = gus.schedule(inst, dt)
        srv = j >= 0
        if not srv.any():
            continue
        sat, us = _accounting(spec, sim, svc[srv], cov[srv], A[srv], C[srv], Tq[srv],
                              size[srv], j[srv].astype(np.int64), l[srv].astype(np.int64))
        out["served"] += int(srv.sum())
        out["satisfied"] += int(sat.sum())
        out["us_sum"] += float(us.astype(np.float64).sum())
    return out
