"""Plain reference of GUS, the paper's greedy (Algorithm 1), NumPy only.

Requests are visited in index order; each takes the feasible (server,
variant) cell of highest utility (ties: lowest flat ``j * L + l``) that
fits the serving server's compute budget and, when offloaded, the
covering edge's communication budget.  ``dt`` is the precision every float
is computed in (``refs/precision.py``).
"""
from __future__ import annotations

import numpy as np

#: a gap reported for a decision the reference cannot accept at all (a
#: request dropped that fits, or a cell that does not fit); utilities of
#: feasible cells lie in [0, 2], so no true utility gap reaches it
BAD_DECISION = 10.0


def utility(acc, A, C, ctime, w_a, w_c, max_as, max_cs, dt=np.float32):
    """``(N, M, L)`` Eq. (1) utility and hard feasibility (without
    placement), computed in ``dt``."""
    acc, ctime = np.asarray(acc).astype(dt), np.asarray(ctime).astype(dt)
    A, C = np.asarray(A).astype(dt)[:, None, None], np.asarray(C).astype(dt)[:, None, None]
    w_a = np.asarray(w_a).astype(dt)[:, None, None]
    w_c = np.asarray(w_c).astype(dt)[:, None, None]
    us = w_a * ((acc - A) / np.asarray(max_as).astype(dt)) + w_c * (
        (C - ctime) / np.asarray(max_cs).astype(dt))
    return us, (acc >= A) & (ctime <= C)


def schedule(inst: dict, dt=np.float32):
    """``(j, l)`` int32 assignments of one unpadded instance (-1 = drop)."""
    us, qos = utility(inst["acc"], inst["A"], inst["C"], inst["ctime"], inst["w_a"],
                      inst["w_c"], inst["max_as"], inst["max_cs"], dt)
    feas = qos & np.asarray(inst["avail"], bool)
    v, u = inst["v"].astype(dt), inst["u"].astype(dt)
    gamma, eta = inst["gamma"].astype(dt).copy(), inst["eta"].astype(dt).copy()
    cover = inst["cover"]
    N, M, L = us.shape
    out_j = np.full(N, -1, np.int32)
    out_l = np.full(N, -1, np.int32)
    servers = np.arange(M)
    for i in range(N):
        s = int(cover[i])
        ok = feas[i] & (v[i] <= gamma[:, None]) & (
            (servers == s)[:, None] | (u[i] <= eta[s]))
        if not ok.any():
            continue
        flat = int(np.argmax(np.where(ok, us[i].astype(np.float32), -np.inf)))
        j, l = divmod(flat, L)
        out_j[i], out_l[i] = j, l
        gamma[j] = gamma[j] - v[i, j, l]
        if j != s:
            eta[s] = eta[s] - u[i, j, l]
    return out_j, out_l


def replay_gap(inst: dict, j_got, l_got) -> float:
    """Widest gap by which a decision lies below the float32 reference's
    best, replaying the decisions in order against the reference's budgets.

    At each request the budgets are those the decisions so far left, so one
    near-tie decided the other way costs only its own utility gap and not
    every later decision.  A request dropped while a cell fits, or a cell
    that is infeasible or does not fit, reads ``BAD_DECISION``."""
    us, qos = utility(inst["acc"], inst["A"], inst["C"], inst["ctime"], inst["w_a"],
                      inst["w_c"], inst["max_as"], inst["max_cs"])
    feas = qos & np.asarray(inst["avail"], bool)
    v, u = inst["v"].astype(np.float32), inst["u"].astype(np.float32)
    gamma = inst["gamma"].astype(np.float32).copy()
    eta = inst["eta"].astype(np.float32).copy()
    cover = inst["cover"]
    N, M, L = us.shape
    servers = np.arange(M)
    gap = 0.0
    for i in range(N):
        s = int(cover[i])
        ok = feas[i] & (v[i] <= gamma[:, None]) & (
            (servers == s)[:, None] | (u[i] <= eta[s]))
        j, l = int(j_got[i]), int(l_got[i])
        if j < 0:
            if ok.any():
                gap = max(gap, BAD_DECISION)
            continue
        if not (0 <= j < M and 0 <= l < L and ok[j, l]):
            gap = max(gap, BAD_DECISION)
            continue
        gap = max(gap, float(us[i][ok].max() - us[i, j, l]))
        gamma[j] = gamma[j] - v[i, j, l]
        if j != s:
            eta[s] = eta[s] - u[i, j, l]
    return gap
