"""``sec4``: the paper's Sec. IV request law as a Poisson stream of frames.

Per edge a homogeneous Poisson process at ``arrival_rate_per_s``, drawn
one request at a time from one shared ``default_rng(seed)`` in the order
of the program's per-request generator (``Scenario.generate_arrivals``):
the gap, the service, the accuracy floor ``N(acc_req_mean, acc_req_std)``
clipped to [1, 99], the deadline ``N(delay_mean_ms, delay_std_ms)`` held
at or above ``delay_min_ms``, the payload ``U[req_size_lo, req_size_hi]``.
"""
from __future__ import annotations

import numpy as np

from bench.gen.arrivals import sorted_columns


def trace(seed: int, n_edge: int, n_services: int, sim: dict, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    rate = float(sim["arrival_rate_per_s"])
    rows = []
    for e in range(n_edge):
        if rate <= 0.0:
            continue
        t = 0.0
        while t < sim["horizon_ms"]:
            t += rng.exponential(1000.0 / rate)
            if t >= sim["horizon_ms"]:
                break
            service = int(rng.integers(0, n_services))
            a = float(np.clip(rng.normal(sim["acc_req_mean"], sim["acc_req_std"]), 1, 99))
            c = max(float(rng.normal(params["delay_mean_ms"], params["delay_std_ms"])),
                    float(params["delay_min_ms"]))
            size = float(rng.uniform(sim["req_size_lo"], sim["req_size_hi"]))
            rows.append((t, e, service, a, c, size))
    rows.sort(key=lambda r: r[0])
    if not rows:
        return sorted_columns([])
    t, cov, svc, a, c, size = (np.array(x) for x in zip(*rows))
    return dict(arrival_ms=t.astype(np.float64), cover=cov.astype(np.int64),
                service=svc.astype(np.int64), A=a.astype(np.float64),
                C=c.astype(np.float64), size=size.astype(np.float64))
