"""``sec4``: the paper's Sec. IV request law, handed to the program as a
scenario, the way a user of ``simulate_fleet`` states a workload.

The program's ``paper-default`` scenario fixes every deadline at
``SimConfig.delay_req_ms``; Sec. IV draws it per request from
``N(1000, 4000)`` ms held at or above 50 ms.  This subclass overrides the
one draw that differs; arrivals, services, accuracy floors and payloads
are the base scenario's.  ``bench/gen/sec4.py`` is the reference's copy.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from repro.core.scenarios import Scenario


@dataclasses.dataclass(frozen=True)
class Sec4(Scenario):
    name: str = "sec4"
    description: str = "Sec. IV workload: homogeneous Poisson, deadlines drawn per request"
    delay_mean_ms: float = 1000.0
    delay_std_ms: float = 4000.0
    delay_min_ms: float = 50.0

    def draw_qos(self, rng, cfg):
        a = float(np.clip(rng.normal(cfg.acc_req_mean, cfg.acc_req_std), 1, 99))
        c = max(float(rng.normal(self.delay_mean_ms, self.delay_std_ms)), self.delay_min_ms)
        return a, c


def scenario(params: dict) -> Scenario:
    return Sec4(**params)
