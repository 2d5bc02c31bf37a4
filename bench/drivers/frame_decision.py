"""Driver ``frame_decision``: back-to-back single-frame decisions, closed loop.

The online controller's call: one frame outstanding at a time, each a
host-side instance handed to ``gus_schedule(pad_instance(inst, n_pad))``
and done when both assignment arrays are on the host.  The frames are a
pool drawn in set-up from the benchmark's copy of the paper's Sec. IV
generator (``bench/gen/instance.py``), one seed per frame from
``--seed``, and cycled.

Correctness: every distinct answer the window gave for each pool frame is
replayed against the plain float32 reference of GUS; the number compared is
the widest gap by which a decision's utility lies below the reference's
best at that point (``bench/refs/gus.py``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.gen.instance import FIELDS, generate_instance
from bench.refs import gus as gus_ref
from bench.refs.precision import dtype
from bench.roofline import gus_work


class Driver:
    unit = "bench/frame"

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int, root):
        gen_args = config["generator"]
        self.n_pad = int(traffic["n_pad"])
        self.warmup = int(traffic["warmup"])
        self.frames = [generate_instance(int(seed) * 65_536 + i, **gen_args)
                       for i in range(int(traffic["pool"]))]

    def setup(self):
        from repro.core import FlatInstance

        self.insts = [FlatInstance(**{k: f[k] for k in FIELDS}) for f in self.frames]
        for i in range(self.warmup):
            self.step(i)

    def step(self, i: int) -> dict:
        from repro.core import gus, instance

        p = i % len(self.insts)
        t0 = time.perf_counter()
        a = gus.gus_schedule(instance.pad_instance(self.insts[p], self.n_pad))
        j, l = np.asarray(a.j), np.asarray(a.l)
        t1 = time.perf_counter()
        return dict(pool=p, latency=t1 - t0, j=j, l=l)

    def resolved(self) -> dict:
        from repro.core.options import resolve_backend

        return dict(backend=resolve_backend(None), n_pad=self.n_pad)

    def release(self):
        self.insts = None
        gc.collect()

    def end_to_end(self, records, t0: float, t1: float) -> dict:
        lat_ms = 1e3 * np.asarray([r["latency"] for r in records])
        return dict(decision_p50_ms=float(np.percentile(lat_ms, 50)),
                    decision_p95_ms=float(np.percentile(lat_ms, 95)))

    def layer_context(self, records) -> dict:
        f = self.frames[0]
        N, M, L = f["acc"].shape
        dtypes = {k: f[k].dtype for k in FIELDS}
        return dict(gus_work=gus_work(dtypes, N * len(records), len(records), M, L),
                    frames=len(records))

    def _answers(self, records):
        """Each pool frame's distinct answers in the window."""
        seen = {}
        for r in records:
            N = self.frames[r["pool"]]["A"].shape[0]
            key = (r["pool"], r["j"][:N].tobytes(), r["l"][:N].tobytes())
            seen.setdefault(key, (r["pool"], r["j"][:N], r["l"][:N]))
        return list(seen.values())

    def check(self, records, rng) -> dict:
        gap = max(gus_ref.replay_gap(self.frames[p], j, l) for p, j, l in self._answers(records))
        return dict(util_gap=float(gap))

    def control(self, rng) -> dict:
        """GUS one precision lower in the program's place, on every pool frame."""
        low = dtype("bfloat16")
        gap = 0.0
        for f in self.frames:
            j, l = gus_ref.schedule(f, low)
            gap = max(gap, gus_ref.replay_gap(f, j, l))
        return dict(util_gap=float(gap))
