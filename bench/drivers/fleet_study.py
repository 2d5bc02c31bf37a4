"""Driver ``fleet_study``: back-to-back ``simulate_fleet`` studies, closed loop.

Each unit of work is one whole Monte-Carlo study, called as a user calls
it: ``simulate_fleet(spec, cfg, policy="gus", scenario=..., n_rep=...,
seed=..., options=EngineOptions(...))``, one study at a time.

The configuration states the deployment: the cluster (one draw of the
Sec. IV cluster generator, ``cluster_seed``), the ``SimConfig`` fields and
the request law (``bench/scenarios/<scenario>.py`` for the program,
``bench/gen/<scenario>.py`` for the reference).  The traffic mix states
only the load: replications per study, frames per replication and the
mean requests per frame, spread evenly over the edges.  Study ``i`` of a
run takes the ``i % pool``-th seed of a pool drawn from ``--seed``;
``warmup`` of them run in set-up, which compiles every shape they reach.

Correctness: once the window has closed, one study drawn from the seed is
regenerated and scheduled replication by replication by the plain
reference (``bench/refs/fleet.py``), and each replication's satisfied
share and mean utility are compared, with the study's request count.
"""
from __future__ import annotations

import gc

import numpy as np

from bench.common import load_module, study_seed
from bench.gen.instance import sec4_cluster
from bench.refs import fleet as fleet_ref
from bench.refs.precision import dtype
from bench.roofline import gus_work

#: dtypes of the leaves dense GUS reads (``FlatInstance``)
GUS_DTYPES = dict(acc="float32", ctime="float32", v="float32", u="float32", avail="bool",
                  cover="int32", A="float32", C="float32", w_a="float32", w_c="float32",
                  gamma="float32", eta="float32")


class Driver:
    unit = "bench/study"

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int, root):
        self.root = root
        self.cluster = sec4_cluster(int(config["cluster_seed"]), **config["generator"])
        frame_ms = float(config["sim"]["frame_ms"])
        self.sim = dict(config["sim"],
                        horizon_ms=int(traffic["frames"]) * frame_ms,
                        arrival_rate_per_s=float(traffic["requests_per_frame"])
                        / self.cluster["n_edge"] / (frame_ms / 1e3))
        self.scenario = config["scenario"]
        self.params = config.get("scenario_params", {})
        self.gen = load_module(root / "bench" / "gen" / f"{self.scenario}.py").trace
        self.n_rep = int(traffic["n_rep"])
        self.options = dict(traffic.get("options", {}))
        self.pool = [study_seed(seed, i, self.n_rep) for i in range(int(traffic["pool"]))]
        self.warmup = int(traffic["warmup"])

    # -- the program ----------------------------------------------------------
    def setup(self):
        from repro.core import ClusterSpec, EngineOptions, SimConfig

        c = self.cluster
        self.spec = ClusterSpec(
            n_edge=c["n_edge"], n_cloud=c["n_cloud"], gamma_frame=c["gamma"],
            eta_frame=c["eta"], proc_ms=c["proc"], placed=c["placed"], acc=c["acc"],
            bandwidth_true=float(c["bandwidth"]),
            cloud_extra_delay=float(c["cloud_extra_delay"]))
        self.cfg = SimConfig(**self.sim)
        self.program_scenario = load_module(
            self.root / "bench" / "scenarios" / f"{self.scenario}.py").scenario(self.params)
        self.engine_options = EngineOptions(**self.options)
        for s in self.pool[: self.warmup]:
            self._study(s)

    def _study(self, s):
        from repro.core import simulator

        return simulator.simulate_fleet(
            self.spec, self.cfg, policy="gus", scenario=self.program_scenario, n_rep=self.n_rep,
            seed=s, options=self.engine_options)

    def step(self, i: int) -> dict:
        s = self.pool[i % len(self.pool)]
        fr = self._study(s)
        return dict(seed=s, users=int(fr.n_requests), frames=int(fr.n_frames) * self.n_rep,
                    sat=np.asarray(fr.satisfied_per_rep, np.float64).copy(),
                    us=np.asarray(fr.mean_us_per_rep, np.float64).copy(),
                    timings=dict(fr.timings or {}))

    def resolved(self) -> dict:
        from repro.core.options import resolve_backend, resolve_options
        from repro.core.simulator import FLEET_REP_GROUP

        o = resolve_options(self.engine_options, scenario=self.program_scenario)
        T = max(1, int(np.ceil(self.cfg.horizon_ms / self.cfg.frame_ms)))
        return dict(backend=resolve_backend(o.backend), scheduler=o.scheduler,
                    rng_mode=o.rng_mode, streaming=o.streaming,
                    window=T if o.window is None else o.window, prefetch=o.prefetch,
                    rep_group=min(o.rep_group or FLEET_REP_GROUP, self.n_rep),
                    devices=o.devices)

    def release(self):
        self.spec = self.cfg = self.program_scenario = None
        gc.collect()

    # -- metrics --------------------------------------------------------------
    def end_to_end(self, records, t0: float, t1: float) -> dict:
        return dict(users_per_s=sum(r["users"] for r in records) / (t1 - t0))

    def layer_context(self, records) -> dict:
        M = self.cluster["gamma"].shape[0]
        L = self.cluster["acc"].shape[1]
        users = sum(r["users"] for r in records)
        frames = sum(r["frames"] for r in records)
        return dict(timings=[r["timings"] for r in records], users=users, frames=frames,
                    gus_work=gus_work(GUS_DTYPES, users, frames, M, L))

    # -- correctness ----------------------------------------------------------
    def reference(self, s: int, precision: str = "float32") -> dict:
        """Per-replication satisfied share and mean utility of study ``s``
        from the plain reference, in ``precision``."""
        reps = [fleet_ref.replication(self.cluster, self.sim, self.params, s + r, self.gen,
                                      dtype(precision))
                for r in range(self.n_rep)]
        req = np.array([o["requests"] for o in reps], np.int64)
        return dict(requests=int(req.sum()),
                    sat=100.0 * np.array([o["satisfied"] for o in reps]) / np.maximum(req, 1),
                    us=np.array([o["us_sum"] for o in reps]) / np.maximum(req, 1))

    @staticmethod
    def compare(got: dict, want: dict) -> dict:
        return dict(
            requests_off=float(abs(got["users"] - want["requests"])),
            sat_gap_pp=float(np.max(np.abs(got["sat"] - want["sat"]))),
            us_gap=float(np.max(np.abs(got["us"] - want["us"]))),
        )

    def check(self, records, rng) -> dict:
        rec = records[int(rng.integers(len(records)))]
        return self.compare(rec, self.reference(rec["seed"]))

    def control(self, rng) -> dict:
        """The reference one precision lower in the program's place, on a
        study of this seed's pool."""
        s = self.pool[int(rng.integers(len(self.pool)))]
        want = self.reference(s)
        low = self.reference(s, "bfloat16")
        return self.compare(dict(users=low["requests"], sat=low["sat"], us=low["us"]), want)
