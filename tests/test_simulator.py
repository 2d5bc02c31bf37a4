"""Virtual-testbed simulator: frame protocol, capacity budgets, EMA estimator."""
import numpy as np
import jax.numpy as jnp

from repro.core import ClusterSpec, SimConfig, gus_schedule_np, local_all, offload_all, simulate


def tiny_spec(edge_gamma=3900.0, cloud_gamma=3000.0, eta=350.0):
    M, K, L = 3, 2, 2
    proc = np.zeros((M, K, L), np.float32)
    proc[0] = proc[1] = [[650.0, 1300.0], [650.0, 1300.0]]
    proc[2] = [[150.0, 300.0], [150.0, 300.0]]
    placed = np.ones((M, K, L), bool)
    acc = np.array([[55.0, 80.0], [55.0, 80.0]], np.float32)
    return ClusterSpec(
        n_edge=2,
        n_cloud=1,
        gamma_frame=np.array([edge_gamma, edge_gamma, cloud_gamma], np.float32),
        eta_frame=np.array([eta, eta, 10 * eta], np.float32),
        proc_ms=proc,
        placed=placed,
        acc=acc,
    )


def cfg(rate=1.0, **kw):
    return SimConfig(
        horizon_ms=kw.pop("horizon_ms", 30_000.0),
        arrival_rate_per_s=rate,
        delay_req_ms=kw.pop("delay_req_ms", 6000.0),
        acc_req_mean=kw.pop("acc_req_mean", 50.0),
        **kw,
    )


def test_counts_add_up():
    r = simulate(tiny_spec(), cfg(), gus_schedule_np, seed=0)
    assert r.n_served + r.n_dropped == r.n_requests
    assert r.n_local + r.n_cloud + r.n_edge_offload == r.n_served
    assert 0 <= r.satisfied_pct <= 100


def test_deterministic_given_seed():
    a = simulate(tiny_spec(), cfg(), gus_schedule_np, seed=3).as_dict()
    b = simulate(tiny_spec(), cfg(), gus_schedule_np, seed=3).as_dict()
    assert a == b


def test_overload_causes_drops():
    light = simulate(tiny_spec(), cfg(rate=0.5), gus_schedule_np, seed=0)
    heavy = simulate(tiny_spec(), cfg(rate=12.0), gus_schedule_np, seed=0)
    assert heavy.satisfied_pct < light.satisfied_pct
    assert heavy.n_dropped > 0


def test_capacity_budget_not_refreshed_by_early_decisions():
    """Queue-cap-triggered early decisions must share the frame budget: with
    per-frame cloud capacity for ~2 requests, a 10x overload cannot satisfy
    much more than capacity even though decisions fire many times per frame."""
    spec = tiny_spec(edge_gamma=1300.0, cloud_gamma=600.0)
    r = simulate(spec, cfg(rate=10.0, queue_cap=2), gus_schedule_np, seed=0)
    # capacity: per frame, 2 edges x 1 (1300/1300) + cloud 2 (600/300) = ~4
    frames = 30_000.0 / 3000.0
    assert r.n_served <= 4.5 * frames + 8, (r.n_served, frames)


def test_accuracy_floor_respected():
    spec = tiny_spec()
    r = simulate(spec, cfg(acc_req_mean=90.0), gus_schedule_np, seed=0)
    assert r.n_served == 0  # no variant reaches 90%
    r2 = simulate(spec, cfg(acc_req_mean=70.0), gus_schedule_np, seed=0)
    # only the 80%-accurate (big) variants qualify
    assert r2.n_served > 0


def test_local_all_never_offloads():
    r = simulate(tiny_spec(), cfg(), lambda i: local_all(i), seed=0)
    assert r.n_cloud == 0 and r.n_edge_offload == 0


def test_offload_all_never_local():
    r = simulate(
        tiny_spec(), cfg(),
        lambda i: offload_all(i, jnp.arange(3) >= 2), seed=0,
    )
    assert r.n_local == 0 and r.n_edge_offload == 0


def test_bandwidth_ema_tracks_channel():
    """E[B_{t+1}] = (B_t + B_{t-1})/2 should converge near the true bandwidth
    even from a bad initial estimate."""
    spec = tiny_spec()
    spec.bandwidth_true = 900.0
    c = cfg(rate=2.0, horizon_ms=60_000.0, bandwidth_init=100.0, channel_sigma=0.05)
    r = simulate(spec, c, lambda i: offload_all(i, jnp.arange(3) >= 2), seed=0)
    est = r.bandwidth_estimates
    assert len(est) > 3
    assert abs(est[-1] - 900.0) / 900.0 < 0.35, est[-5:]
    assert abs(est[-1] - 900.0) < abs(est[0] - 900.0)


# ---------------------------------------------------------------------------
# Fleet: columnar paper-default buckets against Request buckets
# ---------------------------------------------------------------------------

import dataclasses  # noqa: E402

import pytest  # noqa: E402

from repro.core import (  # noqa: E402
    BurstyLossLink,
    EngineOptions,
    ImpairmentConfig,
    IntermittentLink,
    Scenario,
    demo_cluster_spec,
    simulate_fleet,
)


@dataclasses.dataclass(frozen=True)
class _RequestBuckets(Scenario):
    """The paper workload handed to the fleet as ``Request`` buckets: a
    scenario that overrides ``generate_arrivals`` keeps its objects, and
    these are ``to_requests()`` of the very columns the plain scenario
    hands over for the same generator state."""

    name: str = "paper-default-requests"

    def generate_arrivals(self, rng, n_edge, n_services, cfg, rng_mode=None):
        return super().generate_arrivals(rng, n_edge, n_services, cfg, rng_mode=rng_mode)


FLEET_CASES = {
    "dense-gus": (dict(), dict(policy="gus"), EngineOptions(metrics=True)),
    "host-policy": (dict(), dict(policy="lp-bound"), EngineOptions(metrics=True)),
    "hierarchical": (
        dict(), dict(policy="gus"),
        EngineOptions(scheduler="hierarchical", metrics=True),
    ),
    "mobility": (dict(move_prob=0.3), dict(policy="gus"), EngineOptions(metrics=True)),
    "impairments": (
        dict(impairments=ImpairmentConfig(
            enabled=True, link_profiles=(IntermittentLink(), BurstyLossLink()), seed=7,
        )),
        dict(policy="gus"), EngineOptions(metrics=True),
    ),
}


def _fleet_pair(case, n_rep=3, seed=11):
    sim_kw, call_kw, opts = FLEET_CASES[case]
    c = SimConfig(horizon_ms=12_000.0, arrival_rate_per_s=3.0, delay_req_ms=6000.0,
                  **sim_kw)
    spec = demo_cluster_spec()
    runs = [
        simulate_fleet(spec, c, scenario=scn, n_rep=n_rep, seed=seed, options=opts,
                       **call_kw)
        for scn in (Scenario(), _RequestBuckets())
    ]
    return c, runs


@pytest.mark.parametrize("case", sorted(FLEET_CASES))
def test_fleet_columnar_buckets_match_request_buckets(case):
    """The same draws give the same fleet, whether the sources hand the
    frames over as columns (the plain scenario) or as ``Request`` lists."""
    _, (cols, reqs) = _fleet_pair(case)
    assert cols.n_requests > 0
    assert cols.as_dict() == reqs.as_dict()
    np.testing.assert_array_equal(cols.satisfied_per_rep, reqs.satisfied_per_rep)
    np.testing.assert_array_equal(cols.mean_us_per_rep, reqs.mean_us_per_rep)
    assert (cols.final_backlog_per_rep is None) == (reqs.final_backlog_per_rep is None)
    if cols.final_backlog_per_rep is not None:
        np.testing.assert_array_equal(cols.final_backlog_per_rep, reqs.final_backlog_per_rep)
    assert cols.mean_compute_inflation == reqs.mean_compute_inflation
    assert cols.metrics.data.keys() == reqs.metrics.data.keys()
    for key, arr in cols.metrics.data.items():
        np.testing.assert_array_equal(arr, reqs.metrics.data[key], err_msg=key)


@pytest.mark.parametrize("case", ["dense-gus", "host-policy", "hierarchical"])
def test_fleet_columnar_frames_counter(case):
    """``fleet/columnar_frames`` counts every frame of a paper-default
    study (n_rep x frames), and none where the buckets are Request lists."""
    c, (cols, reqs) = _fleet_pair(case)
    T = int(np.ceil(c.horizon_ms / c.frame_ms))
    assert cols.timings["fleet/columnar_frames"] == cols.n_rep * T
    assert reqs.timings["fleet/columnar_frames"] == 0
