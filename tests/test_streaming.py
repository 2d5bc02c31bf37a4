"""Streaming arrival engine: chunked-vs-materialized parity at fixed seed,
bounded-memory invariants, the streaming scenarios, and simulate/fleet runs
off the stream."""
import math
import threading

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    ArrivalStream,
    EngineOptions,
    SimConfig,
    demo_cluster_spec,
    get_scenario,
    list_scenarios,
    simulate,
    simulate_fleet,
    stream_trace,
)


def cfg(**kw):
    return SimConfig(
        horizon_ms=kw.pop("horizon_ms", 20_000.0),
        arrival_rate_per_s=kw.pop("arrival_rate_per_s", 3.0),
        delay_req_ms=kw.pop("delay_req_ms", 6000.0),
        acc_req_mean=50.0,
        acc_req_std=10.0,
        **kw,
    )


def _req_tuple(r):
    return (r.rid, r.arrival_ms, r.cover, r.service, r.A, r.C, r.size_bytes)


# ---------------------------------------------------------------------------
# Parity: frame-by-frame draining == one-shot materialization, fixed seed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", sorted(["paper-default", "diurnal", "flash-crowd",
                                             "hetero-tiers", "sustained-overload",
                                             "diurnal-week"]))
@pytest.mark.parametrize("chunk_ms", [250.0, 3000.0, 7777.0])
def test_streaming_vs_materialized_parity(scenario, chunk_ms):
    c = cfg()
    one_shot = stream_trace(scenario, 11, 4, 3, c)
    s = ArrivalStream(scenario, 11, 4, 3, c)
    chunked = []
    t = 0.0
    while not s.exhausted:
        t += chunk_ms
        chunked.extend(s.take_until(t))
    assert [_req_tuple(r) for r in chunked] == [_req_tuple(r) for r in one_shot]


def test_stream_is_deterministic_given_seed_and_seed_sensitive():
    c = cfg()
    a = [_req_tuple(r) for r in stream_trace("paper-default", 5, 4, 3, c)]
    b = [_req_tuple(r) for r in stream_trace("paper-default", 5, 4, 3, c)]
    other = [_req_tuple(r) for r in stream_trace("paper-default", 6, 4, 3, c)]
    assert a == b
    assert a != other


def test_stream_arrivals_sorted_with_sequential_rids():
    reqs = stream_trace("flash-crowd", 0, 4, 3, cfg())
    times = [r.arrival_ms for r in reqs]
    assert times == sorted(times)
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    assert all(0.0 <= t < cfg().horizon_ms for t in times)


def test_stream_rate_matches_expectation():
    """Constant-rate scenario: emitted count ~ Poisson(rate * horizon * edges)."""
    c = cfg(horizon_ms=60_000.0, arrival_rate_per_s=2.0)
    n = len(stream_trace("paper-default", 0, 4, 3, c))
    expect = 2.0 * 60.0 * 4  # = 480
    assert abs(n - expect) < 5 * math.sqrt(expect)


def test_stream_bounded_lookahead():
    """The stream holds at most one pending arrival per edge."""
    s = ArrivalStream("paper-default", 0, 6, 3, cfg())
    assert len(s._heap) <= 6
    s.take_until(10_000.0)
    assert len(s._heap) <= 6


def test_take_until_respects_boundaries():
    s = ArrivalStream("paper-default", 3, 4, 3, cfg())
    first = s.take_until(5000.0)
    assert all(r.arrival_ms < 5000.0 for r in first)
    nxt = s.peek_ms()
    assert nxt >= 5000.0
    second = s.take_until(10_000.0)
    assert all(5000.0 <= r.arrival_ms < 10_000.0 for r in second)


# ---------------------------------------------------------------------------
# The streaming scenarios
# ---------------------------------------------------------------------------


def test_streaming_scenarios_registered():
    assert "sustained-overload" in list_scenarios()
    assert "diurnal-week" in list_scenarios()
    assert get_scenario("sustained-overload").streaming
    assert get_scenario("diurnal-week").streaming
    assert not get_scenario("paper-default").streaming


def test_sustained_overload_rate_is_multiplied():
    scn = get_scenario("sustained-overload")
    c = cfg()
    assert scn.rate(0, 1000.0, c) == pytest.approx(
        c.arrival_rate_per_s * scn.rate_mult
    )


def test_diurnal_week_has_seven_cycles():
    scn = get_scenario("diurnal-week")
    c = cfg(horizon_ms=70_000.0)
    # rate at t and t + horizon/7 are equal (one full period apart)
    assert scn.rate(0, 1234.0, c) == pytest.approx(
        scn.rate(0, 1234.0 + 10_000.0, c), rel=1e-9
    )
    # and the rate actually swings within a period
    rates = [scn.rate(0, t, c) for t in np.linspace(0, 10_000.0, 20)]
    assert max(rates) > 1.5 * min(rates)


# ---------------------------------------------------------------------------
# simulate / simulate_fleet off the stream
# ---------------------------------------------------------------------------


def test_simulate_streaming_deterministic_and_conserves_counts():
    spec = demo_cluster_spec()
    a = simulate(spec, cfg(), policy="gus", scenario="sustained-overload", seed=0)
    b = simulate(spec, cfg(), policy="gus", scenario="sustained-overload", seed=0)
    assert a.as_dict() == b.as_dict()
    assert a.n_served + a.n_dropped == a.n_requests
    assert a.n_requests > 0


def test_streaming_override_flag():
    """streaming=True forces the stream on a materialized scenario and
    streaming=False forces materialization on a streaming scenario."""
    spec = demo_cluster_spec()
    r_forced = simulate(spec, cfg(), policy="gus", scenario="paper-default",
                        seed=0, streaming=True)
    assert r_forced.n_served + r_forced.n_dropped == r_forced.n_requests
    r_mat = simulate(spec, cfg(), policy="gus", scenario="sustained-overload",
                     seed=0, streaming=False)
    assert r_mat.n_served + r_mat.n_dropped == r_mat.n_requests


def test_simulate_streaming_respects_n_requests_cap():
    spec = demo_cluster_spec()
    r = simulate(spec, cfg(), policy="gus", scenario="sustained-overload",
                 seed=0, n_requests=25)
    assert r.n_requests == 25


def test_fleet_runs_streaming_scenarios():
    spec = demo_cluster_spec()
    fr = simulate_fleet(spec, cfg(horizon_ms=12_000.0), policy="gus",
                        scenario="diurnal-week", n_rep=2, seed=0)
    assert np.isfinite(fr.satisfied_pct) and fr.n_requests > 0


def test_fleet_rep0_arrivals_match_sequential_stream():
    """Fleet replication r uses stream seed ``seed + r``, so rep 0's arrival
    trace equals the sequential simulate's at the same seed."""
    spec = demo_cluster_spec()
    c = cfg(horizon_ms=12_000.0)
    reqs = stream_trace("sustained-overload", 7, spec.n_edge, 3, c)
    fr = simulate_fleet(spec, c, policy="gus", scenario="sustained-overload",
                        n_rep=1, seed=7)
    assert fr.n_requests == len(reqs)


# ---------------------------------------------------------------------------
# Vectorized stream mode: chunking invariance + determinism off the stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", sorted(["paper-default", "diurnal", "flash-crowd",
                                             "hetero-tiers", "sustained-overload",
                                             "diurnal-week"]))
@pytest.mark.parametrize("chunk_ms", [250.0, 3000.0, 7777.0])
def test_vectorized_streaming_chunk_invariance(scenario, chunk_ms):
    """The vectorized stream buffers numpy chunks per edge, but the pull
    pattern still cannot change the draws — frame-by-frame == one-shot."""
    c = cfg()
    one_shot = stream_trace(scenario, 11, 4, 3, c, rng_mode="vectorized")
    s = ArrivalStream(scenario, 11, 4, 3, c, rng_mode="vectorized")
    chunked = []
    t = 0.0
    while not s.exhausted:
        t += chunk_ms
        chunked.extend(s.take_until(t))
    assert [_req_tuple(r) for r in chunked] == [_req_tuple(r) for r in one_shot]


def test_vectorized_stream_bounded_lookahead_and_order():
    s = ArrivalStream("paper-default", 0, 6, 3, cfg(), rng_mode="vectorized")
    assert len(s._heap) <= 6
    first = s.take_until(5000.0)
    assert all(r.arrival_ms < 5000.0 for r in first)
    assert len(s._heap) <= 6
    times = [r.arrival_ms for r in first]
    assert times == sorted(times)
    assert [r.rid for r in first] == list(range(len(first)))


def test_simulate_streaming_vectorized_deterministic():
    spec = demo_cluster_spec()
    a = simulate(spec, cfg(), policy="gus", scenario="sustained-overload", seed=0,
                 rng_mode="vectorized")
    b = simulate(spec, cfg(), policy="gus", scenario="sustained-overload", seed=0,
                 rng_mode="vectorized")
    assert a.as_dict() == b.as_dict()
    assert a.n_served + a.n_dropped == a.n_requests
    assert a.n_requests > 0


# ---------------------------------------------------------------------------
# Overlapped window pipeline: thread safety, shutdown, long-horizon parity
# ---------------------------------------------------------------------------


def _producer_threads():
    return [
        t for t in threading.enumerate() if t.name == "fleet-window-producer"
    ]


def _explode_on_second_call(fn, message):
    calls = {"n": 0}

    def exploding(*args, **kw):
        calls["n"] += 1
        if calls["n"] >= 2:  # let window 0 through, fail while overlapped
            raise RuntimeError(message)
        return fn(*args, **kw)

    return exploding


#: both device fleets run their windows through the same producer thread
PIPELINE_SCHEDULERS = ["dense", "hierarchical"]


@pytest.mark.parametrize("scheduler", PIPELINE_SCHEDULERS)
def test_producer_exception_propagates_without_hang(scheduler, monkeypatch):
    """An exception inside the host-side window builder must surface to the
    caller (not deadlock the queue) and leave no producer thread behind."""
    import repro.core.simulator as sim_mod

    spec = demo_cluster_spec()
    monkeypatch.setattr(sim_mod, "_build_frame_batch", _explode_on_second_call(
        sim_mod._build_frame_batch, "boom in host builder"))
    with pytest.raises(RuntimeError, match="boom in host builder"):
        simulate_fleet(spec, cfg(), policy="gus", n_rep=2, seed=0,
                       options=EngineOptions(scheduler=scheduler, window=2,
                                             prefetch=2))
    for t in _producer_threads():
        t.join(timeout=5.0)
    assert not [t for t in _producer_threads() if t.is_alive()]


@pytest.mark.parametrize("scheduler", PIPELINE_SCHEDULERS)
def test_consumer_error_drains_producer_and_joins(scheduler, monkeypatch):
    """If the *consumer* dies mid-run (device-side error), the early exit
    must drain the bounded queue so the producer unblocks and joins.  The
    dense consumer fails in its window accounting (``satisfied_mask``), the
    hierarchical one in its second call of the jitted runner."""
    import repro.core.simulator as sim_mod

    spec = demo_cluster_spec()
    if scheduler == "dense":
        monkeypatch.setattr(sim_mod, "satisfied_mask", _explode_on_second_call(
            sim_mod.satisfied_mask, "boom in consumer"))
    else:
        real_impl = sim_mod._hier_runner_impl
        monkeypatch.setattr(
            sim_mod, "_hier_runner_impl",
            lambda *a: _explode_on_second_call(real_impl(*a), "boom in consumer"),
        )
    with pytest.raises(RuntimeError, match="boom in consumer"):
        # depth-1 queue + tiny windows: the producer is guaranteed to be
        # blocked in put() when the consumer raises
        simulate_fleet(spec, cfg(), policy="gus", n_rep=2, seed=0,
                       options=EngineOptions(scheduler=scheduler, window=1,
                                             prefetch=1))
    for t in _producer_threads():
        t.join(timeout=5.0)
    assert not [t for t in _producer_threads() if t.is_alive()]


@pytest.mark.parametrize("scheduler", PIPELINE_SCHEDULERS)
def test_no_producer_thread_leak_on_success(scheduler, monkeypatch):
    import repro.core.simulator as sim_mod

    spec = demo_cluster_spec()
    real_build = sim_mod._build_frame_batch
    build_threads = []

    def recording_build(*args, **kw):
        build_threads.append(threading.current_thread().name)
        return real_build(*args, **kw)

    monkeypatch.setattr(sim_mod, "_build_frame_batch", recording_build)
    before = len([t for t in _producer_threads() if t.is_alive()])
    fr = simulate_fleet(spec, cfg(), policy="gus", n_rep=2, seed=0,
                        options=EngineOptions(scheduler=scheduler, window=2,
                                              prefetch=2))
    assert fr.prefetch == 2
    # every window was built on the producer thread, under its one name
    assert build_threads and set(build_threads) == {"fleet-window-producer"}
    assert len([t for t in _producer_threads() if t.is_alive()]) == before


@pytest.mark.slow
def test_sustained_overload_long_horizon_overlap_matches_serial():
    """A long-horizon streaming run under the overlapped pipeline (lazy
    per-window arrivals built in the producer thread) is bit-identical to
    the serial loop — the satellite case the ISSUE calls out."""
    spec = demo_cluster_spec()
    c = cfg(horizon_ms=240_000.0, arrival_rate_per_s=2.0)
    serial = simulate_fleet(spec, c, policy="gus", n_rep=2, seed=3,
                            scenario="sustained-overload", window=4, prefetch=0)
    overlapped = simulate_fleet(spec, c, policy="gus", n_rep=2, seed=3,
                                scenario="sustained-overload", window=4, prefetch=2)
    assert serial.n_requests == overlapped.n_requests
    assert serial.n_served == overlapped.n_served
    np.testing.assert_array_equal(
        serial.satisfied_per_rep, overlapped.satisfied_per_rep
    )
    np.testing.assert_array_equal(serial.mean_us_per_rep, overlapped.mean_us_per_rep)


@pytest.mark.slow
def test_long_horizon_streaming_smoke():
    """10^3 frames through the sequential testbed off the stream — the
    long-horizon mode the materialized path would bloat on."""
    spec = demo_cluster_spec(n_edge=2, n_cloud=1, n_services=2, n_variants=2)
    c = SimConfig(horizon_ms=3_000_000.0, arrival_rate_per_s=0.05,
                  delay_req_ms=6000.0, acc_req_mean=50.0, acc_req_std=10.0)
    r = simulate(spec, c, policy="gus", scenario="diurnal-week", seed=0)
    assert r.n_served + r.n_dropped == r.n_requests
    assert r.n_requests > 100
