"""Telemetry subsystem: inertness, metric-stream correctness, trace schema.

The contracts under test (see ``docs/architecture.md`` section 10):

* **Inertness** — ``metrics=True`` never changes simulation results: for
  every vmappable policy, congestion on/off, impairments on/off, the
  fleet's result fields are bit-identical with the metric stream on and
  off (the disabled path traces the exact pre-telemetry program, so
  equality with the enabled run pins both).  Same for ``simulate`` and
  the host-side (ILP) fleet path.
* **Stream correctness** — per-frame rows satisfy the counting
  invariants (shed <= arrivals, tier histogram sums to served, QoS class
  counts sum to arrivals, utilizations/backlogs finite and >= 0) and
  aggregate EXACTLY to the ``SimResult`` / ``FleetResult`` totals.
* **Tracing** — spans record only while a recorder is installed, the
  emitted JSON passes :func:`validate_chrome_trace`, producer-thread
  spans land on their own tid, and the JSONL exporter's io spans ride
  the "telemetry-writer" thread.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (  # noqa: E402
    AdmissionConfig,
    CongestionConfig,
    ImpairmentConfig,
    IntermittentLink,
    SimConfig,
    demo_cluster_spec,
    get_policy,
    list_policies,
    simulate,
    simulate_fleet,
)
from repro.obs import (  # noqa: E402
    QOS_ACC_EDGES,
    AsyncJsonlWriter,
    MetricsFrame,
    MetricsResult,
    Stopwatch,
    active_recorder,
    instant,
    recording,
    span,
    validate_chrome_trace,
)

VMAPPABLE = [p for p in list_policies() if get_policy(p).vmappable]

SPEC = demo_cluster_spec()

IMPAIRED = ImpairmentConfig(
    enabled=True, link_profiles=(IntermittentLink(),), seed=3,
    outage_mtbf_frames=6.0, outage_mttr_frames=3.0, outage_servers=(1,),
)


def cfg(congestion: bool = False, impaired: bool = False, **kw) -> SimConfig:
    base = dict(
        horizon_ms=4000.0,
        arrival_rate_per_s=4.0,
        delay_req_ms=3000.0,
        acc_req_mean=50.0,
        acc_req_std=10.0,
        congestion=CongestionConfig(enabled=congestion),
        admission=AdmissionConfig(enabled=True, shed=True, queue_cap_mult=2.0),
        impairments=IMPAIRED if impaired else ImpairmentConfig(),
    )
    base.update(kw)
    return SimConfig(**base)


def _assert_fleet_equal(a, b):
    assert a.n_requests == b.n_requests
    assert a.n_served == b.n_served
    np.testing.assert_array_equal(a.satisfied_per_rep, b.satisfied_per_rep)
    np.testing.assert_array_equal(a.mean_us_per_rep, b.mean_us_per_rep)
    assert a.mean_compute_inflation == b.mean_compute_inflation


# ---------------------------------------------------------------------------
# inertness: metrics on/off bitwise parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", VMAPPABLE)
@pytest.mark.parametrize("congestion", [False, True])
@pytest.mark.parametrize("impaired", [False, True])
def test_fleet_metrics_bitwise_inert(policy, congestion, impaired):
    c = cfg(congestion, impaired)
    off = simulate_fleet(SPEC, c, policy=policy, n_rep=2, seed=7)
    on = simulate_fleet(SPEC, c, policy=policy, n_rep=2, seed=7, metrics=True)
    _assert_fleet_equal(off, on)
    assert off.metrics is None
    assert on.metrics is not None


@pytest.mark.parametrize("congestion", [False, True])
def test_simulate_metrics_bitwise_inert(congestion):
    c = cfg(congestion)
    off = simulate(SPEC, c, seed=5)
    on = simulate(SPEC, c, seed=5, metrics=True)
    assert off.n_satisfied == on.n_satisfied
    assert off.n_served == on.n_served
    assert off.mean_us == on.mean_us
    assert off.mean_completion_ms == on.mean_completion_ms
    assert off.bandwidth_estimates == on.bandwidth_estimates
    assert off.metrics is None and on.metrics is not None


def test_host_fleet_metrics_inert():
    # low rate: the exact ILP refuses frames above its variable budget
    c = cfg(congestion=True, arrival_rate_per_s=1.0)
    off = simulate_fleet(SPEC, c, policy="ilp", n_rep=2, seed=1)
    on = simulate_fleet(SPEC, c, policy="ilp", n_rep=2, seed=1, metrics=True)
    _assert_fleet_equal(off, on)
    assert on.metrics is not None


# ---------------------------------------------------------------------------
# metric-stream correctness
# ---------------------------------------------------------------------------


def _check_invariants(m: MetricsResult, n_servers: int):
    d = m.data
    assert d["n_shed"].sum() >= 0
    assert np.all(d["n_shed"] <= d["n_arrivals"])
    assert np.all(d["n_served"] <= d["n_arrivals"])
    assert np.all(d["n_satisfied"] <= d["n_served"])
    assert np.all(d["tier_hist"].sum(-1) == d["n_served"])
    assert np.all(d["qos_count"].sum(-1) == d["n_arrivals"])
    assert np.all(d["qos_sat"] <= d["qos_count"])
    for f in ("util_gamma", "util_eta", "backlog_gamma", "backlog_eta"):
        assert d[f].shape[-1] == n_servers
        assert np.all(np.isfinite(d[f]))
        assert np.all(d[f] >= 0.0)
    assert d["qos_count"].shape[-1] == len(QOS_ACC_EDGES) + 1


def test_fleet_metrics_invariants_and_totals():
    c = cfg(congestion=True, impaired=True)
    fr = simulate_fleet(SPEC, c, n_rep=3, seed=2, metrics=True)
    m = fr.metrics
    assert m.fleet and m.n_rep == 3 and m.n_frames == fr.n_frames
    _check_invariants(m, SPEC.n_servers)
    agg = m.aggregate()
    assert agg["n_arrivals"] == fr.n_requests
    assert agg["n_served"] == fr.n_served
    sat_per_rep = m.data["n_satisfied"].sum(1)
    reqs_per_rep = m.data["n_arrivals"].sum(1)
    np.testing.assert_allclose(
        100.0 * sat_per_rep / np.maximum(reqs_per_rep, 1),
        fr.satisfied_per_rep,
    )
    # congestion on: some backlog must actually appear in the stream
    assert m.data["backlog_gamma"].max() >= 0.0


def test_simulate_metrics_aggregate_matches_exactly():
    c = cfg(congestion=True)
    r = simulate(SPEC, c, seed=4, metrics=True)
    m = r.metrics
    assert not m.fleet
    _check_invariants(m, SPEC.n_servers)
    agg = m.aggregate()
    assert agg["n_arrivals"] == r.n_requests
    assert agg["n_served"] == r.n_served
    assert agg["n_satisfied"] == r.n_satisfied
    assert agg["n_local"] == r.n_local
    assert agg["n_cloud"] == r.n_cloud
    assert agg["n_edge_offload"] == r.n_edge_offload
    # decision times are monotone and frame-aligned or early-closed
    assert np.all(np.diff(m.t_ms) > 0)


def test_windowed_fleet_metrics_match_materialized():
    c = cfg(congestion=True)
    full = simulate_fleet(SPEC, c, n_rep=3, seed=0, metrics=True)
    windowed = simulate_fleet(SPEC, c, n_rep=3, seed=0, metrics=True, window=1)
    for f in MetricsFrame._fields:
        np.testing.assert_array_equal(
            full.metrics.data[f], windowed.metrics.data[f], err_msg=f
        )


def test_metrics_rollups_and_jsonl(tmp_path):
    fr = simulate_fleet(SPEC, cfg(congestion=True), n_rep=2, seed=0, metrics=True)
    m = fr.metrics
    pct = m.percentiles("backlog_gamma")
    assert set(pct) == {"p50", "p90", "p99"} and pct["p50"] <= pct["p99"]
    roll = m.per_edge_rollup()
    assert len(roll["util_gamma"]) == SPEC.n_edge
    assert len(roll["util_gamma_cloud"]) == SPEC.n_servers - SPEC.n_edge

    path = tmp_path / "m.jsonl"
    n = m.to_jsonl(path)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert n == len(rows) == m.n_rep * m.n_frames
    assert sum(r["n_satisfied"] for r in rows) == m.aggregate()["n_satisfied"]
    assert {"frame", "t_ms", "rep", "tier", "qos_sat", "util_gamma"} <= set(rows[0])


def test_async_jsonl_writer(tmp_path):
    path = tmp_path / "w.jsonl"
    with recording() as rec:
        with AsyncJsonlWriter(path) as w:
            for i in range(100):
                w.write({"i": i})
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["i"] for r in rows] == list(range(100))
    # the writer thread's io spans were recorded under its own name
    io = [e for e in rec.events() if e.get("cat") == "io"]
    assert io and rec.to_chrome_trace()
    names = [
        e["args"]["name"] for e in rec.to_chrome_trace()["traceEvents"]
        if e["ph"] == "M"
    ]
    assert "telemetry-writer" in names


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_span_inert_without_recorder():
    assert active_recorder() is None
    with span("unit/x") as s:
        pass
    assert s.elapsed_s >= 0.0
    instant("unit/i")  # no-op, must not raise
    assert active_recorder() is None


def test_stopwatch_accumulates_with_tracing_off():
    sw = Stopwatch()
    with sw.span("a"):
        pass
    with sw.span("a"):
        pass
    with sw.span("b"):
        pass
    assert sw.total("a") > 0.0
    assert sw.total("a", "b") == pytest.approx(sw.total("a") + sw.total("b"))
    assert set(sw.as_dict()) == {"a", "b"}


def test_recording_scopes_and_schema(tmp_path):
    with recording() as rec:
        simulate_fleet(SPEC, cfg(), n_rep=2, seed=0, metrics=True)
    assert active_recorder() is None
    assert {"gen", "build", "dispatch", "metrics"} <= rec.categories()
    assert "fleet/dispatch" in rec.span_names()
    path = tmp_path / "trace.json"
    rec.save(path)
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    assert any(e["ph"] == "M" for e in obj["traceEvents"])
    # after the recorder is gone, new spans don't grow it
    n = len(rec)
    with span("unit/after"):
        pass
    assert len(rec) == n


def test_validate_chrome_trace_rejects_garbage():
    assert validate_chrome_trace(42)
    assert validate_chrome_trace({"nope": []})
    assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "a", "cat": "c", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": -1.0}
    ]}
    assert validate_chrome_trace(bad_dur)


def test_producer_thread_spans_on_own_tid():
    with recording() as rec:
        simulate_fleet(SPEC, cfg(), n_rep=2, seed=0, window=1, prefetch=1)
    trace = rec.to_chrome_trace()
    names = {
        e["tid"]: e["args"]["name"]
        for e in trace["traceEvents"] if e["ph"] == "M"
    }
    assert "fleet-window-producer" in names.values()
    prod_tid = next(t for t, n in names.items() if n == "fleet-window-producer")
    prod_spans = [
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e["tid"] == prod_tid
    ]
    assert {e["name"] for e in prod_spans} >= {"fleet/arrivals", "fleet/grid_build"}
    assert len(rec.thread_ids()) >= 2


def test_timings_fields_derive_from_spans():
    r = simulate(SPEC, cfg(), seed=0)
    assert set(r.timings) >= {"gen_s", "build_s", "sched_s", "realize_s", "total_s"}
    assert all(v >= 0.0 for v in r.timings.values())
    fr = simulate_fleet(SPEC, cfg(), n_rep=2, seed=0)
    assert fr.timings["total_s"] > 0.0
    assert fr.gen_s == pytest.approx(
        fr.timings.get("fleet/generate_traces", 0.0)
        + fr.timings.get("fleet/window_wait", 0.0)
    )
    assert fr.dispatch_s == pytest.approx(fr.timings.get("fleet/dispatch", 0.0))


def test_golden_trace_is_valid():
    with open("results/telemetry/golden_trace.json") as f:
        obj = json.load(f)
    assert validate_chrome_trace(obj) == []
    cats = {e["cat"] for e in obj["traceEvents"] if e["ph"] not in ("M",)}
    assert len(cats) >= 4
    tids = {e["tid"] for e in obj["traceEvents"]}
    assert len(tids) >= 2


# ---------------------------------------------------------------------------
# end-to-end: the documented CLI invocation
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_run_scenario_metrics_and_trace(tmp_path, monkeypatch):
    import sys
    sys.path.insert(0, "examples")
    try:
        import run_scenario
    finally:
        sys.path.pop(0)
    monkeypatch.chdir(tmp_path)
    trace_path = tmp_path / "trace.json"
    r, _ = run_scenario.main([
        "--scenario", "sustained-overload", "--congestion", "--metrics",
        "--trace", str(trace_path), "--horizon-s", "6",
    ])
    obj = json.loads(trace_path.read_text())
    assert validate_chrome_trace(obj) == []
    events = obj["traceEvents"]
    cats = {e["cat"] for e in events if e["ph"] != "M"}
    assert len(cats) >= 4
    assert len({e["tid"] for e in events}) >= 2
    out = tmp_path / "results" / "telemetry" / "sustained-overload-gus.metrics.jsonl"
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert sum(row["n_satisfied"] for row in rows) == r.n_satisfied
    assert sum(row["n_arrivals"] for row in rows) == r.n_requests


@pytest.mark.parametrize("failing", ["start_trace", "stop_trace"])
def test_profile_trace_raises_when_trace_fails(tmp_path, monkeypatch, failing):
    """A requested profile that cannot start or stop is an error: the run
    never exits as if it had been traced."""
    import jax

    from repro.obs import profile_trace, profiling_active

    def boom(*a, **k):
        raise RuntimeError(f"{failing} unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, failing, boom)
    with pytest.raises(RuntimeError, match=failing):
        with profile_trace(tmp_path / "prof"):
            pass
    assert not profiling_active()
    with profile_trace(None):  # no profile asked for: nothing starts
        assert not profiling_active()


# ---------------------------------------------------------------------------
# one instrument: spans on the profiler's clock, counters at the boundaries
# ---------------------------------------------------------------------------

#: the dense fleet's and the online decision's spans that own device gaps
ANNOTATED = {"gus/pad", "gus/call", "fleet/generate_traces", "fleet/arrivals",
             "fleet/grid_build", "fleet/put", "fleet/drain", "fleet/window_metrics"}


def _profiled_host_names(tmp_path, monkeypatch, active: bool) -> set:
    """Host-plane event names of a real CPU profiler trace, started as the
    benchmark harness starts its own (Python tracer off, host tracer level
    2) with the program's ``_ACTIVE`` flag set to ``active``, around a tiny
    dense fleet and one padded ``gus_schedule`` call."""
    from jax.profiler import ProfileData

    from repro.core import gus_schedule
    from repro.core.instance import generate_instance, pad_instance
    from repro.obs import profiler

    inst = generate_instance(0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    monkeypatch.setattr(profiler, "_ACTIVE", active)
    try:
        simulate_fleet(SPEC, cfg(), n_rep=2, seed=0)
        np.asarray(gus_schedule(pad_instance(inst, 128)).j)
    finally:
        monkeypatch.setattr(profiler, "_ACTIVE", False)
        jax.profiler.stop_trace()
    (pb,) = sorted(tmp_path.rglob("*.xplane.pb"))
    return {ev.name for plane in ProfileData.from_file(str(pb)).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events}


@pytest.mark.parametrize("active", [True, False])
def test_spans_are_profiler_annotations_while_active(tmp_path, monkeypatch, active):
    """Under an active profile every work span lands on the host plane and
    the consumer's wait does not; with the flag off no span does."""
    names = _profiled_host_names(tmp_path, monkeypatch, active)
    assert "fleet/window_wait" not in names
    if active:
        assert ANNOTATED <= names
        assert "fleet/dispatch" in names
    else:
        assert not names & (ANNOTATED | {"fleet/dispatch"})


def test_profile_trace_passes_profiler_options(tmp_path, monkeypatch):
    """``profile_trace`` starts the profiler as the benchmark does: host
    tracer level 2, the Python tracer off; spans see the flag while the
    block runs."""
    from repro.obs import profile_trace, profiler, profiling_active

    seen = {}

    def start(log_dir, **kw):
        seen.update(kw, log_dir=log_dir, active=profiler._ACTIVE)

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    with profile_trace(tmp_path / "prof"):
        assert profiling_active() and profiler._ACTIVE is True
    assert profiler._ACTIVE is False and not seen["active"]
    opts = seen["profiler_options"]
    assert opts.host_tracer_level == 2
    assert opts.python_tracer_level == 0
    assert seen["log_dir"] == str(tmp_path / "prof")


def test_fleet_counters_match_shapes():
    """``fleet/h2d_bytes`` is the bytes of every host array placed for the
    scan, ``fleet/rows`` the padded rows scheduled and
    ``fleet/accounting_h2d_bytes`` the accounting's one upload, each
    computed here from shapes and dtypes (3 reps in groups of 2, so one
    padded replication; 3 frames in windows of 2)."""
    from repro.core import EngineOptions
    from repro.core.simulator import _pad_bucket

    c = cfg(horizon_ms=9000.0)
    fr = simulate_fleet(SPEC, c, n_rep=3, seed=0,
                        options=EngineOptions(metrics=True, rep_group=2, window=2))
    T, n_rep, R_pad = fr.n_frames, 3, 4
    n_pad = _pad_bucket(int(fr.metrics.data["n_arrivals"].max()))
    M, L = SPEC.n_servers, SPEC.acc.shape[1]
    f4, b1 = 4, 1  # float32/int32/uint32, bool
    per_frame = (n_pad * 5 * f4                  # cover, A, C, w_a, w_c
                 + n_pad * M * L * (4 * f4 + b1)  # acc, ctime, v, u, avail
                 + M * 2 * f4 + 2 * f4            # gamma, eta, max_as, max_cs
                 + 2 * f4                         # PRNG key
                 + n_pad * f4 + 2 * M * f4        # queueing delay, links, server up
                 + f4)                            # real-row count (metrics)
    assert fr.timings["fleet/h2d_bytes"] == R_pad * T * per_frame
    assert fr.timings["fleet/rows"] == n_rep * T * n_pad
    acct = n_pad * (2 * M * L * f4 + 4 * f4 + 2 * f4) + 2 * f4  # + assignment j, l
    assert fr.timings["fleet/accounting_h2d_bytes"] == n_rep * T * acct
    assert {"fleet/put", "fleet/drain"} <= set(fr.timings)


def test_accounting_reads_only_its_declared_leaves():
    """The fleet uploads and counts only ``SATISFACTION_LEAVES`` (and
    ``CONGESTED_CTIME_LEAVES`` under congestion) for its accounting: the
    functions give the same answers on an instance holding nothing else,
    so no other leaf is uploaded behind ``fleet/accounting_h2d_bytes``."""
    import dataclasses

    from repro.core import gus_schedule
    from repro.core.instance import generate_instance
    from repro.core.queueing import CONGESTED_CTIME_LEAVES, congested_ctime
    from repro.core.satisfaction import SATISFACTION_LEAVES, mean_us, satisfied_mask

    inst = generate_instance(0)
    a = gus_schedule(inst)

    def only(leaves):
        return dataclasses.replace(inst, **{f.name: None for f in dataclasses.fields(inst)
                                            if f.name not in leaves})

    sat = only(SATISFACTION_LEAVES)
    np.testing.assert_array_equal(satisfied_mask(sat, a.j, a.l), satisfied_mask(inst, a.j, a.l))
    np.testing.assert_array_equal(mean_us(sat, a.j, a.l), mean_us(inst, a.j, a.l))
    M = inst.n_servers
    tq = np.full(inst.n_requests, 5.0, np.float32)
    phi_c, phi_e = np.linspace(1.0, 2.0, M, dtype=np.float32), np.full(M, 1.5, np.float32)
    np.testing.assert_array_equal(congested_ctime(only(CONGESTED_CTIME_LEAVES), tq, phi_c, phi_e),
                                  congested_ctime(inst, tq, phi_c, phi_e))


def test_hier_post_is_split_into_drain_and_deaggregate():
    from repro.core import EngineOptions

    fr = simulate_fleet(SPEC, cfg(), policy="gus", n_rep=2, seed=0,
                        options=EngineOptions(scheduler="hierarchical"))
    assert {"fleet/hier_drain", "fleet/hier_deaggregate"} <= set(fr.timings)
    assert "fleet/hier_post" not in fr.timings


def test_stopwatch_counts_and_counter_events():
    sw = Stopwatch()
    with recording() as rec:
        sw.count("unit/bytes", 3)
        sw.count("unit/bytes", 4)
        with sw.span("unit/a"):
            pass
    assert sw.as_dict()["unit/bytes"] == 7 and sw.total("unit/a") > 0.0
    counters = [e for e in rec.to_chrome_trace()["traceEvents"] if e["ph"] == "C"]
    assert [e["args"]["unit/bytes"] for e in counters] == [3, 4]
    assert validate_chrome_trace(rec.to_chrome_trace()) == []


def test_stopwatch_shared_across_threads_loses_no_update():
    """The fleet's producer and worker threads share one stopwatch: many
    threads with a short switch interval lose neither a count nor a span."""
    import sys
    import threading

    sw = Stopwatch()
    n_threads, n_iter = 16, 500

    def work():
        for _ in range(n_iter):
            sw.count("unit/rows", 1)
            sw._add("unit/s", 1.0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert sw.counts["unit/rows"] == n_threads * n_iter
    assert sw.totals["unit/s"] == n_threads * n_iter
