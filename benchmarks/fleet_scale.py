"""Fleet-scale benchmark: Monte-Carlo throughput toward the paper's 20 000
replications, across replication counts, device meshes, and host-pipeline
modes.

Five sweeps over ``simulate_fleet`` on a paper-sized cluster (10 servers,
10 model variants), all with the jitted GUS policy (engine axes are passed
as one ``EngineOptions`` value — the per-call keywords are deprecated):

  replication_sweep  wall-clock and requests/s vs n_rep on one device
  device_sweep       fixed n_rep sharded over 1..D devices (strong scaling)
  weak_scaling       n_rep grows with the device count (per-device throughput)
  overlap_sweep      the 64-replication point under the host-pipeline modes:
                     the serial PR-4 loop (prefetch=0, per-request RNG) vs
                     the overlapped producer + vectorized columnar arrivals
                     (prefetch>0, rng_mode="vectorized", windowed)
  users_sweep        (``--users-sweep``) users-per-frame axis 10^3 -> 10^5 on
                     the ``mega-city`` scenario under the hierarchical
                     class-aggregate scheduler; asserts sub-quadratic
                     wall-time scaling in num_users

Each row reports the end-to-end wall time, the *dispatch* time
(``FleetResult.dispatch_s`` — the phase inside the jitted fleet programs,
which is what device sharding accelerates) and the *generation* time
(``FleetResult.gen_s`` — host-side arrival generation + frame-grid build
that actually *blocked* the pipeline; build work hidden behind device
compute by ``prefetch`` never shows up there).  Rows keep the best of
``--repeats`` runs to shave scheduler noise.

Writes ``results/fleet_scale/BENCH_fleet.json``.  CI gates on it three ways:

* perf-regression gate — ``--compare benchmarks/baselines/BENCH_fleet.json
  --tolerance 0.30`` fails when single-device throughput regresses by more
  than the band against the checked-in baseline
  (``--update-baseline`` refreshes the file);
* multi-device gate — ``--assert-scaling 1.0`` (run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) fails when the
  dispatch-phase throughput at the largest mesh does not beat one device;
* overlap gate — ``--assert-overlap 5.0`` fails unless the overlapped +
  vectorized mode cuts the blocking host generation+build time (``gen_s``)
  of the 64-replication point by at least that factor vs the serial
  per-request pipeline.

Run:

    python benchmarks/fleet_scale.py --tiny                 # CI smoke
    python benchmarks/fleet_scale.py                        # full sweep
    python benchmarks/fleet_scale.py --tiny --assert-overlap 5.0
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python benchmarks/fleet_scale.py --tiny --assert-scaling 1.0
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

_SRC = Path(__file__).resolve().parent.parent / "src"
if _SRC.is_dir() and str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

import jax

from repro.core import (
    EngineOptions,
    SimConfig,
    demo_cluster_spec,
    get_scenario,
    simulate_fleet,
)
from repro.core.impairments import (
    AdmissionConfig,
    BurstyLossLink,
    ImpairmentConfig,
    IntermittentLink,
)
from repro.obs import profile_trace

try:  # imported as benchmarks.fleet_scale (run.py)
    from .common import gate_rows_against_baseline
except ImportError:  # run as a script: benchmarks/ is sys.path[0]
    from common import gate_rows_against_baseline

POLICY = "gus"


def bench_spec():
    """Paper-sized cluster: 9 edges + 1 cloud, 10 model variants — heavy
    enough per frame that the device program dominates a group's cost."""
    return demo_cluster_spec(n_edge=9, n_cloud=1, n_services=5, n_variants=10)


def bench_cfg(tiny: bool) -> SimConfig:
    return SimConfig(
        horizon_ms=12_000.0 if tiny else 30_000.0,
        arrival_rate_per_s=6.0,
        delay_req_ms=6000.0,
        acc_req_mean=50.0,
        acc_req_std=10.0,
    )


def _measure(
    spec, cfg, *, n_rep: int, devices: int, repeats: int,
    scenario="paper-default", policy=POLICY, **opt_kw,
) -> dict:
    """Best-of-``repeats`` timing of one fleet configuration (plus one
    untimed warmup so compilation never lands in a timed run).  Extra
    keywords (prefetch, rng_mode, window, scheduler) become
    ``EngineOptions`` fields."""
    opts = EngineOptions(devices=devices, **opt_kw)
    simulate_fleet(
        spec, cfg, policy=policy, scenario=scenario, n_rep=n_rep, seed=0,
        options=opts,
    )
    best_wall = best_disp = best_gen = float("inf")
    fr = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        fr = simulate_fleet(
            spec, cfg, policy=policy, scenario=scenario, n_rep=n_rep, seed=0,
            options=opts,
        )
        wall = time.perf_counter() - t0
        best_wall = min(best_wall, wall)
        best_disp = min(best_disp, fr.dispatch_s)
        best_gen = min(best_gen, fr.gen_s)
    frames = n_rep * fr.n_frames
    return {
        "n_rep": n_rep,
        "devices": devices,
        "wall_s": round(best_wall, 4),
        "dispatch_s": round(best_disp, 4),
        "gen_s": round(best_gen, 4),
        "gen_share": round(best_gen / best_wall, 4),
        "n_requests": fr.n_requests,
        "n_frames": frames,
        "reqs_per_s": round(fr.n_requests / best_wall, 1),
        "frames_per_s": round(frames / best_wall, 1),
        "dispatch_frames_per_s": round(frames / max(best_disp, 1e-9), 1),
        "per_device_frames_per_s": round(frames / best_wall / devices, 1),
        **{k: v for k, v in opt_kw.items() if v is not None},
    }


def run_users_sweep(*, tiny: bool, repeats: int) -> list:
    """Users-per-frame scaling axis on the ``mega-city`` scenario under the
    hierarchical class-aggregate scheduler (``scheduler="hierarchical"``,
    windowed).  Each point rescales ``rate_per_edge_per_s`` so the nominal
    arrivals per frame hit the target (users = rate * n_edge * frame_s);
    asserts the measured wall time grows *sub-quadratically* in the request
    count between consecutive points — the whole point of scheduling class
    aggregates instead of 10^5 individual users.

    The sweep runs with **admission control and link impairments enabled**
    (class-level shedding + per-member realized channels — the composition
    the PR-9 host loop hard-raised on), and the largest point is re-timed
    under ``REPRO_HIER_HOST_LOOP=1`` (the retained PR-9 per-window host
    loop): the device pipeline must come in measurably faster in the full
    sweep (the 10^5-users point); in ``--tiny`` the speedup is reported
    but not asserted."""
    n_edge = 20
    spec = demo_cluster_spec(n_edge=n_edge, n_cloud=1, n_services=5, n_variants=10)
    cfg = SimConfig(
        horizon_ms=9_000.0,
        admission=AdmissionConfig(enabled=True, shed=True),
        impairments=ImpairmentConfig(
            enabled=True,
            link_profiles=(IntermittentLink(), BurstyLossLink()),
            seed=7,
        ),
    )
    frame_s = cfg.frame_ms / 1000.0
    base = get_scenario("mega-city")
    targets = [1_000, 10_000] if tiny else [1_000, 10_000, 100_000]
    # materialized columnar traces (streaming=False keeps arrivals as array
    # slices, never per-request objects) + per-frame windows with prefetch:
    # the producer thread builds frame k+1's class grid while the device
    # crunches frame k — the overlap the per-window host loop cannot have
    opts = EngineOptions(
        scheduler="hierarchical", window=1, prefetch=2, streaming=False
    )
    rows = []
    for users in targets:
        scn = dataclasses.replace(
            base, rate_per_edge_per_s=users / (n_edge * frame_s)
        )
        best_wall = float("inf")
        fr = None
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            fr = simulate_fleet(
                spec, cfg, policy="gus", scenario=scn, n_rep=1, seed=0,
                options=opts,
            )
            best_wall = min(best_wall, time.perf_counter() - t0)
        row = {
            "users_per_frame": users,
            "n_requests": fr.n_requests,
            "n_frames": fr.n_frames,
            "wall_s": round(best_wall, 4),
            "reqs_per_s": round(fr.n_requests / best_wall, 1),
            "satisfied_pct": round(float(fr.satisfied_per_rep.mean()), 3),
        }
        rows.append(row)
        print(f"users_sweep,users={users},n_requests={fr.n_requests},"
              f"{row['wall_s']}s,{row['reqs_per_s']} req/s", flush=True)

    # PR-9 host-loop baseline at the largest point (same trace; the host
    # loop ignores admission — it predates it — so it does strictly *less*
    # work and still has to lose on wall time)
    top = rows[-1]
    scn = dataclasses.replace(
        base, rate_per_edge_per_s=top["users_per_frame"] / (n_edge * frame_s)
    )
    host_wall = float("inf")
    os.environ["REPRO_HIER_HOST_LOOP"] = "1"
    try:
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            simulate_fleet(
                spec, cfg, policy="gus", scenario=scn, n_rep=1, seed=0,
                options=opts,
            )
            host_wall = min(host_wall, time.perf_counter() - t0)
    finally:
        del os.environ["REPRO_HIER_HOST_LOOP"]
    top["host_loop_wall_s"] = round(host_wall, 4)
    top["device_speedup_vs_host"] = round(host_wall / max(top["wall_s"], 1e-9), 2)
    print(f"users_sweep,host-loop baseline at {top['users_per_frame']} "
          f"users/frame: {top['host_loop_wall_s']}s vs device "
          f"{top['wall_s']}s ({top['device_speedup_vs_host']}x)", flush=True)
    if not tiny and top["device_speedup_vs_host"] <= 1.0:
        raise SystemExit(
            f"users_sweep gate: device hier pipeline ({top['wall_s']}s) is "
            f"not faster than the PR-9 host loop ({top['host_loop_wall_s']}s) "
            f"at the {top['users_per_frame']}-users point"
        )
    import math as _math

    for lo, hi in zip(rows, rows[1:]):
        ratio_n = hi["n_requests"] / max(lo["n_requests"], 1)
        ratio_t = hi["wall_s"] / max(lo["wall_s"], 1e-9)
        exponent = _math.log(ratio_t) / _math.log(ratio_n)
        if ratio_t >= ratio_n**2:
            raise SystemExit(
                f"users_sweep gate: wall time grew {ratio_t:.1f}x for a "
                f"{ratio_n:.1f}x request-count step "
                f"({lo['users_per_frame']} -> {hi['users_per_frame']} "
                f"users/frame) — scaling exponent {exponent:.2f} is not "
                f"sub-quadratic"
            )
        print(f"users_sweep gate: {lo['users_per_frame']} -> "
              f"{hi['users_per_frame']} users/frame scales with exponent "
              f"{exponent:.2f} (< 2 required)", flush=True)
    return rows


def run(*, tiny: bool, out: str, device_counts, repeats: int,
        users_sweep: bool = False) -> dict:
    spec = bench_spec()
    cfg = bench_cfg(tiny)
    # the device sweeps always run the full-size horizon: per-group compute
    # must dominate dispatch overhead for a scaling measurement to mean
    # anything, and at ~1 s per row they stay CI-affordable even in --tiny
    scale_cfg = bench_cfg(tiny=False)
    avail = jax.local_device_count()
    if device_counts is None:
        device_counts = [d for d in (1, 2, 4, 8) if d <= avail]
    device_counts = sorted(set(device_counts))

    rep_values = [16, 64] if tiny else [64, 256, 1024]
    rep_fixed = 64 if tiny else rep_values[-1]
    weak_base = 8 if tiny else 32

    print(f"# fleet_scale: tiny={tiny} devices={device_counts} (avail {avail})")
    replication_sweep = []
    for n_rep in rep_values:
        row = _measure(spec, cfg, n_rep=n_rep, devices=1, repeats=repeats)
        replication_sweep.append(row)
        print(f"replication_sweep,n_rep={n_rep},{row['wall_s']}s,"
              f"{row['reqs_per_s']} req/s", flush=True)

    device_sweep = []
    for d in device_counts:
        row = _measure(spec, scale_cfg, n_rep=rep_fixed, devices=d, repeats=repeats)
        device_sweep.append(row)
        print(f"device_sweep,devices={d},{row['wall_s']}s,"
              f"dispatch={row['dispatch_s']}s", flush=True)

    weak_scaling = []
    for d in device_counts:
        row = _measure(
            spec, scale_cfg, n_rep=weak_base * d, devices=d, repeats=repeats
        )
        weak_scaling.append(row)
        print(f"weak_scaling,devices={d},n_rep={weak_base * d},"
              f"per_device={row['per_device_frames_per_s']} frames/s", flush=True)

    # host-pipeline modes at the ISSUE's 64-replication point: the serial
    # PR-4 loop vs the overlapped producer + vectorized columnar arrivals.
    # `serial` pins prefetch=0 + the per-request RNG (the pre-overlap
    # pipeline, bit-identical to the default mode's results); `overlap`
    # windows the scan (~4 windows over the horizon) so the producer has
    # device compute to hide the grid build behind.
    import numpy as _np

    T = int(_np.ceil(cfg.horizon_ms / cfg.frame_ms))
    W = max(1, T // 4)
    overlap_sweep = []
    for label, kw in [
        ("serial", dict(prefetch=0, rng_mode="paper-default")),
        ("prefetch", dict(prefetch=2, window=W, rng_mode="paper-default")),
        ("vectorized", dict(prefetch=0, rng_mode="vectorized")),
        ("overlap", dict(prefetch=2, window=W, rng_mode="vectorized")),
    ]:
        row = _measure(spec, cfg, n_rep=64, devices=1, repeats=repeats, **kw)
        row["mode"] = label
        overlap_sweep.append(row)
        print(f"overlap_sweep,mode={label},{row['wall_s']}s,"
              f"gen={row['gen_s']}s ({row['gen_share']:.0%} of wall),"
              f"dispatch={row['dispatch_s']}s", flush=True)
    serial_row = overlap_sweep[0]
    overlap_row = overlap_sweep[-1]
    overlap_summary = {
        "n_rep": 64,
        "gen_s_serial": serial_row["gen_s"],
        "gen_s_overlap": overlap_row["gen_s"],
        "gen_s_reduction": round(serial_row["gen_s"] / max(overlap_row["gen_s"], 1e-9), 2),
        "gen_share_serial": serial_row["gen_share"],
        "gen_share_overlap": overlap_row["gen_share"],
        "wall_speedup": round(serial_row["wall_s"] / overlap_row["wall_s"], 2),
    }
    print(f"overlap: host gen+build blocking {serial_row['gen_s']}s -> "
          f"{overlap_row['gen_s']}s ({overlap_summary['gen_s_reduction']}x lower), "
          f"end-to-end {overlap_summary['wall_speedup']}x", flush=True)

    # scaling between the smallest and largest swept mesh (usually 1 -> D,
    # but an explicit --devices list without 1 still gets a valid report)
    base, top = device_sweep[0], device_sweep[-1]
    scaling = {
        "base_devices": base["devices"],
        "devices": top["devices"],
        "end_to_end": round(base["wall_s"] / top["wall_s"], 3),
        "dispatch": round(
            top["dispatch_frames_per_s"] / max(base["dispatch_frames_per_s"], 1e-9), 3
        ),
    }
    print(f"scaling {base['devices']} -> {top['devices']} devices: "
          f"end-to-end {scaling['end_to_end']}x, dispatch {scaling['dispatch']}x")

    report = {
        "meta": {
            "bench": "fleet_scale",
            "tiny": tiny,
            "policy": POLICY,
            "jax": jax.__version__,
            "devices_available": avail,
            "repeats": repeats,
            "horizon_ms": cfg.horizon_ms,
            "arrival_rate_per_s": cfg.arrival_rate_per_s,
        },
        "replication_sweep": replication_sweep,
        "device_sweep": device_sweep,
        "weak_scaling": weak_scaling,
        "scaling_1_to_max": scaling,
        "overlap_sweep": overlap_sweep,
        "overlap_summary": overlap_summary,
    }
    if users_sweep:
        report["users_sweep"] = run_users_sweep(tiny=tiny, repeats=repeats)
    out_dir = Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "BENCH_fleet.json"
    path.write_text(json.dumps(report, indent=2))
    print(f"wrote {path}")
    return report


def compare_against_baseline(report: dict, baseline_path: str, tolerance: float):
    """Fail (SystemExit) when single-device throughput regresses by more
    than ``tolerance`` against the checked-in baseline.  Rows are matched
    on (n_rep, devices); unmatched rows are skipped, so the baseline can
    lag the sweep's shape."""
    baseline = json.loads(Path(baseline_path).read_text())
    gate_rows_against_baseline(
        report["replication_sweep"],
        baseline.get("replication_sweep", []),
        key_fn=lambda r: (r["n_rep"], r["devices"]),
        metric="reqs_per_s",
        tolerance=tolerance,
        baseline_path=baseline_path,
        unit=" req/s",
        gate_name="perf gate",
    )
    if "users_sweep" in report and baseline.get("users_sweep"):
        gate_rows_against_baseline(
            report["users_sweep"],
            baseline["users_sweep"],
            key_fn=lambda r: r["users_per_frame"],
            metric="reqs_per_s",
            tolerance=tolerance,
            baseline_path=baseline_path,
            unit=" req/s",
            gate_name="users-sweep perf gate",
        )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true", help="CI smoke: small sweep")
    ap.add_argument("--out", default="results/fleet_scale")
    ap.add_argument("--devices", type=int, action="append",
                    help="device count to sweep (repeatable; default powers "
                         "of two up to jax.local_device_count())")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repeats per row, best kept (default 3; 2 tiny)")
    ap.add_argument("--users-sweep", action="store_true",
                    help="also sweep users-per-frame 10^3 -> 10^5 (10^4 in "
                         "--tiny) on the mega-city scenario under the "
                         "hierarchical scheduler, asserting sub-quadratic "
                         "wall-time scaling")
    ap.add_argument("--compare", metavar="BASELINE_JSON",
                    help="perf-regression gate against a checked-in baseline")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional throughput drop for --compare")
    ap.add_argument("--assert-scaling", default=None, metavar="X",
                    help="fail unless dispatch-phase throughput at the largest "
                         "mesh beats X times one device; 'auto' requires >1.0 "
                         "on hosts with >= 4 cores (virtual devices have real "
                         "parallel headroom there) and a 0.7 no-degradation "
                         "floor on smaller hosts")
    ap.add_argument("--assert-overlap", type=float, default=None, metavar="X",
                    help="fail unless prefetch + rng_mode=vectorized cut the "
                         "blocking host generation+build time (gen_s) of the "
                         "64-replication point by more than X times vs the "
                         "serial per-request pipeline")
    ap.add_argument("--update-baseline", metavar="PATH",
                    help="also write the report to PATH (refresh the baseline)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the sweep "
                         "into DIR (the program's spans are annotated)")
    args = ap.parse_args(argv)

    repeats = args.repeats if args.repeats is not None else (2 if args.tiny else 3)
    with profile_trace(args.profile):
        report = run(tiny=args.tiny, out=args.out, device_counts=args.devices,
                     repeats=repeats, users_sweep=args.users_sweep)

    if args.update_baseline:
        Path(args.update_baseline).parent.mkdir(parents=True, exist_ok=True)
        Path(args.update_baseline).write_text(json.dumps(report, indent=2))
        print(f"baseline refreshed at {args.update_baseline}")
    if args.compare:
        compare_against_baseline(report, args.compare, args.tolerance)
    if args.assert_scaling is not None:
        cores = os.cpu_count() or 1
        if args.assert_scaling == "auto":
            floor = 1.0 if cores >= 4 else 0.7
        else:
            floor = float(args.assert_scaling)
        got = report["scaling_1_to_max"]["dispatch"]
        d_base = report["scaling_1_to_max"]["base_devices"]
        d_max = report["scaling_1_to_max"]["devices"]
        if d_max <= d_base:
            raise SystemExit("--assert-scaling needs a multi-device sweep; "
                             "set XLA_FLAGS=--xla_force_host_platform_device_count=8")
        if got <= floor:
            raise SystemExit(
                f"dispatch throughput scaling {d_base} -> {d_max} devices is "
                f"{got}x, required > {floor}x ({cores} cores)"
            )
        print(f"scaling gate: {got}x > {floor}x on {d_base} -> {d_max} devices "
              f"({cores} cores)")
    if args.assert_overlap is not None:
        got = report["overlap_summary"]["gen_s_reduction"]
        if got < args.assert_overlap:
            raise SystemExit(
                f"overlap gate: blocking host gen+build reduced only {got}x "
                f"at the 64-replication point, required >= {args.assert_overlap}x "
                f"(serial {report['overlap_summary']['gen_s_serial']}s vs "
                f"overlapped {report['overlap_summary']['gen_s_overlap']}s)"
            )
        print(f"overlap gate: gen_s reduced {got}x >= {args.assert_overlap}x")


if __name__ == "__main__":
    main()
