"""Telemetry overhead gate: tracing must be free when it is off.

Measures, at the fleet benchmark's 64-replication point (the same
``bench_spec``/``bench_cfg`` as ``benchmarks/fleet_scale.py``):

1. **Disabled-path overhead** — the cost the span instrumentation adds to
   a run with no recorder installed.  The per-span disabled cost (two
   ``perf_counter`` calls, a ``None`` check and a profile-flag check) is
   microbenchmarked directly, the span count of the bench point is taken
   from a recorded run, and their product over the untraced wall time is
   the overhead fraction.  This analytic form is robust to run-to-run noise that
   would swamp a naive wall-clock diff of two sub-second runs; the gate
   (``--assert-overhead``, CI uses 0.01) holds it under 1%.
2. **Enabled overheads** — wall-clock deltas of (a) recording host spans
   and (b) the ``metrics=True`` device stream, reported (not gated):
   enabling telemetry is allowed to cost, disabling it is not.

    PYTHONPATH=src python benchmarks/telemetry_overhead.py --assert-overhead 0.01
    PYTHONPATH=src python -m benchmarks.run --only telemetry
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_SRC = Path(__file__).resolve().parent.parent / "src"
if _SRC.is_dir() and str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

from repro.core import EngineOptions, simulate_fleet  # noqa: E402
from repro.obs import CAT_SCHED, recording, span  # noqa: E402

try:  # imported as benchmarks.telemetry_overhead (run.py)
    from .fleet_scale import POLICY, bench_cfg, bench_spec
except ImportError:  # run as a script: benchmarks/ is sys.path[0]
    from fleet_scale import POLICY, bench_cfg, bench_spec


def _per_span_disabled_s(iters: int = 200_000) -> float:
    """Microbenchmark one disabled span (no recorder installed)."""
    t0 = time.perf_counter()
    for _ in range(iters):
        with span("bench/disabled", CAT_SCHED):
            pass
    return (time.perf_counter() - t0) / iters


def _best_wall(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def measure(*, tiny: bool, repeats: int) -> dict:
    spec = bench_spec()
    cfg = bench_cfg(tiny)
    n_rep = 16 if tiny else 64
    kw = dict(policy=POLICY, n_rep=n_rep, seed=0)

    simulate_fleet(spec, cfg, **kw)  # warmup: compile out of the timings
    base_s = _best_wall(lambda: simulate_fleet(spec, cfg, **kw), repeats)

    with recording() as rec:
        traced_s = _best_wall(lambda: simulate_fleet(spec, cfg, **kw), 1)
    n_spans = sum(1 for e in rec.events() if e["ph"] == "X")

    m_opts = EngineOptions(metrics=True)
    simulate_fleet(spec, cfg, options=m_opts, **kw)  # metrics-variant warmup
    metrics_s = _best_wall(
        lambda: simulate_fleet(spec, cfg, options=m_opts, **kw), repeats
    )

    per_span_s = _per_span_disabled_s()
    disabled_overhead_s = n_spans * per_span_s
    return {
        "bench": {
            "tiny": tiny,
            "n_rep": n_rep,
            "repeats": repeats,
            "wall_s": round(base_s, 4),
        },
        "disabled": {
            "per_span_s": per_span_s,
            "n_spans": n_spans,
            "overhead_s": disabled_overhead_s,
            "overhead_frac": disabled_overhead_s / base_s,
        },
        "enabled_tracing": {
            "wall_s": round(traced_s, 4),
            "n_events": len(rec),
            "overhead_frac": round(traced_s / base_s - 1.0, 4),
        },
        "enabled_metrics": {
            "wall_s": round(metrics_s, 4),
            "overhead_frac": round(metrics_s / base_s - 1.0, 4),
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true", help="CI smoke: small point")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per variant, best kept")
    ap.add_argument("--out", default="results/telemetry_overhead.json")
    ap.add_argument("--assert-overhead", type=float, default=None, metavar="F",
                    help="fail if the disabled-path overhead fraction "
                         "reaches F (CI gates at 0.01 = 1%%)")
    args = ap.parse_args(argv)

    report = measure(tiny=args.tiny, repeats=args.repeats)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(report, indent=2))

    d = report["disabled"]
    print(f"bench point: {report['bench']['n_rep']} reps, "
          f"{report['bench']['wall_s']}s untraced")
    print(f"disabled path: {d['n_spans']} spans x {d['per_span_s']:.2e}s "
          f"= {d['overhead_s']:.2e}s ({100 * d['overhead_frac']:.4f}%)")
    print(f"tracing on:    {100 * report['enabled_tracing']['overhead_frac']:+.2f}%")
    print(f"metrics on:    {100 * report['enabled_metrics']['overhead_frac']:+.2f}%")
    print(f"report -> {args.out}")

    if args.assert_overhead is not None and d["overhead_frac"] >= args.assert_overhead:
        raise SystemExit(
            f"telemetry overhead gate: disabled-path fraction "
            f"{d['overhead_frac']:.4f} >= {args.assert_overhead}"
        )
    if args.assert_overhead is not None:
        print(f"overhead gate: {d['overhead_frac']:.5f} < {args.assert_overhead}")


if __name__ == "__main__":
    main()
