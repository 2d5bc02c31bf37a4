"""Benchmark entry point: one function per paper table/figure + systems
benches.  ``PYTHONPATH=src python -m benchmarks.run [--fast]``.

  fig1a-d   — numerical sweeps (Fig. 1(a)-(d))
  fig1e-h   — virtual-testbed sweeps (Fig. 1(e)-(h))
  figures   — paper-figure pipeline: every policy x scenario, JSON + markdown
  resilience — impairment/outage matrix only (the `resilience` paper figure)
  render    — matplotlib panels from the figures JSON (no-op without matplotlib)
  optimal   — GUS vs exact ILP (the ~90%-of-CPLEX table)
  sched     — GUS scheduling throughput (jit/vmap systems number)
  fleet     — sharded Monte-Carlo fleet throughput (BENCH_fleet.json)
  scenarios — satisfied-% per scheduler per registered workload scenario
  telemetry — disabled-path telemetry overhead gate (< 1%)
  roofline  — per-(arch x shape x mesh) roofline table from dry-run reports
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer MC runs")
    ap.add_argument(
        "--only",
        choices=["fig1num", "fig1test", "figures", "resilience", "render", "optimal", "sched", "fleet", "serving", "extensions", "scenarios", "telemetry", "roofline"],
        default=None,
    )
    args = ap.parse_args(argv)
    mc = 64 if args.fast else None

    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    from . import (
        fig1_numerical,
        fig1_testbed,
        fleet_scale,
        optimal_gap,
        paper_figures,
        render_figures,
        roofline_table,
        scenario_sweep,
        scheduler_throughput,
        serving_bench,
        telemetry_overhead,
        extensions_bench,
    )

    jobs = {
        "fig1num": lambda: fig1_numerical.main(**({"mc": mc} if mc else {})),
        "fig1test": lambda: fig1_testbed.main(
            n_points=(200, 1600) if args.fast else (200, 800, 1600),
            seeds=(0,) if args.fast else (0, 1, 2),
        ),
        "figures": lambda: paper_figures.run(tiny=args.fast),
        "resilience": lambda: paper_figures.run(
            tiny=args.fast, only=("resilience",),
            out="results/resilience",
        ),
        "render": lambda: render_figures.main([]),
        "optimal": lambda: optimal_gap.main(10 if args.fast else 25),
        "sched": lambda: scheduler_throughput.main([]),
        "fleet": lambda: fleet_scale.main(["--tiny"] if args.fast else []),
        "serving": lambda: serving_bench.main(6 if args.fast else 12),
        "extensions": lambda: extensions_bench.main(fast=args.fast),
        "scenarios": lambda: (
            scenario_sweep.main(seeds=(0,), n_rep=4) if args.fast else scenario_sweep.main()
        ),
        "telemetry": lambda: telemetry_overhead.main(
            ["--tiny", "--assert-overhead", "0.01"] if args.fast
            else ["--assert-overhead", "0.01"]
        ),
        "roofline": roofline_table.main,
    }
    # `resilience` is an alias for the CI smoke step; the full `figures`
    # pipeline already includes that figure, so skip the alias by default
    selected = [args.only] if args.only else [n for n in jobs if n != "resilience"]
    for name in selected:
        t0 = time.time()
        print(f"\n=== {name} " + "=" * 50, flush=True)
        jobs[name]()
        print(f"=== {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    sys.exit(main())
