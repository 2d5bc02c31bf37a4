"""Readings that set a cell's correctness limits, in one process.

    python bench/readings.py --workload <cell> --seeds <a> <b> ... \
        [--seconds 3] [--control-seeds <c> ...]

For each ``--seeds`` entry: one run of the cell (short window, no trace),
printing the numbers ``correct`` compares (the lower readings: what sound
runs of the program give).  For each ``--control-seeds`` entry: the
driver's control, the plain reference one precision lower in the
program's place, at the cell's own size (the upper readings).  One JSON
line per reading.  Refuses a host without a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from run import ROOT, BenchError, Cell, devices, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(ROOT, args.workload)
        devices(cell.chips)
    except BenchError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    for s in args.seeds:
        res = run_cell(cell, s, args.seconds, False, t_start=time.perf_counter(),
                       log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps(dict(kind="program", seed=s, attempted=res["attempted"],
                              metrics={k: v["value"] for k, v in res["metrics"].items()},
                              **{k: v["value"] for k, v in res["checks"].items()})),
              flush=True)
    for s in args.control_seeds:
        t0 = time.perf_counter()
        got = cell.driver(s).control(np.random.default_rng(s))
        print(json.dumps(dict(kind="control", seed=s, seconds=time.perf_counter() - t0, **got)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
