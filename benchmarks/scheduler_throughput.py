"""Systems benchmark: GUS scheduling throughput.

The paper argues GUS is a 'polynomial constant-time' online decision
algorithm; here we measure the jit+vmap implementation's decisions/second —
the number that determines how many edge frames per second one controller
can schedule.  Prints CSV (impl,batch,instances_per_s,us_per_call) and
writes ``results/scheduler_throughput/BENCH_scheduler.json``.

Two device backends are measured: the jitted XLA loop (``jax-jit`` /
``jax-vmap`` rows) and the fused Pallas kernel (``pallas`` rows, see
:mod:`repro.kernels.gus_pallas`).  Before any Pallas row is timed its
assignments are asserted **bit-identical** to the XLA path — a CPU run
(interpret mode) therefore gates *parity*, while an accelerator run also
gates *speed*: on TPU the Pallas rows are compiled Mosaic, enter the
baseline gate, and the batch-64 Pallas point must be no slower than the
batch-64 XLA point.

CI gates on it: ``--compare benchmarks/baselines/BENCH_scheduler.json
--tolerance 0.50`` fails when a gated row's throughput regresses by more
than the band against the checked-in baseline (the wide band absorbs
shared-runner noise; ``--update-baseline`` refreshes the file).  The
un-jitted numpy oracle row and interpret-mode Pallas rows are reported but
never gated — parity references, not products.  The report's ``meta``
records the jax/jaxlib versions and the device platform/kind so baseline
mismatches across containers are diagnosable from the JSON alone.

Run:

    PYTHONPATH=src python -m benchmarks.scheduler_throughput
    PYTHONPATH=src python -m benchmarks.scheduler_throughput \\
        --compare benchmarks/baselines/BENCH_scheduler.json
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jaxlib
import numpy as np

from repro.core import (
    GeneratorConfig,
    aggregate_instance,
    generate_batch,
    generate_instance,
    gus_schedule,
    gus_schedule_batch,
    gus_schedule_np,
    hier_backend_fn,
    hier_cells_np,
)
from repro.kernels.gus_pallas import pallas_interpret
from repro.kernels.hier_pallas import hier_cells_pallas

from .common import csv_row, gate_rows_against_baseline

CFG = GeneratorConfig()  # paper scale: N=100, M=10, L=10


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def _env_meta() -> dict:
    """Toolchain + device identity for cross-container baseline forensics."""
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "device_platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.local_device_count(),
        "pallas_interpret": pallas_interpret(),
    }


def _assert_bit_parity(a, b, what: str):
    """Integer assignments must agree exactly — the Pallas rows are only
    timed after they have earned their place on the same plot."""
    for field in ("j", "l"):
        av, bv = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        if not np.array_equal(av, bv):
            raise SystemExit(
                f"scheduler bench: pallas/xla assignment mismatch on {what} "
                f"({field}: {int((av != bv).sum())} cells differ) — refusing "
                "to benchmark a kernel that is not bit-identical"
            )


def _hier_class_args(inst, k: int = 3):
    """Class tensors for the hierarchical allocator rows: a paper-scale
    frame tiled ``k``-fold so every class carries a multi-member count and
    the analytic chunk loop actually loops."""
    rep = lambda x: np.repeat(np.asarray(x), k, axis=0)  # noqa: E731
    import dataclasses

    tiled = dataclasses.replace(
        inst,
        cover=rep(inst.cover), A=rep(inst.A), C=rep(inst.C),
        w_a=rep(inst.w_a), w_c=rep(inst.w_c),
        acc=rep(inst.acc), ctime=rep(inst.ctime), v=rep(inst.v),
        u=rep(inst.u), avail=rep(inst.avail),
    )
    agg = aggregate_instance(tiled)
    o = np.argsort(agg.first_idx, kind="stable")
    return (
        agg.us[o], agg.feas[o], agg.v[o], agg.u[o],
        agg.cover[o].astype(np.int32), agg.count[o].astype(np.int32),
        np.asarray(inst.gamma, np.float32), np.asarray(inst.eta, np.float32),
    )


def _assert_cells_parity(got, exp, what: str):
    for name, g, e in zip(("take", "start"), got, exp):
        g, e = np.asarray(g), np.asarray(e)
        if not np.array_equal(g, e):
            raise SystemExit(
                f"scheduler bench: hier cell mismatch on {what} ({name}: "
                f"{int((g != e).sum())} cells differ) — refusing to "
                "benchmark an allocator that is not bit-identical"
            )


def run(repeats: int = 3) -> dict:
    print("impl,batch,instances_per_s,us_per_call")
    inst = generate_instance(0, CFG)
    env = _env_meta()
    # interpret-mode (CPU) Pallas rows are parity evidence, not perf claims;
    # only the compiled Mosaic path enters the perf gates
    pallas_gated = not env["pallas_interpret"]
    rows = []

    def add(impl, batch, per_call_s, gated):
        rows.append(
            {
                "impl": impl,
                "batch": batch,
                "instances_per_s": round(batch / per_call_s, 1),
                "us_per_call": round(per_call_s / batch * 1e6, 1),
                "gated": gated,
            }
        )
        print(csv_row(impl, batch, f"{batch / per_call_s:.1f}",
                      f"{per_call_s / batch * 1e6:.0f}"))

    add("numpy", 1, _time(lambda i: gus_schedule_np(i), inst, reps=1), gated=False)
    add("jax-jit", 1, _time(gus_schedule, inst, reps=repeats), gated=True)

    pallas1 = lambda i: gus_schedule(i, backend="pallas")  # noqa: E731
    _assert_bit_parity(pallas1(inst), gus_schedule(inst), "batch-1 instance")
    add("pallas", 1, _time(pallas1, inst, reps=repeats), gated=pallas_gated)

    pallas_b = lambda b: gus_schedule_batch(b, backend="pallas")  # noqa: E731
    for bs in (16, 64):
        batch = generate_batch(0, bs, CFG)
        add("jax-vmap", bs, _time(gus_schedule_batch, batch, reps=repeats),
            gated=True)
        _assert_bit_parity(
            pallas_b(batch), gus_schedule_batch(batch), f"batch-{bs} grid"
        )
        add("pallas", bs, _time(pallas_b, batch, reps=repeats),
            gated=pallas_gated)

    # hierarchical analytic allocator (class-aggregate fleet path): same
    # three-implementation story, parity asserted before any row is timed
    hargs = _hier_class_args(generate_instance(0, CFG, as_numpy=True))
    ref = hier_cells_np(*hargs)
    xla_fn, pal_fn = hier_backend_fn("xla"), hier_backend_fn("pallas")
    _assert_cells_parity(xla_fn(*hargs), ref, "hier frame (xla)")
    _assert_cells_parity(pal_fn(*hargs), ref, "hier frame (pallas)")
    add("hier-np", 1, _time(hier_cells_np, *hargs, reps=1), gated=False)
    add("hier-xla", 1, _time(xla_fn, *hargs, reps=repeats), gated=True)
    add("hier-pallas", 1, _time(pal_fn, *hargs, reps=repeats),
        gated=pallas_gated)

    # batched hier rows: vmap over a replication axis, the fleet's layout
    bs = 16
    hbatch = [np.broadcast_to(a, (bs,) + a.shape).copy() for a in hargs]
    vx = jax.jit(jax.vmap(xla_fn))
    _assert_cells_parity(
        jax.tree.map(lambda x: np.asarray(x)[0], tuple(vx(*hbatch))), ref,
        f"hier batch-{bs} (xla)")
    _assert_cells_parity(
        jax.tree.map(lambda x: np.asarray(x)[0],
                     tuple(hier_cells_pallas(*hbatch))), ref,
        f"hier batch-{bs} (pallas)")
    add("hier-xla", bs, _time(vx, *hbatch, reps=repeats), gated=True)
    add("hier-pallas", bs, _time(hier_cells_pallas, *hbatch, reps=repeats),
        gated=pallas_gated)

    return {
        "meta": {
            "bench": "scheduler_throughput",
            "n_requests": CFG.n_requests,
            "repeats": repeats,
            **env,
        },
        "rows": rows,
    }


def _row(report: dict, impl: str, batch: int):
    return next(
        (r for r in report["rows"] if r["impl"] == impl and r["batch"] == batch),
        None,
    )


def gate_pallas_vs_xla(report: dict, slack: float = 0.10):
    """Accelerator-only speed gate: the compiled Pallas kernel must be no
    slower than the jitted XLA path at the batch-64 bench point (``slack``
    absorbs timer noise).  Interpret-mode (CPU) runs skip this — there the
    Pallas rows gate parity, not speed."""
    if report["meta"].get("pallas_interpret", True):
        print("pallas-vs-xla speed gate skipped (interpret mode: parity-only)")
        return
    xla = _row(report, "jax-vmap", 64)
    pal = _row(report, "pallas", 64)
    if xla is None or pal is None:
        raise SystemExit("scheduler bench: missing batch-64 row for the "
                         "pallas-vs-xla gate")
    if pal["instances_per_s"] < xla["instances_per_s"] * (1.0 - slack):
        raise SystemExit(
            f"scheduler perf gate: pallas batch-64 {pal['instances_per_s']} "
            f"inst/s is slower than xla {xla['instances_per_s']} inst/s "
            f"(allowed slack {slack:.0%})"
        )
    print(f"pallas-vs-xla speed gate OK ({pal['instances_per_s']} vs "
          f"{xla['instances_per_s']} inst/s at batch 64)")


def compare_against_baseline(report: dict, baseline_path: str, tolerance: float):
    """Fail (SystemExit) when a gated row's throughput regresses by more than
    ``tolerance``; rows match on (impl, batch), unmatched rows are skipped."""
    baseline = json.loads(Path(baseline_path).read_text())
    gate_rows_against_baseline(
        [r for r in report["rows"] if r["gated"]],
        baseline.get("rows", []),
        key_fn=lambda r: (r["impl"], r["batch"]),
        metric="instances_per_s",
        tolerance=tolerance,
        baseline_path=baseline_path,
        unit=" inst/s",
        gate_name="scheduler perf gate",
    )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results/scheduler_throughput")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--compare", metavar="BASELINE_JSON",
                    help="perf-regression gate against a checked-in baseline")
    ap.add_argument("--tolerance", type=float, default=0.50,
                    help="allowed fractional throughput drop for --compare "
                         "(wide by default: jit timings on shared runners are noisy)")
    ap.add_argument("--update-baseline", metavar="PATH",
                    help="also write the report to PATH (refresh the baseline)")
    args = ap.parse_args(argv)

    report = run(repeats=args.repeats)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "BENCH_scheduler.json"
    path.write_text(json.dumps(report, indent=2))
    print(f"wrote {path}")

    if args.update_baseline:
        Path(args.update_baseline).parent.mkdir(parents=True, exist_ok=True)
        Path(args.update_baseline).write_text(json.dumps(report, indent=2))
        print(f"baseline refreshed at {args.update_baseline}")
    if args.compare:
        gate_pallas_vs_xla(report)
        compare_against_baseline(report, args.compare, args.tolerance)
    return True


if __name__ == "__main__":
    main()
