"""Bring-up check: the scheduling path on a TPU, at the sizes users run.

Runs in one process, through the library's own entry points, and compares
every result with the repository's references:

1. device check — refuses to run anywhere but a TPU;
2. dense GUS at the paper's numerical size (64 frames of
   ``GeneratorConfig()``: N = 100 padded to 128, M = 10, L = 10), XLA and
   Pallas, each frame against ``gus_schedule_np``;
3. the paper-default Monte-Carlo fleet at 64 replications (the
   ``benchmarks/fleet_scale.py`` point), XLA against Pallas, plus one
   ``simulate`` per backend against one scheduled by ``gus_schedule_np``;
4. the hierarchical allocator's chunk count on 4096 single-class frames
   whose budget is an exact multiple of the cost (the chip's f32 divide
   is not correctly rounded), XLA and Pallas against ``hier_cells_np``;
5. ``mega-city`` under the hierarchical scheduler (>= 10^5 users per frame,
   admission and impairments on, ``window=1``, 3 frames), XLA against
   Pallas, and one captured class grid through ``hier_cells`` on each
   backend against ``hier_cells_np``.

``--chips 4`` runs only the path across chips instead: both fleets at
``devices=4`` against ``devices=1``.

Each phase prints one line with its timings and mismatch counts; a Pallas
phase also says whether its lowered programs call the compiled kernel
(``tpu_custom_call``).  Any mismatch, exception or missing kernel exits
nonzero.  On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Run from the checkout root:  ``python chip_smoke.py [--chips 4]``
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import tempfile
import time
import traceback
from importlib import metadata
from pathlib import Path

import jax
import numpy as np

SRC = Path(__file__).resolve().parent / "src"


def _version(pkg: str) -> str:
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


def _device_check(n_chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    print(
        f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={_version('jaxlib')} libtpu={_version('libtpu')}",
        flush=True,
    )
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {d0.platform!r}); not running")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} chips, "
                 f"JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


@contextlib.contextmanager
def _lowered_programs():
    """Collect the StableHLO text of every program lowered in the block."""
    texts: list = []
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield texts
        finally:
            jax.config.update("jax_dump_ir_to", "")
            texts.extend(p.read_text() for p in sorted(Path(d).glob("*.mlir")))


class Smoke:
    """Phase runner: one report line per phase, failures collected."""

    def __init__(self):
        self.failures: list = []

    def phase(self, name: str, fn, *, kernel: bool = False):
        t0 = time.perf_counter()
        try:
            if kernel:
                with _lowered_programs() as texts:
                    info = fn()
                info["tpu_custom_call"] = any("tpu_custom_call" in t for t in texts)
                if not info["tpu_custom_call"]:
                    self.failures.append(f"{name}: no tpu_custom_call lowered")
            else:
                info = fn()
        except Exception:
            traceback.print_exc()
            self.failures.append(f"{name}: exception")
            print(f"phase {name}: FAILED (exception)", flush=True)
            return None
        info["phase_s"] = time.perf_counter() - t0
        bad = info.get("mismatches", 0)
        if bad:
            self.failures.append(f"{name}: {bad} mismatches")
        shown = {k: v for k, v in info.items() if not k.startswith("_")}
        print(f"phase {name}: " + json.dumps(shown, default=float), flush=True)
        return info


def _timed_twice(fn):
    """Call ``fn`` twice: ``(first result, second result, timings)``.  The
    first call compiles; the second is the wall time of a warm call."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out2 = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return out, out2, {"compile_s": max((t1 - t0) - (t2 - t1), 0.0),
                       "wall_s": t2 - t1}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _paper_frames(n=64, n_pad=128, seed=0):
    from repro.core import (GeneratorConfig, generate_instance, pad_instance,
                            stack_instances)

    insts = [pad_instance(generate_instance(seed + i, GeneratorConfig(),
                                            as_numpy=True), n_pad)
             for i in range(n)]
    return insts, stack_instances(insts)


def phase_dense_gus(insts, batch, backend: str) -> dict:
    from repro.core import gus_schedule_batch, gus_schedule_np

    out, _, timing = _timed_twice(
        lambda: gus_schedule_batch(batch, backend=backend))
    j, l = np.asarray(out.j), np.asarray(out.l)
    bad = 0
    for b, inst in enumerate(insts):
        want = gus_schedule_np(inst)
        bad += int(not (np.array_equal(j[b], np.asarray(want.j))
                        and np.array_equal(l[b], np.asarray(want.l))))
    return {"frames": len(insts), "served": int((j >= 0).sum()),
            "mismatches": bad, **timing}


def _paper_fleet_setup():
    from repro.core import SimConfig, demo_cluster_spec

    # benchmarks/fleet_scale.py: bench_spec() and bench_cfg(tiny=False)
    spec = demo_cluster_spec(n_edge=9, n_cloud=1, n_services=5, n_variants=10)
    cfg = SimConfig(horizon_ms=30_000.0, arrival_rate_per_s=6.0,
                    delay_req_ms=6000.0, acc_req_mean=50.0, acc_req_std=10.0)
    return spec, cfg


def _city_setup():
    from repro.core import SimConfig, demo_cluster_spec
    from repro.core.impairments import (AdmissionConfig, BurstyLossLink,
                                        ImpairmentConfig, IntermittentLink)

    # the mega-city smoke of .github/workflows/ci.yml
    spec = demo_cluster_spec(n_edge=20, n_cloud=1, n_services=5, n_variants=10)
    cfg = SimConfig(
        horizon_ms=9_000.0,
        admission=AdmissionConfig(enabled=True, shed=True),
        impairments=ImpairmentConfig(
            enabled=True, link_profiles=(IntermittentLink(), BurstyLossLink()),
            seed=7,
        ),
    )
    return spec, cfg


def _fleet_view(fr) -> dict:
    """Everything a fleet run reports, devices excluded."""
    d = {k: v for k, v in fr.as_dict().items() if k != "n_devices"}
    d["n_served"] = fr.n_served
    d["satisfied_per_rep"] = np.asarray(fr.satisfied_per_rep).tolist()
    d["mean_us_per_rep"] = np.asarray(fr.mean_us_per_rep).tolist()
    return d


def _diff(a: dict, b: dict) -> int:
    """Number of differing entries (list entries count one by one)."""
    bad = 0
    for k in sorted(set(a) | set(b)):
        x, y = a.get(k), b.get(k)
        if isinstance(x, list) and isinstance(y, list) and len(x) == len(y):
            bad += sum(p != q for p, q in zip(x, y))
        else:
            bad += int(x != y)
    return bad


def _run_fleet(spec, cfg, scenario, n_rep, **opts):
    from repro.core import EngineOptions, simulate_fleet

    return simulate_fleet(spec, cfg, policy="gus", scenario=scenario,
                          n_rep=n_rep, seed=0, options=EngineOptions(**opts))


def phase_fleet(setup, scenario, n_rep, ref=None, **opts) -> dict:
    spec, cfg = setup()
    fr, fr2, timing = _timed_twice(
        lambda: _run_fleet(spec, cfg, scenario, n_rep, **opts))
    view = _fleet_view(fr)
    info = {"n_requests": fr.n_requests, "frames": fr.n_frames,
            "users_per_frame": fr.n_requests / (fr.n_frames * n_rep),
            "satisfied_pct": fr.satisfied_pct,
            "mismatches": _diff(view, _fleet_view(fr2)),  # run to run
            "_view": view, **timing}
    if ref is not None:
        info["mismatches"] += _diff(view, ref["_view"])
    return info


def phase_simulate(backend: str) -> dict:
    from repro.core import EngineOptions, gus_schedule_np, simulate

    spec, cfg = _paper_fleet_setup()
    opts = EngineOptions(backend=backend)
    got, _, timing = _timed_twice(
        lambda: simulate(spec, cfg, scenario="paper-default", seed=0,
                         options=opts))
    want = simulate(spec, cfg, gus_schedule_np, scenario="paper-default", seed=0)
    a, b = got.as_dict(), want.as_dict()
    return {"n_requests": got.n_requests, "mismatches": _diff(a, b),
            **timing}


@contextlib.contextmanager
def _capture_class_grid():
    """Record the first window handed to the hierarchical fleet runner and
    rebuild its first frame's allocator inputs exactly as the runner's step
    forms them (congestion off: budgets unchanged; shed mask precomputed)."""
    from repro.core import simulator

    real = simulator._hier_runner_impl
    seen: dict = {}

    def impl(cells_fn, ccfg, acfg, keep_pre=False):
        run = real(cells_fn, ccfg, acfg, keep_pre)

        def wrapped(carry, inst, us, feas, tq, cnt):
            if "grid" not in seen and keep_pre and not ccfg.enabled:
                f = np.asarray(feas[0, 0]) & np.asarray(tq[0, 0])[:, None, None]
                seen["grid"] = tuple(np.asarray(x) for x in (
                    us[0, 0], f, inst.v[0, 0], inst.u[0, 0], inst.cover[0, 0],
                    cnt[0, 0], inst.gamma[0, 0], inst.eta[0, 0]))
            return run(carry, inst, us, feas, tq, cnt)

        return wrapped

    simulator._hier_runner_impl = impl
    try:
        yield seen
    finally:
        simulator._hier_runner_impl = real


def phase_class_grid(grid, backend: str) -> dict:
    from repro.core import hier_cells, hier_cells_np

    out, _, timing = _timed_twice(
        lambda: hier_cells(*grid, backend=backend))
    take, start = (np.asarray(x) for x in out)
    want_take, want_start = hier_cells_np(*grid)
    return {"classes": int((grid[5] > 0).sum()), "padded_classes": grid[0].shape[0],
            "members_placed": int(take.sum()),
            "mismatches": int((take != want_take).sum()
                              + (start != want_start).sum()),
            **timing}


def _exact_multiple_frames(n=4096, seed=0):
    """Single-class frames whose budget is an exact f32 multiple of the
    cell's cost, so the allocator's chunk is its fit count — where a bare
    ``floor(budget / cost)`` on the chip lands one below NumPy's."""
    rng = np.random.default_rng(seed)
    cost = rng.uniform(0.5, 3000.0, n).astype(np.float32)
    budget = rng.integers(1, 64, n).astype(np.float32) * cost
    ones = np.ones((n, 1, 1, 1), np.float32)
    return (ones, ones.astype(bool), cost.reshape(ones.shape),
            np.zeros_like(ones), np.zeros((n, 1), np.int32),
            np.full((n, 1), 100, np.int32), budget.reshape(n, 1),
            np.zeros((n, 1), np.float32))


def phase_fit_count(frames, backend: str) -> dict:
    from repro.core import hier_cells, hier_cells_np

    fn = jax.jit(jax.vmap(functools.partial(hier_cells, backend=backend)))
    out, _, timing = _timed_twice(lambda: fn(*frames))
    take = np.asarray(out[0])
    want = np.stack([hier_cells_np(*(x[b] for x in frames))[0]
                     for b in range(take.shape[0])])
    return {"frames": take.shape[0], "members_placed": int(take.sum()),
            "mismatches": int((take != want).sum()), **timing}


def one_chip(smoke: Smoke):
    insts, batch = _paper_frames()
    for backend in ("xla", "pallas"):
        smoke.phase(f"dense_gus/{backend}",
                    lambda: phase_dense_gus(insts, batch, backend),
                    kernel=backend == "pallas")
    ref = smoke.phase("paper_fleet/xla", lambda: phase_fleet(
        _paper_fleet_setup, "paper-default", 64, backend="xla"))
    smoke.phase("paper_fleet/pallas", lambda: phase_fleet(
        _paper_fleet_setup, "paper-default", 64, ref=ref, backend="pallas"),
        kernel=True)
    for backend in ("xla", "pallas"):
        smoke.phase(f"simulate_vs_numpy/{backend}",
                    lambda: phase_simulate(backend), kernel=backend == "pallas")
    frames = _exact_multiple_frames()
    for backend in ("xla", "pallas"):
        smoke.phase(f"fit_count/{backend}",
                    lambda: phase_fit_count(frames, backend),
                    kernel=backend == "pallas")
    city = dict(scheduler="hierarchical", window=1)
    with _capture_class_grid() as seen:
        ref = smoke.phase("mega_city/xla", lambda: phase_fleet(
            _city_setup, "mega-city", 1, backend="xla", **city))
    smoke.phase("mega_city/pallas", lambda: phase_fleet(
        _city_setup, "mega-city", 1, ref=ref, backend="pallas", **city),
        kernel=True)
    if "grid" not in seen:
        smoke.failures.append("mega_city: no class grid captured")
        return
    for backend in ("xla", "pallas"):
        smoke.phase(f"class_grid/{backend}",
                    lambda: phase_class_grid(seen["grid"], backend),
                    kernel=backend == "pallas")


def four_chips(smoke: Smoke):
    ref = smoke.phase("paper_fleet/devices=1", lambda: phase_fleet(
        _paper_fleet_setup, "paper-default", 64, devices=1))
    smoke.phase("paper_fleet/devices=4", lambda: phase_fleet(
        _paper_fleet_setup, "paper-default", 64, ref=ref, devices=4))
    city = dict(scheduler="hierarchical", window=1)
    ref = smoke.phase("mega_city/devices=1", lambda: phase_fleet(
        _city_setup, "mega-city", 1, devices=1, **city))
    smoke.phase("mega_city/devices=4", lambda: phase_fleet(
        _city_setup, "mega-city", 1, ref=ref, devices=4, **city))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip (default); 4: only "
                         "the fleets across four chips against one")
    args = ap.parse_args(argv)
    device = _device_check(args.chips)

    sys.path.insert(0, str(SRC))
    from repro.compile_cache import use_compile_cache
    from repro.kernels.gus_pallas import pallas_interpret

    print(f"compile cache: {use_compile_cache()}", flush=True)
    if pallas_interpret():
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")

    smoke = Smoke()
    (four_chips if args.chips == 4 else one_chip)(smoke)
    if smoke.failures:
        print("chip_smoke FAILED: " + "; ".join(smoke.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
