"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name: ``BENCHMARK.json`` at the checkout root names them, the
configuration's ``file`` holds the deployment, ``bench/traffic/<traffic>.json``
the traffic mix and the driver that runs it (``bench/drivers/<driver>.py``),
and each per-layer metric is read by ``bench/layers/<metric>.py``.

A run: refuse anything but a TPU with at least the cell's chips; set up
(build inputs from the seed, warm every shape the cell uses, from the
compilation cache in ``<checkout>/.jax_cache`` after the first run); run
the driver's units back to back for ``--seconds``; read the device's peak
memory; free the program's state; compare a sample of what the window
produced with the plain reference; print each compared number beside its
limit on standard error, and one JSON object as the last line of standard
output.  With ``--trace 1`` the window runs under the profiler and the line
carries the per-layer metrics, ``busy_s``/``window_s`` and ``breakdown``;
with ``--trace 0`` it carries the end-to-end metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.common import load_module, read_json  # noqa: E402


#: groups that state a deployment: a configuration's, never a traffic mix's
DEPLOYMENT_KEYS = ("generator", "cluster_seed", "sim", "scenario", "scenario_params")


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown cell...)."""


class Cell:
    """One ``workloads`` entry with everything it names, found by name."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.manifest = read_json(self.root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = read_json(self.root / configs[self.entry["config"]]["file"])
        self.traffic = read_json(self.root / "bench" / "traffic" / f"{self.entry['traffic']}.json")
        stated = sorted(set(self.traffic) & set(DEPLOYMENT_KEYS))
        if stated:
            raise BenchError(f"traffic {self.entry['traffic']!r} states the deployment "
                             f"({', '.join(stated)}); that belongs to its configuration")
        self.chips = int(self.entry["chips"])

    def _applies(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._applies(m)]

    def per_layer(self):
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m else m["moves"] in reported)]

    def driver(self, seed: int):
        mod = load_module(self.root / "bench" / "drivers" / f"{self.traffic['driver']}.py")
        return mod.Driver(self.config, self.traffic, seed, self.chips, self.root)

    def layer_reader(self, metric: str):
        return load_module(self.root / "bench" / "layers" / f"{metric}.py").read

    def limits(self) -> dict:
        return self.traffic["limits"]


def devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices; not running")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts backend compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw):
        if not self.armed:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_loads += 1


def _start_trace(log_dir: str):
    """Start the profiler with the Python tracer off (it would record every
    Python call of the host layers), and switch on the program's own
    profiler annotations, which ``repro.obs.profiler`` emits only while
    its ``_ACTIVE`` flag is set (its ``profile_trace`` takes no profiler
    options).  A program without that flag is refused: setting it would
    succeed silently and every program annotation would be missing."""
    import jax
    from repro.obs import profiler

    if not isinstance(getattr(profiler, "_ACTIVE", None), bool):
        raise BenchError("repro.obs.profiler has no boolean _ACTIVE flag: the program's "
                         "annotations cannot be switched on for the trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    profiler._ACTIVE = True


def _stop_trace():
    import jax
    from repro.obs import profiler

    profiler._ACTIVE = False
    jax.profiler.stop_trace()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, log=print, t_start: float = None) -> dict:
    """One run of ``cell``: the result object and the compared numbers.
    ``setup_s`` runs from ``t_start`` (the process's start by default) to
    the window's start."""
    t_start = T_START if t_start is None else t_start
    import jax
    import numpy as np

    devs = devices(cell.chips, require_tpu)
    src = str(cell.root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    driver = cell.driver(seed)
    driver.setup()
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    if trace:  # the trace goes under $TMPDIR and is deleted once reduced
        _start_trace(tmp.name)
    records = []
    counter.armed = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation(driver.unit):
                records.append(driver.step(len(records)))
    t1 = time.perf_counter()
    counter.armed = False
    setup_s = t0 - t_start
    if trace:
        _stop_trace()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    result = dict(correct=False, attempted=len(records), failed=0, metrics={},
                  device=dict(platform=devs[0].platform, kind=devs[0].device_kind,
                              count=len(devs), memory_peak_bytes=peak))
    if trace:
        from bench.trace_reduce import reduce_trace

        pb = sorted(Path(tmp.name).rglob("*.xplane.pb"))
        red = reduce_trace(pb[-1], n_devices=cell.chips)
        tmp.cleanup()
        ctx = dict(driver.layer_context(records), trace=red, kind=devs[0].device_kind)
        for m in cell.per_layer():
            v = cell.layer_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = dict(value=float(v), unit=m["unit"])
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    else:
        values = dict(driver.end_to_end(records, t0, t1), setup_s=setup_s)
        for m in cell.end_to_end():
            result["metrics"][m["name"]] = dict(value=float(values[m["name"]]), unit=m["unit"])
    log(f"window: {len(records)} units in {t1 - t0:.3f} s; set-up {setup_s:.3f} s; "
        f"compiles in window {counter.compiles}, cache loads {counter.cache_loads}; "
        f"resolved {json.dumps(driver.resolved())}")
    result["compiles_in_window"] = counter.compiles
    driver.release()
    del devs, stats
    limits = cell.limits()
    t_check = time.perf_counter()
    got = driver.check(records, np.random.default_rng(seed))
    log(f"reference comparison took {time.perf_counter() - t_check:.3f} s")
    result["failed"] = sum(got[k] > limits[k] for k in got)
    result["correct"] = result["failed"] == 0 and set(got) == set(limits)
    result["checks"] = {k: dict(value=got[k], limit=limits[k]) for k in sorted(got)}
    return result


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(root, args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          require_tpu=require_tpu,
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"correct: {result['correct']}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
