"""The per-layer metrics that read the program's spans and counters:
idle under named spans from ``reduce_trace``'s ``idle_gaps``, bytes and
padded rows from ``FleetResult.timings`` counters."""
from __future__ import annotations

import pytest

from bench import run, spans
from bench.trace_reduce import reduce_trace
from test_bench_trace_reduce import _xspace


def _reader(name):
    """``bench/layers/<name>.py``'s reader, found by name as the harness does."""
    from conftest import ROOT

    return run.Cell(ROOT, "paper.fleet").layer_reader(name)


def _fleet_trace():
    """One study on the host: arrivals, then the group's put, the device
    scan and its drain, then accounting; times in microseconds."""
    pd = _xspace(
        host_events=[("bench/window", 0, 1000), ("bench/study", 1, 999),
                     ("fleet/arrivals", 50, 250), ("fleet/dispatch", 300, 400),
                     ("fleet/put", 300, 50), ("fleet/drain", 360, 340),
                     ("fleet/window_metrics", 700, 200)],
        device_ops=[("while.1", 360, 300, "jit_per_rep"),
                    ("fusion", 750, 50, "jit_take_along_axis")],
        modules=[("jit_per_rep(1)", 360, 300), ("jit_take_along_axis(2)", 750, 50)],
    )
    return reduce_trace(pd)


def test_span_idle_per_kuser_from_a_trace():
    red = _fleet_trace()
    gaps = dict(red["breakdown"]["idle_gaps"])
    # gaps: [0, 360] mid 180 (arrivals), [660, 750] mid 705 (window_metrics),
    # [800, 1000] mid 900 (bench/study)
    assert gaps == pytest.approx({"fleet/arrivals": 360e-6, "fleet/window_metrics": 90e-6,
                                  "bench/study": 200e-6})
    ctx = dict(trace=red, users=2000)
    assert _reader("arrivals_idle_ms_per_kuser")(ctx) == pytest.approx(1e3 * 360e-6 / 2)
    # put and drain own no gap in a short list: they read 0, not None
    assert _reader("transfer_idle_ms_per_kuser")(ctx) == 0.0
    assert _reader("build_idle_ms_per_kuser")(ctx) == 0.0


def test_span_idle_per_frame_from_a_trace():
    pd = _xspace(
        host_events=[("bench/window", 0, 1000), ("bench/frame", 1, 499),
                     ("gus/pad", 10, 300), ("gus/call", 320, 60),
                     ("bench/frame", 500, 500), ("gus/pad", 510, 300)],
        device_ops=[("while.17", 400, 100, "jit__gus_schedule_xla"),
                    ("fusion", 900, 100, "jit__gus_schedule_xla")],
        modules=[("jit__gus_schedule_xla(1)", 400, 100), ("jit__gus_schedule_xla(2)", 900, 100)],
    )
    ctx = dict(trace=reduce_trace(pd), frames=2)
    # [0, 400] mid 200 -> gus/pad; [500, 900] mid 700 -> gus/pad
    assert _reader("pad_idle_ms_per_frame")(ctx) == pytest.approx(1e3 * 800e-6 / 2)
    assert _reader("call_idle_ms_per_frame")(ctx) == 0.0


#: each idle reader: the spans it reads and its cell's program spans
SPAN_READERS = {
    "pad_idle_ms_per_frame": (("gus/pad",), spans.ONLINE_SPANS),
    "call_idle_ms_per_frame": (("gus/call",), spans.ONLINE_SPANS),
    "arrivals_idle_ms_per_kuser": (("fleet/generate_traces", "fleet/arrivals"),
                                   spans.FLEET_SPANS),
    "build_idle_ms_per_kuser": (("fleet/grid_build",), spans.FLEET_SPANS),
    "transfer_idle_ms_per_kuser": (("fleet/put", "fleet/drain"), spans.FLEET_SPANS),
}


def _one_gap_each(names):
    """A trace in which each of ``names`` owns one 100 us device gap."""
    host = [("bench/window", 0, 200 * len(names))]
    ops = []
    for i, n in enumerate(names):
        host.append((n, 200 * i + 1, 99))
        ops.append((f"fusion.{i}", 200 * i + 100, 100, "jit_f"))
    return reduce_trace(_xspace(host, ops, [("jit_f(1)", 100, 200 * len(names))]))


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_without_gaps_read_none(name):
    """A trace with no idle gap (a CPU run has no device plane, so no gap
    either) gives no reading."""
    pd = _xspace([("bench/window", 0, 100), ("fleet/put", 0, 100), ("gus/pad", 0, 100)],
                 [("fusion.1", 0, 100, "jit_f")], [("jit_f(1)", 0, 100)])
    red = reduce_trace(pd)
    assert red["breakdown"]["idle_gaps"] == []
    assert _reader(name)(dict(trace=red, users=1000, frames=3)) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_on_a_full_list_without_their_names_read_none(name):
    """Ten names own gaps, the cell's other program spans among them, and
    the read ones are not: they may sit below the cut, so the reading is
    ``None``, not 0."""
    read, program = SPAN_READERS[name]
    others = [n for n in program if n not in read]
    fill = [f"sim/other_{chr(97 + i)}" for i in range(11 - len(others))]
    red = _one_gap_each(others + fill)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert len(gaps) == 10 and others[0] in gaps
    assert _reader(name)(dict(trace=red, users=1000, frames=3)) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_on_a_trace_without_program_spans_read_none(name):
    """A program whose spans are not annotations leaves gaps to the
    benchmark's units and to its own older annotations only (the names
    below are those of a program before spans annotated): the reading is
    ``None``, never 0."""
    red = _one_gap_each(["bench/study", "fleet/group", "fleet/window",
                         "bench/frame", "gus/xla"])
    assert _reader(name)(dict(trace=red, users=1000, frames=3)) is None


def test_counter_readers():
    t = [{"fleet/h2d_bytes": 3_000_000, "fleet/accounting_h2d_bytes": 1_000_000,
          "fleet/rows": 1280, "fleet/put": 0.1},
         {"fleet/h2d_bytes": 3_000_000, "fleet/accounting_h2d_bytes": 1_000_000,
          "fleet/rows": 1280}]
    ctx = dict(timings=t, users=2000)
    assert _reader("h2d_mb_per_kuser")(ctx) == pytest.approx(8.0 / 2)
    assert _reader("pad_rows_share")(ctx) == pytest.approx(100.0 * (1 - 2000 / 2560))
    # a program without the counters (its timings hold spans only) reads None
    old = dict(timings=[{"fleet/put": 0.1, "total_s": 1.0}], users=2000)
    assert _reader("h2d_mb_per_kuser")(old) is None
    assert _reader("pad_rows_share")(old) is None


def test_traced_cpu_run_reports_the_counters(tiny_root):
    """A traced run of the tiny fleet cell on the CPU: the profiler runs,
    the trace reduces, the counter metrics are read from the program; the
    span metrics find no device gap and are left out."""
    cell = run.Cell(tiny_root, "paper.fleet")
    res = run.run_cell(cell, 2**32 + 5, 0.3, True, require_tpu=False, log=lambda s: None)
    m = res["metrics"]
    assert 0.0 < m["h2d_mb_per_kuser"]["value"] and m["h2d_mb_per_kuser"]["unit"] == "MB/kuser"
    assert 0.0 < m["pad_rows_share"]["value"] < 100.0
    assert not {"arrivals_idle_ms_per_kuser", "transfer_idle_ms_per_kuser"} & set(m)
    assert res["correct"]
