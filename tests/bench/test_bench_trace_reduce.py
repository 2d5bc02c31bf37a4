"""The reduction from a profiler trace to device busy/idle, per-program
device time and ``breakdown``."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.trace_reduce import program_seconds, reduce_trace

FIXTURE = Path(__file__).with_name("fixtures") / "online.xplane.pb"


def _xspace(host_events, device_ops, modules):
    """A text-proto XSpace: host annotations on one thread, one TPU with
    its ops and module executions; times in microseconds."""
    from jax.profiler import ProfileData

    names = {}

    def mid(name):
        return names.setdefault(name, len(names) + 1)

    def events(evs, stat=None):
        out = []
        for name, start, dur, *mod in evs:
            st = (f' stats {{ metadata_id: 1 str_value: "{mod[0]}" }}' if mod else "")
            out.append(f"events {{ metadata_id: {mid(name)} offset_ps: {int(start * 1e6)} "
                       f"duration_ps: {int(dur * 1e6)}{st} }}")
        return "\n".join(out)

    host = events(host_events)
    host_meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                          for n, i in names.items())
    names.clear()
    ops, mods = events(device_ops), events(modules)
    dev_meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                         for n, i in names.items())
    txt = f"""
    planes {{ id: 1 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
      {host_meta} }}
    planes {{ id: 2 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }}
      {dev_meta}
      stat_metadata {{ key: 1 value {{ id: 1 name: "hlo_module" }} }} }}
    """
    return ProfileData.from_text_proto(txt)


def test_busy_is_the_union_of_ops_inside_the_window():
    pd = _xspace(
        host_events=[("bench/window", 100, 1000), ("bench/frame", 150, 300),
                     ("gus/xla", 600, 200)],
        # overlapping ops count once; the op before the window is cut off
        device_ops=[("fusion.1", 50, 100, "jit_f"), ("fusion.2", 300, 100, "jit_f"),
                    ("fusion.3", 350, 100, "jit_f"), ("while.1", 650, 100, "jit_g")],
        modules=[("jit_f(3)", 50, 400), ("jit_g(4)", 650, 100)],
    )
    r = reduce_trace(pd)
    assert r["window_s"] == pytest.approx(1000e-6)
    # [100, 150] + [300, 450] + [650, 750] = 50 + 150 + 100 us
    assert r["busy_s"] == pytest.approx(300e-6)
    assert r["programs"] == pytest.approx({"jit_f": 350e-6, "jit_g": 100e-6})
    assert program_seconds(r, r"^jit_f$") == pytest.approx(350e-6)
    assert program_seconds(r, r"^nothing$") is None
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["jit_f:fusion.2"] == pytest.approx(100e-6)
    assert ops["jit_f:fusion.1"] == pytest.approx(50e-6)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # gaps [150, 300] (inside bench/frame), [450, 650] (mid 550: window only),
    # [750, 1100] (mid 925: window only)
    assert gaps["bench/frame"] == pytest.approx(150e-6)
    assert gaps["bench/window"] == pytest.approx(550e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_used_chip_without_ops_counts_as_idle():
    pd = _xspace([("bench/window", 0, 100)], [("fusion.1", 0, 100, "jit_f")],
                 [("jit_f(1)", 0, 100)])
    assert reduce_trace(pd)["busy_s"] == pytest.approx(100e-6)
    assert reduce_trace(pd, n_devices=4)["busy_s"] == pytest.approx(25e-6)


def test_trace_without_window_is_refused():
    pd = _xspace([("bench/frame", 0, 10)], [], [])
    with pytest.raises(ValueError):
        reduce_trace(pd)


def test_recorded_chip_trace():
    """Two ``paper.online`` frames recorded on a TPU v5e (the window cut at
    the end of the second frame): the numbers the reduction gives are
    pinned, and they hold together."""
    r = reduce_trace(FIXTURE, n_devices=1)
    assert r["window_s"] == pytest.approx(0.036262739, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.002459179, rel=1e-9)
    assert set(r["programs"]) == {"jit__gus_schedule_xla", "jit_concatenate",
                                  "jit_broadcast_in_dim", "jit_convert_element_type"}
    assert program_seconds(r, r"_gus_schedule_(xla|pallas)$") == pytest.approx(
        0.002439147, rel=1e-9)
    top, seconds = r["breakdown"]["device_ops"][0]
    assert top == "jit__gus_schedule_xla:while.17"
    assert seconds <= r["busy_s"]
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert len(r["breakdown"]["device_ops"]) <= 10
