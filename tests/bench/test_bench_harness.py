"""The harness is driven by data, its manifest keeps to the contract, and
it refuses to measure anywhere but on a TPU."""
from __future__ import annotations

import json
import re

from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def _manifest():
    from conftest import ROOT

    return ROOT, json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_names_units_and_files():
    root, m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])
    assert 1 <= m["run_seconds"] <= 51
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics + m["workloads"] + m["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in m["workloads"]] + [w["traffic"] for w in m["workloads"]]:
        assert NAME.match(n), n
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert (root / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.0 < e["bound"] <= 0.25
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (root / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(m["workloads"]) // 2)


def test_every_cell_reports_what_its_layer_metrics_move():
    root, m = _manifest()
    for w in m["workloads"]:
        cell = run.Cell(root, w["name"])
        e2e = {x["name"] for x in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = cell.per_layer()
        assert layers, w["name"]
        for x in layers:
            assert x["moves"] in e2e, (w["name"], x["name"])
            assert (root / "bench" / "layers" / f"{x['name']}.py").is_file()
    layer_names = {}
    for x in m["per_layer"]:
        assert x["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "\n" not in x["layer"]
        for c in x["workloads"]:
            assert c in {w["name"] for w in m["workloads"]}
        layer_names.setdefault(x["layer"].lower(), set()).add(x["layer"])
    assert all(len(v) == 1 for v in layer_names.values())


def test_new_configuration_traffic_and_metric_are_found_by_name(tiny_root):
    """A later change adds files and manifest entries only: a deployment, a
    traffic mix and a layer metric placed beside the others run with no
    edit to any existing file."""
    cfg = json.loads((tiny_root / "bench/configs/paper-numerical.json").read_text())
    cfg["name"] = "paper-small"
    cfg["generator"]["n_requests"] = 40
    (tiny_root / "bench/configs/paper-small.json").write_text(json.dumps(cfg))
    traffic = json.loads((tiny_root / "bench/traffic/paper.online.json").read_text())
    traffic.update(pool=3, warmup=1, n_pad=64)
    (tiny_root / "bench/traffic/small.online.json").write_text(json.dumps(traffic))
    (tiny_root / "bench/layers/decisions_per_unit.py").write_text(
        "def read(ctx):\n    return float(ctx['frames'])\n")
    man = json.loads((tiny_root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(name="paper-small", source="https://arxiv.org/abs/2011.08381",
                               file="bench/configs/paper-small.json", reduced=["n_requests"],
                               why="test"))
    man["workloads"].append(dict(name="small.online", config="paper-small",
                                 traffic="small.online", chips=1, why="test"))
    for e in man["end_to_end"]:
        if e["name"].startswith("decision_"):
            e["workloads"].append("small.online")
    man["per_layer"].append(dict(name="decisions_per_unit", unit="frames", better="higher",
                                 source="program_counter", layer="test",
                                 moves="decision_p50_ms", workloads=["small.online"]))
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = run.Cell(tiny_root, "small.online")
    res = run.run_cell(cell, 2**33 + 5, 0.3, False, require_tpu=False, log=lambda s: None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"decision_p50_ms", "decision_p95_ms", "setup_s"}
    res = run.run_cell(cell, 2**33 + 6, 0.3, True, require_tpu=False, log=lambda s: None)
    assert res["metrics"]["decisions_per_unit"]["value"] == res["attempted"] > 0
    assert list(res)[-1] == "checks"


def test_refuses_to_run_without_a_tpu(capsys):
    rc = run.main(["--workload", "paper.online", "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_refuses_an_unknown_cell(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_a_traffic_mix_cannot_restate_the_deployment(tiny_root, capsys):
    """Load belongs to the traffic mix, the deployment to its configuration:
    a mix that carries a configuration's group is refused before any run."""
    path = tiny_root / "bench/traffic/paper.fleet.json"
    traffic = json.loads(path.read_text())
    traffic["sim"] = {"acc_req_mean": 50.0}
    path.write_text(json.dumps(traffic))
    try:
        run.Cell(tiny_root, "paper.fleet")
    except run.BenchError as e:
        assert "sim" in str(e)
    else:
        raise AssertionError("a traffic mix restated the deployment")
    assert run.main(["--workload", "paper.fleet", "--seed", "1", "--seconds", "1"],
                    root=tiny_root, require_tpu=False) != 0
    assert capsys.readouterr().out.strip() == ""


def test_trace_refuses_a_program_without_its_annotation_flag(monkeypatch, tmp_path):
    from repro.obs import profiler

    monkeypatch.delattr(profiler, "_ACTIVE")
    try:
        run._start_trace(str(tmp_path))
    except run.BenchError as e:
        assert "_ACTIVE" in str(e)
    else:
        raise AssertionError("the trace started without the program's annotations")


def test_setup_runs_from_the_start_it_is_given(tiny_root):
    """A second run in one process reports its own set-up, not the time
    since the process began."""
    import time

    cell = run.Cell(tiny_root, "paper.online")
    t = time.perf_counter()
    res = run.run_cell(cell, 2**32 + 3, 0.3, False, require_tpu=False, log=lambda s: None,
                       t_start=t)
    assert 0.0 < res["metrics"]["setup_s"]["value"] < time.perf_counter() - t
    old = run.run_cell(cell, 2**32 + 3, 0.3, False, require_tpu=False, log=lambda s: None,
                       t_start=t - 1e3)
    assert old["metrics"]["setup_s"]["value"] > 1e3
