"""What decides ``correct``: the control and the planted faults fail it.

At test size on the CPU: the plain reference one precision lower
(bfloat16) in the program's place fails each cell's limits, and a run
whose timed path is broken underneath comes out not correct, once for
each fault the cell can have: half of the batch left out and an answer
altered where it is produced.  No cell runs across chips, so no exchange
between chips can be left out.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import run

CELLS = ("paper.online", "paper.fleet")


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_reference_agrees_with_itself(tiny_root, name):
    cell = run.Cell(tiny_root, name)
    driver = cell.driver(2**32 + 11)
    limits = cell.limits()
    low = driver.control(np.random.default_rng(3))
    assert any(low[k] > limits[k] for k in limits), (low, limits)
    if hasattr(driver, "reference"):
        s = driver.pool[0]
        want = driver.reference(s)
        same = driver.compare(dict(users=want["requests"], sat=want["sat"], us=want["us"]), want)
        assert all(v == 0.0 for v in same.values())


def _run(tiny_root, name, seed=2**31 + 77):
    return run.run_cell(run.Cell(tiny_root, name), seed, 0.3, False,
                        require_tpu=False, log=lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_root, name):
    res = _run(tiny_root, name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def _broken_fleet(monkeypatch, fault):
    from repro.core import simulator

    real = simulator.simulate_fleet

    def broken(*a, **kw):
        n_rep = kw["n_rep"]
        if fault == "half_batch":  # half the replications run; the rest take their mean
            fr = real(*a, **dict(kw, n_rep=max(n_rep // 2, 1)))
            h = fr.satisfied_per_rep.shape[0]
            fill = n_rep - h
            fr.satisfied_per_rep = np.concatenate(
                [fr.satisfied_per_rep, np.full(fill, fr.satisfied_per_rep.mean())])
            fr.mean_us_per_rep = np.concatenate(
                [fr.mean_us_per_rep, np.full(fill, fr.mean_us_per_rep.mean())])
            fr.n_requests = int(round(fr.n_requests * n_rep / h))
            fr.n_rep = n_rep
            return fr
        fr = real(*a, **kw)  # one replication's answer altered by 1 % of its users
        fr.satisfied_per_rep = fr.satisfied_per_rep.copy()
        fr.satisfied_per_rep[0] += 1.0
        return fr

    monkeypatch.setattr(simulator, "simulate_fleet", broken)


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered", "frame_left_out"])
def test_broken_fleet_is_not_correct(tiny_root, monkeypatch, fault):
    if fault == "frame_left_out":  # the study's last frame is never scheduled
        import dataclasses

        from repro.core import simulator

        real = simulator.simulate_fleet

        def broken(spec, cfg, **kw):
            return real(spec, dataclasses.replace(cfg, horizon_ms=cfg.horizon_ms - cfg.frame_ms),
                        **kw)

        monkeypatch.setattr(simulator, "simulate_fleet", broken)
    else:
        _broken_fleet(monkeypatch, fault)
    res = _run(tiny_root, "paper.fleet")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_broken_decision_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.core import gus

    real = gus.gus_schedule

    def broken(inst, **kw):
        a = real(inst, **kw)
        j, l = np.asarray(a.j).copy(), np.asarray(a.l).copy()
        if fault == "half_batch":  # the second half of the frame's requests left out
            n = int((np.asarray(inst.A) < 1e8).sum())
            j[n // 2:], l[n // 2:] = -1, -1
        else:  # the first served request's answer altered
            i = int(np.argmax(j >= 0))
            j[i] = -1
        return gus.Assignment(j, l)

    monkeypatch.setattr(gus, "gus_schedule", broken)
    res = _run(tiny_root, "paper.online")
    assert not res["correct"], res["checks"]
