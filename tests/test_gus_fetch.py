"""``gus_schedule`` starts a concrete call's answer back to the host.

Every caller that runs the call concretely reads ``j`` and ``l`` on the
host next, so the copies of both to the host start right after dispatch,
whatever the frame's leaves, and the result is still an ``Assignment`` of
``jax.Array``s.  A call whose outputs are tracers starts no copy.  Nothing
of the answer may change: it is held bit-identical to the same frame's
answer on device leaves, and the fleet's result to the bare jitted
program's.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import SimConfig, demo_cluster_spec, gus, simulate_fleet  # noqa: E402
from repro.core.gus import gus_schedule  # noqa: E402
from repro.core.instance import (  # noqa: E402
    FlatInstance,
    GeneratorConfig,
    generate_instance,
    pad_instance,
)
from repro.core.frames import _pad_bucket  # noqa: E402
from repro.obs.trace import recording  # noqa: E402

RELAX = [(False, False), (True, False), (False, True)]
RELAX_IDS = ["exact", "relax_compute", "relax_comm"]


def _frame(n: int, seed: int = 0) -> FlatInstance:
    """A Sec. IV frame of ``n`` requests, NumPy leaves, padded to its bucket."""
    inst = generate_instance(seed, GeneratorConfig(n_requests=n), as_numpy=True)
    return pad_instance(inst, _pad_bucket(n))


def _on_device(inst: FlatInstance) -> FlatInstance:
    return jax.tree.map(jax.device_put, inst)


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a.j), np.asarray(b.j))
    np.testing.assert_array_equal(np.asarray(a.l), np.asarray(b.l))


@pytest.fixture
def copies(monkeypatch):
    """Every ``jax.Array`` whose copy to the host is started, in order."""
    started = []
    cls = type(jnp.zeros(()))
    orig = cls.copy_to_host_async

    def spy(self):
        started.append(self)
        return orig(self)

    monkeypatch.setattr(cls, "copy_to_host_async", spy)
    return started


# ---------------------------------------------------------------------------
# 1. a host frame: same answer, copies started, still jax.Arrays
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("relax", RELAX, ids=RELAX_IDS)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n", [1, 37, 100])
def test_host_frame_matches_device_leaves(n, backend, relax, copies):
    inst = _frame(n, seed=n)
    kw = dict(backend=backend, relax_compute=relax[0], relax_comm=relax[1])
    got = gus_schedule(inst, **kw)
    assert [id(x) for x in copies] == [id(got.j), id(got.l)]
    want = gus_schedule(_on_device(inst), **kw)
    assert [id(x) for x in copies[2:]] == [id(want.j), id(want.l)]
    _assert_same(got, want)
    N = inst.A.shape[0]
    for x in (got.j, got.l):
        assert isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
        assert x.dtype == jnp.int32 and x.shape == (N,)


# ---------------------------------------------------------------------------
# 2. any device leaf: the answer is started to the host, the leaves are not
# ---------------------------------------------------------------------------

DEVICE_LEAVES = {
    "all": gus._FIELDS,
    "avail": ("avail",),
    "servers": ("gamma", "eta", "max_as", "max_cs"),
}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(DEVICE_LEAVES))
def test_device_leaf_starts_no_copy(case, backend, copies):
    """A frame with device leaves starts no copy of those leaves to the
    host: only the answer's two arrays go back, as for a host frame."""
    inst = _frame(37, seed=3)
    on_dev = DEVICE_LEAVES[case]
    mixed = dataclasses.replace(inst, **{k: jax.device_put(getattr(inst, k)) for k in on_dev})
    got = gus_schedule(mixed, backend=backend)
    assert [id(x) for x in copies] == [id(got.j), id(got.l)]
    assert isinstance(got.j, jax.Array) and isinstance(got.l, jax.Array)
    _assert_same(got, gus_schedule(inst, backend=backend))


# ---------------------------------------------------------------------------
# 3. under a transform
# ---------------------------------------------------------------------------

def _stack(inst, k=2):
    return jax.tree.map(lambda x: np.stack([x] * k), inst)


#: each way of calling GUS under a transform, and the copies it starts:
#: ``vmap`` of a function that only closes over the NumPy frame binds GUS
#: with no batched input, so JAX runs the call as it stands, concretely
TRANSFORMS = {
    "jit_closure": (lambda inst: jax.jit(lambda: gus_schedule(inst))(), 0),
    "vmap_closure": (lambda inst: jax.tree.map(
        lambda x: x[0], jax.vmap(lambda _: gus_schedule(inst))(jnp.arange(2))), 2),
    "jit": (lambda inst: jax.jit(gus_schedule)(inst), 0),
    "vmap": (lambda inst: jax.tree.map(lambda x: x[0], jax.vmap(gus_schedule)(_stack(inst))), 0),
    "scan": (lambda inst: jax.tree.map(
        lambda x: x[0], jax.lax.scan(lambda c, f: (c, gus_schedule(f)), 0, _stack(inst, 1))[1]), 0),
}


@pytest.mark.parametrize("how", sorted(TRANSFORMS))
def test_transforms_trace_and_agree(how, copies):
    inst = _frame(37, seed=4)
    want = gus_schedule(_on_device(inst))
    copies.clear()
    fn, n_copies = TRANSFORMS[how]
    with recording() as rec:
        got = fn(inst)
    assert len(copies) == n_copies
    calls = [e["args"] for e in rec.events() if e["name"] == "gus/call"]
    assert [c["d2h_transfers"] for c in calls] == [n_copies // 2] * len(calls)
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# 4. the fleet: GUS on tracers only, the bare program's result
# ---------------------------------------------------------------------------

def _gus_dispatcher(inst):
    return gus.gus_schedule(inst)


def _gus_program(inst):
    return gus._gus_schedule_xla(inst)


def test_simulate_fleet_matches_bare_program():
    """A fleet through ``gus_schedule`` gives the jitted program's own
    result (``tests/test_scenarios.py`` holds the default fleet to the
    per-frame simulator; ``tests/test_gus_parity.py`` that to the NumPy
    oracle), and no ``gus/call`` in it fetched anything."""
    spec = demo_cluster_spec()
    cfg = SimConfig(horizon_ms=9_000.0, arrival_rate_per_s=2.0, delay_req_ms=6000.0)
    with recording() as rec:
        got = simulate_fleet(spec, cfg, _gus_dispatcher, n_rep=3, seed=7)
    calls = [e["args"] for e in rec.events() if e["name"] == "gus/call"]
    assert calls and all(c["d2h_transfers"] == 0 and c["d2h_bytes"] == 0 for c in calls)
    want = simulate_fleet(spec, cfg, _gus_program, n_rep=3, seed=7)
    assert got.as_dict() == want.as_dict()
    np.testing.assert_array_equal(got.satisfied_per_rep, want.satisfied_per_rep)
    np.testing.assert_array_equal(got.mean_us_per_rep, want.mean_us_per_rep)
