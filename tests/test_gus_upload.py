"""``gus_schedule``'s packed upload: one transfer per host-side frame.

NumPy leaves are written into one ``uint32`` buffer on the host, put on
the device with one ``jax.device_put`` and unpacked inside the jitted GUS
program; ``jax.Array`` leaves pass as they are and tracers are never
packed.  The program must see the very bits it would have been given leaf
by leaf, so the assignments are held bit-identical to the same frame with
every leaf on the device first.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import gus  # noqa: E402
from repro.core.gus import gus_schedule  # noqa: E402
from repro.core.instance import (  # noqa: E402
    FlatInstance,
    GeneratorConfig,
    generate_instance,
    pad_instance,
)
from repro.core.frames import _pad_bucket  # noqa: E402

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


class _Counter:
    """Wraps a callable and counts its calls."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, *a, **k):
        self.calls.append(a)
        return self.fn(*a, **k)


# ---------------------------------------------------------------------------
# 1. the packed path gives the unpacked path's assignments, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("relax", RELAX, ids=RELAX_IDS)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n", [1, 37, 100])
def test_packed_matches_device_leaves(n, backend, relax, monkeypatch):
    inst = _frame(n, seed=n)
    assert all(isinstance(x, (np.ndarray, np.generic)) for x in jax.tree.leaves(inst))
    dev = _on_device(inst)
    pack = _Counter(gus._pack)
    monkeypatch.setattr(gus, "_pack", pack)
    kw = dict(backend=backend, relax_compute=relax[0], relax_comm=relax[1])
    got = gus_schedule(inst, **kw)
    assert len(pack.calls) == 1
    want = gus_schedule(dev, **kw)
    assert len(pack.calls) == 1  # device leaves are not packed
    _assert_same(got, want)
    assert np.asarray(got.j).shape == (inst.A.shape[0],)


# ---------------------------------------------------------------------------
# 2. unpacking returns every leaf bit-exact
# ---------------------------------------------------------------------------

_F32_SPECIAL = np.array(
    [np.nan, -np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-45, -1e-40, 1.1754942e-38,
     np.finfo(np.float32).max, np.finfo(np.float32).min, 1.0], np.float32)
_NAN_PAYLOAD = np.array([0x7FC00001, 0xFFBFFFFF, 0x7F800001], np.uint32).view(np.float32)
_I32_EXTREME = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, -1, 0, 1], np.int32)

ROUND_TRIPS = {
    "float32_specials": dict(a=_F32_SPECIAL.reshape(3, 4), b=_NAN_PAYLOAD),
    "int32_extremes": dict(a=_I32_EXTREME, b=_I32_EXTREME[::-1].reshape(5, 1, 1)),
    "bool_odd_lengths": dict(a=np.array([True, False, True]),
                             b=np.arange(15).reshape(3, 5) % 3 == 0, c=np.array(True)),
    "scalars": dict(a=np.float32(-0.0), b=np.int32(-2**31), c=np.float32(np.nan)),
    "sub_word_dtypes": dict(a=np.array([-128, 127, 0, -1, 5], np.int8),
                            b=np.array([0, 65535, 7], np.uint16),
                            c=np.array([1.5, -np.inf, -0.0], jnp.bfloat16)),
    "mixed_order": dict(a=np.array([True]), b=_F32_SPECIAL, c=np.array([0], np.int8),
                        d=_I32_EXTREME, e=np.zeros((0, 3), np.float32)),
}


@pytest.mark.parametrize("case", sorted(ROUND_TRIPS))
def test_unpack_is_bit_exact(case):
    host = {k: np.asarray(v) for k, v in ROUND_TRIPS[case].items()}
    layout = gus._upload_layout(tuple((k, x.shape, x.dtype) for k, x in host.items()))
    words = gus._pack(host, layout)
    assert words.dtype == np.uint32
    assert words.nbytes == sum(-(-x.nbytes // 4) * 4 for x in host.values())
    out = jax.jit(gus._unpack, static_argnums=1)(jax.device_put(words), layout)
    for k, x in host.items():
        y = np.asarray(out[k])
        assert y.dtype == x.dtype and y.shape == x.shape, k
        assert y.tobytes() == x.tobytes(), k


def test_layout_is_cached_per_shape():
    a = gus._host_leaves(_frame(37))
    b = gus._host_leaves(_frame(40, seed=1))  # same bucket, other values
    spec = lambda h: tuple((k, x.shape, x.dtype) for k, x in h.items())  # noqa: E731
    assert gus._upload_layout(spec(a)) is gus._upload_layout(spec(b))
    assert gus._upload_layout(spec(gus._host_leaves(_frame(100)))) != gus._upload_layout(spec(a))


# ---------------------------------------------------------------------------
# 3. mixed host and device leaves
# ---------------------------------------------------------------------------

MIXED = {
    "avail_on_device": ("avail",),
    "cells_on_device": ("acc", "ctime", "v", "u"),
    "servers_on_device": ("gamma", "eta", "max_as", "max_cs"),
    "all_but_cover_on_device": tuple(f for f in gus._FIELDS if f != "cover"),
}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(MIXED))
def test_mixed_host_and_device_leaves(case, backend, monkeypatch):
    inst = _frame(37, seed=3)
    on_dev = MIXED[case]
    mixed = dataclasses.replace(inst, **{k: jax.device_put(getattr(inst, k)) for k in on_dev})
    put = _Counter(jax.device_put)
    monkeypatch.setattr(jax, "device_put", put)
    got = gus_schedule(mixed, backend=backend)
    (words,) = put.calls[0]
    host_bytes = sum(-(-np.asarray(getattr(inst, k)).nbytes // 4) * 4
                     for k in gus._FIELDS if k not in on_dev)
    assert len(put.calls) == 1 and words.nbytes == host_bytes
    monkeypatch.undo()
    _assert_same(got, gus_schedule(_on_device(inst), backend=backend))


# ---------------------------------------------------------------------------
# 4. tracers are never packed
# ---------------------------------------------------------------------------

TRANSFORMS = {
    "jit": lambda inst: jax.jit(gus_schedule)(inst),
    "jit_one_traced_leaf": lambda inst: jax.jit(
        lambda A: gus_schedule(dataclasses.replace(inst, A=A)))(inst.A),
    "vmap": lambda inst: jax.tree.map(
        lambda x: x[0], jax.vmap(gus_schedule)(jax.tree.map(lambda x: np.stack([x, x]), inst))),
    "scan": lambda inst: jax.tree.map(
        lambda x: x[0], jax.lax.scan(lambda c, f: (c, gus_schedule(f)), 0,
                                     jax.tree.map(lambda x: np.stack([x]), inst))[1]),
}


@pytest.mark.parametrize("how", sorted(TRANSFORMS))
def test_tracers_are_never_packed(how, monkeypatch):
    inst = _frame(37, seed=4)
    want = gus_schedule(_on_device(inst))
    pack = _Counter(gus._pack)
    monkeypatch.setattr(gus, "_pack", pack)
    got = TRANSFORMS[how](inst)
    assert pack.calls == []
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# 5. one jax.device_put per call on host leaves, and no implicit transfer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n", [37, 100])
def test_one_device_put_per_call(n, backend, monkeypatch):
    inst = _frame(n, seed=5)
    want = gus_schedule(inst, backend=backend)  # compiles outside the guard
    put = _Counter(jax.device_put)
    monkeypatch.setattr(jax, "device_put", put)
    with jax.transfer_guard_host_to_device("disallow"):
        got = gus_schedule(inst, backend=backend)
    assert len(put.calls) == 1
    (words,) = put.calls[0]
    assert isinstance(words, np.ndarray) and words.dtype == np.uint32
    _assert_same(got, want)
