"""``pad_instance``: one fill contract on two paths.

Host-resident input (every request-axis leaf a NumPy array) is padded with
NumPy and stays on the host; device-resident or mixed input (any leaf a
``jax.Array``) is padded by one jitted program.  Both must give what a
plain NumPy statement of the contract gives: the same values, dtypes and
shapes, with server-axis leaves and scalars passed through.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import gus_schedule  # noqa: E402
from repro.core.instance import (  # noqa: E402
    FlatInstance,
    GeneratorConfig,
    generate_instance,
    pad_instance,
)

#: the contract, stated independently of the code under test
FILL = dict(cover=0, A=1e9, C=-1.0, w_a=0.0, w_c=0.0, acc=0.0, ctime=1e9,
            v=0.0, u=0.0, avail=False)
DTYPE = dict(cover=np.int32, avail=np.bool_)
PASS_THROUGH = ("gamma", "eta", "max_as", "max_cs")

SEC4 = GeneratorConfig()
SMALL = GeneratorConfig(n_requests=13, n_edge=3, n_cloud=1, n_services=4, n_variants=3)


def _instance(where: str, seed: int, cfg: GeneratorConfig) -> FlatInstance:
    host = generate_instance(seed, cfg, as_numpy=True)
    if where == "host":
        return host
    if where == "device":
        return generate_instance(seed, cfg)
    # mixed, as simulate()'s admission leaves it: one device leaf
    return FlatInstance(**{**vars(host), "avail": jnp.asarray(host.avail)})


def _expected(inst: FlatInstance, n_pad: int) -> dict:
    out = {}
    for k, fill in FILL.items():
        x = np.asarray(getattr(inst, k))
        rows = np.full((n_pad - x.shape[0],) + x.shape[1:], fill)
        out[k] = np.concatenate([x, rows]).astype(DTYPE.get(k, np.float32))
    return out


def _pow2_above(n: int) -> int:
    return 1 << n.bit_length()


@pytest.mark.parametrize("where", ["host", "device", "mixed"])
@pytest.mark.parametrize("extra", ["none", "one", "pow2"])
@pytest.mark.parametrize("cfg", [SEC4, SMALL], ids=["sec4", "small"])
def test_pad_matches_numpy_contract(where, extra, cfg):
    inst = _instance(where, 5, cfg)
    N = cfg.n_requests
    n_pad = {"none": N, "one": N + 1, "pow2": _pow2_above(N)}[extra]
    padded = pad_instance(inst, n_pad)
    if n_pad == N:
        assert padded is inst
    want = _expected(inst, n_pad)
    for k, w in want.items():
        got = getattr(padded, k)
        assert got.shape == w.shape, k
        assert np.asarray(got).dtype == w.dtype, k
        np.testing.assert_array_equal(np.asarray(got), w, err_msg=k)
    for k in PASS_THROUGH:
        assert getattr(padded, k) is getattr(inst, k), k
    host_leaves = not any(isinstance(getattr(padded, k), jax.Array) for k in FILL)
    assert host_leaves == (where == "host")


@pytest.mark.parametrize("where", ["host", "device", "mixed"])
def test_pad_down_raises(where):
    inst = _instance(where, 0, SMALL)
    with pytest.raises(ValueError):
        pad_instance(inst, SMALL.n_requests - 1)


def test_host_padding_does_no_device_work():
    inst = generate_instance(1, SEC4, as_numpy=True)
    with jax.transfer_guard("disallow"):
        padded = pad_instance(inst, 128)
    assert not any(isinstance(getattr(padded, k), jax.Array) for k in FILL)


@pytest.mark.parametrize("cfg", [SEC4, SMALL], ids=["sec4", "small"])
def test_gus_same_on_host_and_device_padding(cfg):
    n_pad = _pow2_above(cfg.n_requests)
    for seed in range(4):
        host = gus_schedule(pad_instance(_instance("host", seed, cfg), n_pad))
        dev = gus_schedule(pad_instance(_instance("device", seed, cfg), n_pad))
        np.testing.assert_array_equal(np.asarray(host.j), np.asarray(dev.j))
        np.testing.assert_array_equal(np.asarray(host.l), np.asarray(dev.l))
