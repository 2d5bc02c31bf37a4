"""Both scheduler kernels compile for a TPU v5e — checked without the chip.

The TPU compiler is installed with jax, and it compiles for a chip that is
described (``v5e:2x2``) rather than attached.  These tests compile, never
run, the Pallas kernels and the XLA GUS program at the sizes the chip runs
(the paper's numerical frame, and a ``mega-city`` class grid of 20 480
classes x 21 servers x 10 variants), so a layout Mosaic refuses, or a
kernel that overflows VMEM, fails here instead of on the chip.  Where a
kernel is expected, the compiled program must call it (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the tests run under several
workers.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gus_pallas import gus_assign_pallas
from repro.kernels.hier_pallas import hier_cells_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _gus_specs(B, N, M, L):
    f, i = jnp.float32, jnp.int32
    return ([((B, N), i)] + [((B, N), f)] * 4 + [((B, N, M, L), f)] * 4
            + [((B, N, M, L), jnp.bool_), ((B, M), f), ((B, M), f),
               ((B,), f), ((B,), f)])


def _hier_specs(B, C, M, L):
    f, i = jnp.float32, jnp.int32
    return ([((B, C, M, L), f), ((B, C, M, L), jnp.bool_)]
            + [((B, C, M, L), f)] * 2 + [((B, C), i)] * 2 + [((B, M), f)] * 2)


@pytest.mark.parametrize("B,N,M,L", [(8, 128, 10, 10), (8, 64, 5, 3)])
def test_gus_kernel_compiles_for_v5e(one_chip, B, N, M, L):
    text = _compiled_text(
        lambda *a: gus_assign_pallas(*a, interpret=False),
        _shapes(one_chip, *_gus_specs(B, N, M, L)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,C,M,L", [(1, 20480, 21, 10), (1, 256, 5, 3)])
def test_hier_kernel_compiles_for_v5e(one_chip, B, C, M, L):
    text = _compiled_text(
        lambda *a: hier_cells_pallas(*a, interpret=False),
        _shapes(one_chip, *_hier_specs(B, C, M, L)),
    )
    assert "tpu_custom_call" in text


def test_hier_kernel_compiles_vmapped_for_v5e(one_chip):
    """The fleet runner's lifting: vmap over replications of the
    batch-of-one entry adds a grid axis in front of (frame, class chunk)."""
    def one(*a):
        take, start = hier_cells_pallas(*(x[None] for x in a), interpret=False)
        return take[0], start[0]

    text = _compiled_text(
        jax.vmap(one), _shapes(one_chip, *_hier_specs(4, 2048, 21, 10))
    )
    assert "tpu_custom_call" in text


def test_gus_xla_compiles_for_v5e(one_chip):
    from repro.core.gus import _gus_schedule_batch_xla
    from repro.core.instance import FlatInstance

    names = ("cover", "A", "C", "w_a", "w_c", "acc", "ctime", "v", "u",
             "avail", "gamma", "eta", "max_as", "max_cs")
    leaves = dict(zip(names, _shapes(one_chip, *_gus_specs(64, 128, 10, 10))))
    text = _compiled_text(_gus_schedule_batch_xla, [FlatInstance(**leaves)])
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_gus_packed_upload_compiles_for_v5e(one_chip, backend):
    """The online decision's program: one packed ``uint32`` buffer of a
    host-padded Sec. IV frame, unpacked inside the jitted call."""
    import dataclasses

    import numpy as np

    from repro.core import gus
    from repro.core.instance import generate_instance, pad_instance

    host = gus._host_leaves(pad_instance(generate_instance(0, as_numpy=True), 128))
    layout = gus._upload_layout(tuple((k, x.shape, x.dtype) for k, x in host.items()))
    words = jax.ShapeDtypeStruct((sum(n for _, _, n, _, _ in layout),), np.uint32,
                                 sharding=one_chip)
    inst = gus.FlatInstance(**dict.fromkeys(f.name for f in dataclasses.fields(gus.FlatInstance)))
    if backend == "pallas":
        lowered = gus._gus_schedule_pallas.lower(inst, words, layout=layout, interpret=False)
    else:
        lowered = gus._gus_schedule_xla.lower(inst, words, layout=layout)
    text = lowered.compile().as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")
