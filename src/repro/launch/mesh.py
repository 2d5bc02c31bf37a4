"""Production mesh definitions.

As a FUNCTION (not module-level constant) so importing never touches jax
device state — the dry-run sets XLA_FLAGS for 512 host devices before any
jax import; tests/benches see the real single device."""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_fleet_mesh", "mesh_name"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (256 chips) or 2x16x16 two-pod fleet (512 chips).

    Axes: `pod` (DCN, pure-DP) x `data` (batch) x `model` (tensor/expert)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (host) devices are available."""
    return _make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(n_devices: Optional[int] = None):
    """1-D ``("rep",)`` mesh for sharding ``simulate_fleet``'s replication axis.

    Uses the first ``n_devices`` local devices (all of them by default).
    Requesting more devices than the process can see raises — never a silent
    fallback; start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get N virtual
    CPU devices for testing."""
    avail = jax.local_device_count()
    n = avail if n_devices is None else int(n_devices)
    if n < 1 or n > avail:
        raise ValueError(
            f"make_fleet_mesh(n_devices={n_devices}): need 1 <= n_devices <= "
            f"jax.local_device_count() == {avail}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for virtual "
            "CPU devices"
        )
    return _make_mesh((n,), ("rep",))


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
