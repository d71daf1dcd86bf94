"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading "pod" axis
carries pure data parallelism across the pod-level DCN/ICI boundary — the
axis CHIME's cross-pod gradient compression and the elastic re-mesh policy
(runtime/fault.py) operate on.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``. JAX >= 0.7 makes mesh
    axes ``Explicit`` by default, which turns sharding into part of each
    array's type; the model's gathers and ``with_sharding_constraint``
    calls are written for compiler-propagated (Auto) shardings."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the same axis names — lets every pjit code path
    run unmodified in tests on CPU."""
    return make_mesh((1, 1), ("data", "model"))


def get_mesh(name: str) -> jax.sharding.Mesh:
    """CLI-facing mesh resolver: 'local' | 'production' | 'multipod' |
    an explicit 'DxM' shape (e.g. '4x2' = data=4, model=2 over the first
    8 visible devices)."""
    if name == "local":
        return make_local_mesh()
    if name == "production":
        return make_production_mesh()
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    try:
        d, m = (int(p) for p in name.split("x"))
        return make_mesh((d, m), ("data", "model"))
    except ValueError:
        raise ValueError(f"unknown mesh {name!r} (want local | production "
                         f"| multipod | DxM)") from None
