"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and smoke tests/benches must keep seeing the single real device.

Mesh semantics (TPU v5e pods):

* single-pod: ``(16, 16)`` over ``("data", "model")`` — 256 chips, both
  axes on ICI (2D torus: one physical ring per mesh dim).
* multi-pod: ``(2, 16, 16)`` over ``("pod", "data", "model")`` — 512 chips;
  the ``pod`` axis rides DCN (pod-to-pod network), everything else ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the models place activations by
    ``with_sharding_constraint`` (GSPMD propagation), not by Explicit
    sharding types, which are ``jax.make_mesh``'s default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1, *, data: int | None = None,
                   multi_pod: bool = False) -> Mesh:
    """Small mesh over whatever devices exist (tests, examples)."""
    n = jax.device_count()
    data = data or max(n // model, 1)
    if multi_pod:
        assert data % 2 == 0
        return _make_mesh((2, data // 2, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
