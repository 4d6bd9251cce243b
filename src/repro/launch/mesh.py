"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before the first jax call and only then builds meshes.

Meshes are built by ``distributed.meshes.make_mesh`` over the first
``prod(shape)`` devices.
"""

from __future__ import annotations

from repro.distributed.meshes import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CI-grade dry-run tests; (1, 1) runs on one device."""
    return make_mesh(shape, axes)
