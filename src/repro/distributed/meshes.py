"""Mesh helpers + logical-axis translation.

Models annotate arrays with *logical* axes ("dp", "fsdp", "tp"); the launcher
installs a rule set mapping them onto whatever physical mesh is live:

  single pod (16, 16) ("data", "model"):   dp=("data",), fsdp="data", tp="model"
  multi-pod (2, 16, 16) ("pod","data","model"):
                                            dp=("pod","data"), fsdp="data", tp="model"

Keeping models in logical axes is what makes elastic re-meshing (checkpoint
restore onto a different topology) a pure launcher concern.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_STATE = threading.local()

#: ``jax.shard_map`` without the replication check: the distributed
#: operator's out_specs are replicated where declared, but the checker cannot
#: always prove it through ``dynamic_slice`` + ``all_gather`` chains.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Build a Mesh over the first ``prod(shape)`` devices.

    Unlike ``jax.make_mesh`` this accepts a shape smaller than the device
    count (it slices), which is what lets a size-1 solver mesh run inside a
    plain single-device pytest process.
    """
    size = int(np.prod(shape))
    devices = jax.devices()
    if size > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {size} devices; "
            f"only {len(devices)} available (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={size} for a "
            f"host-platform test mesh)"
        )
    return Mesh(np.asarray(devices[:size]).reshape(shape), axes)


def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """Parse a ``--mesh ROWSxMODEL`` spec ("4x2" -> (4, 2)).

    "auto" (or "") puts every local device on the row axis — the right
    default for KRR, whose workhorse parallelism is row sharding.
    """
    if spec in ("auto", ""):
        return (len(jax.devices()), 1)
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() and int(p) > 0 for p in parts):
        raise ValueError(
            f"mesh spec {spec!r} invalid; accepted: 'ROWSxMODEL' with "
            f"positive integers (e.g. '4x2') or 'auto'"
        )
    return (int(parts[0]), int(parts[1]))


def make_solver_mesh(spec: str | tuple[int, int] | None = None) -> Mesh:
    """("data", "model") mesh for distributed KRR solves.

    ``spec``: "ROWSxMODEL" string, (rows, model) tuple, or None/"auto" for
    all local devices on rows.  A (1, 1) mesh is always valid — size-1 axes
    make every collective a no-op, so the distributed code path runs in a
    plain single-device process (the pytest fallback).
    """
    if spec is None or isinstance(spec, str):
        rows, model = parse_mesh_spec(spec if isinstance(spec, str) else "auto")
    else:
        rows, model = spec
    return make_mesh((rows, model), ("data", "model"))


def default_rules(mesh: Mesh) -> dict[str, Any]:
    axes = mesh.axis_names
    if "pod" in axes:
        return {"dp": ("pod", "data"), "fsdp": "data", "tp": "model"}
    if "data" in axes:
        return {"dp": ("data",), "fsdp": "data", "tp": "model"}
    # degenerate single-axis test meshes
    ax = axes[0]
    return {"dp": (ax,), "fsdp": ax, "tp": None}


@contextlib.contextmanager
def logical_rules(rules: dict[str, Any]):
    prev = getattr(_STATE, "rules", None)
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def current_rules() -> dict[str, Any] | None:
    return getattr(_STATE, "rules", None)


def to_physical(spec: P, rules: dict[str, Any] | None = None) -> P:
    """Translate a logical PartitionSpec to physical mesh axes."""
    rules = rules or current_rules()
    if rules is None:
        return spec
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            phys: list[str] = []
            for e in entry:
                r = rules.get(e, e)
                if r is None:
                    continue
                phys.extend(r if isinstance(r, (tuple, list)) else (r,))
            out.append(tuple(phys) if phys else None)
        else:
            r = rules.get(entry, entry)
            if r is None:
                out.append(None)
            elif isinstance(r, (tuple, list)):
                out.append(tuple(r))
            else:
                out.append(r)
    return P(*out)


def logical_constraint(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint in logical axes; no-op without rules/mesh."""
    rules = current_rules()
    if rules is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, to_physical(spec, rules))
    except (ValueError, RuntimeError):
        return x  # no mesh in scope (unit tests on 1 device)


def tree_to_physical(spec_tree, rules: dict[str, Any] | None = None):
    return jax.tree.map(
        lambda s: to_physical(s, rules),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def named_shardings(mesh: Mesh, spec_tree, rules: dict[str, Any] | None = None):
    rules = rules or default_rules(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, to_physical(s, rules)),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def sanitize_pspec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharded axes that don't divide the dim (e.g. batch=1 decode)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        out.append(entry if dim % total == 0 else None)
    return P(*out)


def sanitized_shardings(mesh: Mesh, spec_tree, struct_tree,
                        rules: dict[str, Any] | None = None):
    """named_shardings + per-dim divisibility sanitation vs. struct shapes."""
    rules = rules or default_rules(mesh)

    def one(spec, struct):
        phys = to_physical(spec, rules)
        phys = sanitize_pspec(phys, tuple(struct.shape), mesh)
        return NamedSharding(mesh, phys)

    return jax.tree.map(
        one, spec_tree, struct_tree, is_leaf=lambda s: isinstance(s, P)
    )
