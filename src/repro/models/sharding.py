"""Logical-axis sharding (MaxText-style rules).

Parameters and activations are annotated with *logical* axis names
("embed", "heads", "mlp", ...); a per-config rule table maps each logical
axis to a physical mesh axis (or a tuple, or None).  Rules are resolved
against whatever mesh is active, so the same model code runs on the
single-pod (data, model) mesh, the multi-pod (pod, data, model) mesh, and
the 1-device CPU mesh used by smoke tests.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _resolve_axis(rule, mesh_axes: tuple[str, ...]):
    """Map one logical axis's rule onto the axes present in the mesh."""
    if rule is None:
        return None
    if isinstance(rule, str):
        return rule if rule in mesh_axes else None
    # tuple of candidate axes: keep those present (e.g. batch over pod+data)
    present = tuple(a for a in rule if a in mesh_axes)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def spec(rules: Mapping[str, object], logical: Sequence[str | None],
         mesh: Mesh | None = None) -> P:
    """PartitionSpec for an array whose dims carry ``logical`` axis names."""
    mesh = mesh or _current_mesh()
    mesh_axes = tuple(mesh.axis_names) if mesh is not None else ()
    out, used = [], set()
    for name in logical:
        if name is None:
            out.append(None)
            continue
        axis = _resolve_axis(rules.get(name), mesh_axes)
        # a physical mesh axis may appear at most once in a PartitionSpec
        if axis is None:
            out.append(None)
        elif isinstance(axis, tuple):
            fresh = tuple(a for a in axis if a not in used)
            used.update(fresh)
            out.append(fresh if fresh else None)
        elif axis in used:
            out.append(None)
        else:
            used.add(axis)
            out.append(axis)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _current_mesh() -> Mesh | None:
    """The mesh installed by ``with mesh:`` around the current trace."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        m = jax.interpreters.pxla.thread_resources.env.physical_mesh
    return None if m.empty else m


def constrain(x, rules: Mapping[str, object],
              logical: Sequence[str | None]):
    """with_sharding_constraint by logical axes; no-op outside a mesh."""
    mesh = _current_mesh()
    if mesh is None or mesh.devices.size <= 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(rules, logical, mesh)))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def named_sharding(mesh: Mesh, rules: Mapping[str, object],
                   logical: Sequence[str | None],
                   shape: Sequence[int] | None = None) -> NamedSharding:
    """NamedSharding for logical axes; with ``shape`` given, mesh axes that
    do not divide the corresponding dim are dropped (jit input shardings
    must be even — e.g. qwen1.5's 40 heads cannot split 16 ways, so the
    head axis falls back to replication and GSPMD reshards internally)."""
    s = spec(rules, logical, mesh)
    if shape is not None:
        parts = []
        for i, axis in enumerate(s):
            if i < len(shape) and shape[i] % _axis_size(mesh, axis) != 0:
                parts.append(None)
            else:
                parts.append(axis)
        s = P(*parts)
    return NamedSharding(mesh, s)


def weight_use(w, rules: Mapping[str, object],
               logical: Sequence[str | None]):
    """FSDP weight-gather: constrain a *stored-sharded* weight to its
    compute sharding (tensor-parallel axes only) at the use site.

    Without this, GSPMD may satisfy a contraction over an fsdp-sharded
    ("embed"->data) weight dim by computing partial sums and ALL-REDUCING
    THE ACTIVATIONS (e.g. f32[B,S,d] per projection — the dominant
    collective in the v0 baseline roofline).  Constraining the weight to
    embed->None forces the intended all-gather of the (much smaller)
    weight instead.
    """
    rules2 = dict(rules)
    rules2["embed"] = None
    return constrain(w, rules2, logical)

