"""Activation-sharding constraints that are no-ops off-mesh.

The launch layer wraps tracing in ``jax.set_mesh(mesh)``; inside
the model we then pin the few activation layouts GSPMD gets wrong on its
own (notably: vocab-sharded logits, batch-sharded residual stream).
``constrain(x, "data", None, "model")`` filters axis names against the
ambient (abstract) mesh, so the same model code runs unsharded on CPU tests.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

# "data"-like axes are expanded to every data axis present ("pod","data")
_DATA_ALIASES = {"data": ("pod", "data")}


def _ambient_axes():
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return None
    if mesh is None or not mesh.axis_names:
        return None
    return tuple(mesh.axis_names)


def constrain(x, *spec):
    axes = _ambient_axes()
    if axes is None:
        return x
    parts = []
    for s in spec:
        if s is None:
            parts.append(None)
        elif s in _DATA_ALIASES:
            expand = tuple(a for a in _DATA_ALIASES[s] if a in axes)
            parts.append(expand if expand else None)
        elif s in axes:
            parts.append(s)
        else:
            parts.append(None)
    # pad to rank
    while len(parts) < x.ndim:
        parts.append(None)
    return jax.lax.with_sharding_constraint(x, P(*parts[:x.ndim]))
