"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_node_chunk_mesh(n_devices=None):
    """1-D mesh with named axis ("node_chunk",) for the deCSVM engines
    (``repro.core.decentral``): each device owns a contiguous chunk of
    ``ceil(m / n_devices)`` network nodes, so m is not capped by the
    device count."""
    n = len(jax.devices()) if n_devices is None else n_devices
    assert 1 <= n <= len(jax.devices()), (n, len(jax.devices()))
    return jax.make_mesh((n,), ("node_chunk",))


def make_chunk_lam_mesh(n_chunk: int, n_lam=None):
    """2-D mesh with named axes ("node_chunk", "lam") for the deCSVM
    lambda-path engine (``repro.core.decentral.decsvm_path_mesh``): node
    chunks shard over "node_chunk" (the paper's communication axis — the
    halo exchange runs only here), lambda grid cells over "lam"."""
    n = len(jax.devices())
    n_lam = (n // n_chunk) if n_lam is None else n_lam
    assert n_chunk * n_lam <= n, (n_chunk, n_lam, n)
    return jax.make_mesh((n_chunk, n_lam), ("node_chunk", "lam"))


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

