"""Traceable registry of every public driver entry point.

Mirrors the 13-driver parity matrix of `tests/test_solver.py::_drivers`
(the contract: those recipes ARE the public surface; its two chunked
fits run here at m = 2x the forced device count, so the halo-exchange
trace is real), plus the bf16 megakernel mode (the reason contract (b)
exists), the fit-serving bucket executor (`decsvm_path_select_many`,
behind `serving.fit`), the Metropolis gossip scan
(`gossip.gossip_average`), and the warm path on the (node_chunk, lam)
mesh (`mesh-2d-block`, odd m — the ghost-padding + two-axis-stop
trace).

Shapes are deliberately tiny (m=8, n=12, p=8, 2-point grids): tracing
cost is what matters, not solution quality; `jax.make_jaxpr` never
executes a round.  Chunked/mesh drivers trace against whatever CPU
devices exist (a 1-device mesh still emits `shard_map` equations, which
is what the contracts inspect); the CLIs force host devices before
importing jax so CI traces a real multi-device binding — 4 for
`tools.jaxtrace`, 8 for `tools.meshcheck`.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Tuple

M, N, P = 8, 12, 8
L = 2          # lambda grid points
NB = 2         # problems per serving bucket
ITERS = 6
LAM = 0.05

#: the 13 parity drivers of tests/test_solver.py, by registry name
PARITY_DRIVERS = (
    "dense", "pallas", "tol", "uneven", "path-batched", "path-warm",
    "chunked", "chunked-topology", "mesh-2d", "megakernel",
    "megakernel-tol", "megakernel-path-warm", "mesh-2d-megakernel",
)


class Driver(NamedTuple):
    name: str
    fn: Callable            # traced as jax.make_jaxpr(fn)(*args)
    args: Tuple
    bf16: bool              # run contract (b) on this trace


@functools.lru_cache(maxsize=1)
def build_registry() -> Dict[str, Driver]:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import decentral, gossip
    from repro.core import path as path_mod
    from repro.core.admm import ADMMConfig, decsvm_fit
    from repro.core.admm_adaptive import decsvm_fit_tol, decsvm_fit_uneven
    from repro.core.graph import k_regular, ring

    Wn = np.asarray(ring(M), np.float32)
    Wj = jnp.asarray(Wn)
    lams = jnp.asarray([2 * LAM, LAM], jnp.float32)
    lams_host = [2 * LAM, LAM]
    mask = jnp.ones((M, N), jnp.float32)

    a = ADMMConfig(lam=LAM, max_iter=ITERS)
    pal = ADMMConfig(lam=LAM, max_iter=ITERS, use_pallas=True)
    pz = ADMMConfig(lam=0.0, max_iter=ITERS)
    mk = ADMMConfig(lam=LAM, max_iter=ITERS, backend="megakernel")
    mkz = ADMMConfig(lam=0.0, max_iter=ITERS, backend="megakernel")
    b16 = ADMMConfig(lam=LAM, max_iter=ITERS, backend="megakernel_bf16")

    X = jnp.zeros((M, N, P), jnp.float32)
    y = jnp.ones((M, N), jnp.float32)
    Xs = jnp.zeros((NB, M, N, P), jnp.float32)
    ys = jnp.ones((NB, M, N), jnp.float32)
    Ws = jnp.broadcast_to(Wj, (NB, M, M))
    # chunked-engine shapes: 2x the device count the CLI forces, so each
    # chunk really carries multiple nodes
    W8n = np.asarray(ring(2 * M), np.float32)
    X8 = jnp.zeros((2 * M, N, P), jnp.float32)
    y8 = jnp.ones((2 * M, N), jnp.float32)
    # gossip operands: per-node vectors to average over the ring
    vals = jnp.ones((M, 3), jnp.float32)
    # chunked-inside-lambda mesh shapes: an ODD node count, so the tail
    # chunk really pads with ghost rows on any multi-device mesh
    M_BLK = 2 * M + 1
    Wblk = np.asarray(ring(M_BLK), np.float32)
    Xblk = jnp.zeros((M_BLK, N, P), jnp.float32)
    yblk = jnp.ones((M_BLK, N), jnp.float32)

    recipes = {
        "dense": (lambda X, y: decsvm_fit(X, y, Wj, a), (X, y), False),
        "pallas": (lambda X, y: decsvm_fit(X, y, Wj, pal), (X, y), False),
        "tol": (lambda X, y: decsvm_fit_tol(X, y, Wj, a, tol=1e-6,
                                            stop_rule="kkt",
                                            check_every=2)[0],
                (X, y), False),
        "uneven": (lambda X, y: decsvm_fit_uneven(X, y, mask, Wj, a),
                   (X, y), False),
        "path-batched": (lambda X, y: path_mod.decsvm_path_batched(
            X, y, Wj, lams, pz), (X, y), False),
        "path-warm": (lambda X, y: path_mod.decsvm_path_warm(
            X, y, Wj, lams, pz, tol=1e-6, stop_rule="kkt",
            check_every=2)[0], (X, y), False),
        "mesh-2d": (lambda X, y: decentral.decsvm_path_mesh(
            X, y, Wn, lams_host, pz, mode="batched").path, (X, y), False),
        "megakernel": (lambda X, y: decsvm_fit(X, y, Wj, mk), (X, y), False),
        "megakernel-tol": (lambda X, y: decsvm_fit_tol(
            X, y, Wj, mk, tol=1e-6, stop_rule="kkt", check_every=2)[0],
            (X, y), False),
        "megakernel-path-warm": (lambda X, y: path_mod.decsvm_path_warm(
            X, y, Wj, lams, mkz, tol=1e-6, stop_rule="kkt",
            check_every=2)[0], (X, y), False),
        "mesh-2d-megakernel": (lambda X, y: decentral.decsvm_path_mesh(
            X, y, Wn, lams_host, mkz, mode="batched").path, (X, y), False),
        # bf16 megakernel mode: the traces contract (b) runs on
        "megakernel-bf16": (lambda X, y: decsvm_fit(X, y, Wj, b16),
                            (X, y), True),
        "megakernel-bf16-tol": (lambda X, y: decsvm_fit_tol(
            X, y, Wj, b16, tol=1e-6, stop_rule="kkt", check_every=2)[0],
            (X, y), True),
        # masked fit under a bf16 config: the fused kernel has no mask
        # operand, so this runs the streaming jnp fallback — the trace
        # where a narrowed X would be re-upcast every round (the
        # LOOP_CONST_CAST regression this registry exists to guard)
        "uneven-bf16": (lambda X, y: decsvm_fit_uneven(X, y, mask, Wj, b16),
                        (X, y), True),
        # the fit-serving bucket executor (tuning.select_lambda_path_many
        # jits exactly this program per bucket)
        "serving-bucket": (lambda Xs, ys: path_mod.decsvm_path_select_many(
            Xs, ys, Ws, lams, a, mode="warm", criterion="bic",
            check_every=2).best_B, (Xs, ys), False),
        # chunked engine: m = 2x devices, so the trace carries the halo
        # exchange (local dot + ppermute ring) and the ghost-padding
        # guards; the same ring as a dense W and as a BlockTopology
        "chunked": (lambda X8, y8: decentral.decsvm_fit_chunked(
            X8, y8, W8n, a), (X8, y8), False),
        "chunked-topology": (lambda X8, y8: decentral.decsvm_fit_chunked(
            X8, y8, k_regular(2 * M, 2), a), (X8, y8), False),
        # lax.scan Metropolis gossip — the decentralized averaging
        # primitive the async-topology work will build on
        "gossip": (lambda v: gossip.gossip_average(v, Wj, rounds=ITERS),
                   (vals,), False),
        # warm mode on the (node_chunk, lam) mesh at odd m, so the trace
        # carries the halo ppermutes, ghost padding, AND the two-axis
        # pmax-agreed stop (the deadlock surface of NONUNIFORM_STOP)
        "mesh-2d-block": (lambda Xb, yb: decentral.decsvm_path_mesh(
            Xb, yb, Wblk, lams_host, pz, mode="warm",
            check_every=2).path, (Xblk, yblk), False),
    }
    return {name: Driver(name, fn, args, bf16)
            for name, (fn, args, bf16) in recipes.items()}


def trace(driver: Driver):
    """ClosedJaxpr of one driver at its registry shapes."""
    import jax
    return jax.make_jaxpr(driver.fn)(*driver.args)


def trace_all() -> Dict[str, Tuple[Driver, object]]:
    reg = build_registry()
    return {name: (d, trace(d)) for name, d in reg.items()}
