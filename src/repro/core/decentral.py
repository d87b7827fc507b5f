"""Multi-device decentralized ADMM engines (shard_map drivers over the
unified Algorithm-1 step of ``repro.core.solver``).

Semantics are identical to ``repro.core.admm`` *by construction*: the same
``solver.make_step`` runs here with the neighbour sum swapped for a real
collective.  Each device owns m/ndev nodes; two exchange schedules:

  - "gather" (any graph): all_gather the (m_local, p) primal block then apply
    the local adjacency rows.  Correct for arbitrary W; collective volume
    O(m p) per round.
  - "ring" (ring graphs, device-aligned): lax.ppermute of only the two shard
    boundary rows; volume O(p) per round.  This is the beyond-paper,
    ICI-native schedule — on a TPU torus a ring of nodes maps onto physical
    one-hop links, exactly matching the paper's communication model.
  - "block" (any graph, any m): the chunked node-megabatch layout — each
    device owns a contiguous chunk of ceil(m/ndev) nodes on the
    "node_chunk" axis, the W B neighbour sum is computed block-wise
    (diagonal blocks as local dense dots, cross-chunk block diagonals
    rotated in via ppermute, all-zero block diagonals skipped statically
    from the topology's block-sparsity pattern), and m that doesn't
    divide the chunk count pads with exact-no-op ghost nodes.  This is
    the m >> devices path: m = 1024 networks run on 8 devices.

Three engines, in increasing parallelism:

  - ``decsvm_fit_sharded``: one fit, node state sharded over the "node" axis.
  - ``decsvm_path_sharded``: the lambda grid vmapped on top of the node
    sharding — one program fits all L grid points, but every device carries
    all L (lambda multiplies per-device memory and compute).
  - ``decsvm_path_mesh``: the true 2-D (node, lam) device mesh — grid
    points live on their own mesh axis, with warm-start continuation and
    fused modified-BIC / k-fold-CV scoring inside the same shard_map
    program.  Per-device cost scales with L / (lam-axis size).

All engines accept ``lam_weights`` (per-coordinate l1 multipliers), so the
LLA stage-2 re-fit of ``repro.core.penalties`` runs sharded.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import sanitize, solver
from repro.core.admm import ADMMConfig

Array = jax.Array

def _pvary(x, axes):
    """Mark a zero-init scan carry as varying over the manual axes (shard_map
    requires carries that become shard-local to start out varying)."""
    return jax.lax.pcast(x, axes, to="varying")


def _replicated(mesh: Mesh, x: Array) -> Array:
    """An engine's result, replicated on every device of ``mesh``.

    Outputs leave ``shard_map`` sharded over the node (and lam) axes.  On a
    mesh with explicit axis types (``jax.make_mesh``'s default) slicing or
    indexing such an axis outside the program -- the ghost-row trim, the
    selected path row -- is a sharding type error, so every engine hands its
    result back replicated, as the sharded engines always documented."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def make_node_mesh(n_devices: Optional[int] = None) -> Mesh:
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), ("node",))


def make_node_chunk_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ("node_chunk",) mesh for the chunked engines (m >> devices)."""
    from repro.launch.mesh import make_node_chunk_mesh as _make
    return _make(n_devices)


def _neighbor_sum_fn(schedule: str, ndev: int, Wl: Optional[Array]):
    """Neighbour-sum backend for ``solver.make_step`` inside shard_map.

    ``gather``: (W B)_l via all_gather + the local adjacency rows Wl.
    ``ring``: left+right neighbours via jnp.roll locally, shard boundaries
    fixed with point-to-point permutes (ndev is static: JAX<0.7 has no
    jax.lax.axis_size to recover it inside the mapped function).
    """
    if schedule == "ring":

        def ring_sum(Bl):
            up = jnp.roll(Bl, -1, axis=0)    # row i <- row i+1 (local)
            dn = jnp.roll(Bl, 1, axis=0)     # row i <- row i-1 (local)
            fwd = [(d, (d + 1) % ndev) for d in range(ndev)]
            bwd = [(d, (d - 1) % ndev) for d in range(ndev)]
            first_of_next = jax.lax.ppermute(Bl[:1], "node", bwd)
            last_of_prev = jax.lax.ppermute(Bl[-1:], "node", fwd)
            up = up.at[-1:].set(first_of_next)
            dn = dn.at[:1].set(last_of_prev)
            return up + dn

        return ring_sum

    def gather_sum(Bl):
        B_all = jax.lax.all_gather(Bl, "node", axis=0, tiled=True)   # (m, p)
        return solver.mm(Wl, B_all)

    return gather_sum


def _local_problem(Xl, yl, degl, rhol, cfg, mask=None) -> solver.Problem:
    omega = 1.0 / (2.0 * cfg.tau * degl + rhol + cfg.lam0)
    return solver.Problem(Xl, yl, degl, rhol, omega, mask)


def _block_neighbor_sum_fn(axis: str, ndev: int, Wd_l: Array,
                           Woff_l: Array, offsets):
    """Block-sparse chunked neighbour sum: (W B)_l with W viewed as an
    ndev x ndev grid of (mc, mc) blocks.

    The diagonal block is a local dense dot.  Cross-chunk blocks live on
    the statically-kept ring offsets only (``offsets``, from the
    topology's block-sparsity pattern — all-zero block diagonals are
    skipped at trace time): a moving copy of B rotates offset-to-offset
    via ``ppermute`` (delta shifts, so k offsets cost k hops total) and
    each kept offset contributes one (mc, mc) x (mc, p) dot.

    Wd_l: (mc, mc) local diagonal block rows; Woff_l: (K, mc, mc) local
    rows of the K kept off-diagonal block diagonals.
    """
    def block_sum(Bl):
        acc = solver.mm(Wd_l, Bl)
        moving = Bl
        prev = 0
        for j, k in enumerate(offsets):
            shift = k - prev
            # device d receives from device (d + shift) % ndev, so after
            # the permute ``moving`` on device d holds chunk (d + k)'s B
            perm = [(s, (s - shift) % ndev) for s in range(ndev)]
            moving = jax.lax.ppermute(moving, axis, perm)
            acc = acc + solver.mm(Woff_l[j], moving)
            prev = k
        return acc

    return block_sum


def _padded_omega(degl, rhol, cfg):
    """omega = 1/(2 tau deg + rho + lam0), but 0 on all-zero padded ghost
    rows (deg = rho = 0), where the dense formula divides by lam0 — inf
    omega turns the ghost rows' 0 * inf update into NaN.  Real rows have
    denom > 0, so this is bit-identical to ``_local_problem`` there."""
    denom = 2.0 * cfg.tau * degl + rhol + cfg.lam0
    safe = jnp.where(denom > 0, denom, 1.0)
    return jnp.where(denom > 0, 1.0 / safe, jnp.zeros_like(denom))


def _padded_problem(Xl, yl, degl, rhol, cfg, mask=None) -> solver.Problem:
    return solver.Problem(Xl, yl, degl, rhol,
                          _padded_omega(degl, rhol, cfg), mask)


def _zero_state(shape, dtype, axes) -> solver.SolverState:
    """Zero SolverState with B, P, and progress marked varying over the
    manual axes (progress starts replicated but becomes the shard-local
    max|B_new - B| after one step; t stays replicated).  Accumulators are
    promoted to fp32 — under the bf16 megakernel mode only X narrows."""
    dtype = jnp.promote_types(dtype, jnp.float32)
    B = _pvary(jnp.zeros(shape, dtype), axes)
    Pd = _pvary(jnp.zeros(shape, dtype), axes)
    prog = _pvary(jnp.asarray(jnp.inf, dtype), axes)
    return solver.SolverState(B, Pd, jnp.zeros((), jnp.int32), prog)


@functools.lru_cache(maxsize=64)
def build_sharded_admm(m: int, p: int, cfg: ADMMConfig, mesh: Mesh,
                       schedule: str = "gather"):
    """Build the jitted sharded ADMM loop (lowerable against structs).

    Cached on (m, p, cfg, mesh, schedule) — ``jax.jit`` caches by function
    identity, so without this every driver call would rebuild the closure
    and retrace/recompile from scratch.

    Returns a jitted fn (X (m,n,p), y (m,n), W (m,m), deg (m,), rho (m,),
    lam_weights (p,)) -> B (m, p), node state sharded over "node".
    """
    ndev = mesh.shape["node"]
    assert m % ndev == 0, f"m={m} must be divisible by #devices={ndev}"

    def sharded_loop(Xl, yl, Wl, degl, rhol, lamw):
        step = solver.make_step(cfg, _neighbor_sum_fn(schedule, ndev, Wl))
        prob = _local_problem(Xl, yl, degl, rhol, cfg)
        state = _zero_state((Xl.shape[0], p), Xl.dtype, ("node",))
        return solver.run_fixed(step, prob, cfg.lam, lamw,
                                num_iters=cfg.max_iter, state=state).B

    fn = jax.shard_map(
        sharded_loop, mesh=mesh,
        in_specs=(P("node"), P("node"), P("node"), P("node"), P("node"),
                  P()),
        out_specs=P("node"))
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def build_sharded_path(m: int, p: int, L: int, cfg: ADMMConfig, mesh: Mesh,
                       schedule: str = "gather"):
    """Sharded node x lambda engine: node state sharded over devices, the
    lambda grid vmapped on top — one compiled program fits all L grid
    points, each with the same collective schedule as the single fit.

    Returns a jitted fn (X, y, W, deg, rho, lams (L,), lam_weights (p,))
    -> path (L, m, p).
    """
    ndev = mesh.shape["node"]
    assert m % ndev == 0, f"m={m} must be divisible by #devices={ndev}"

    def sharded_loop(Xl, yl, Wl, degl, rhol, lams, lamw):
        step = solver.make_step(cfg, _neighbor_sum_fn(schedule, ndev, Wl))
        prob = _local_problem(Xl, yl, degl, rhol, cfg)
        m_local = Xl.shape[0]

        def fit_one(lam, B0, P0, prog0):
            state = solver.SolverState(B0, P0, jnp.zeros((), jnp.int32),
                                       prog0)
            return solver.run_fixed(step, prob, lam, lamw,
                                    num_iters=cfg.max_iter, state=state).B

        sdt = jnp.promote_types(Xl.dtype, jnp.float32)
        B0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node",))
        P0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node",))
        prog0 = _pvary(jnp.full((L,), jnp.inf, sdt), ("node",))
        return jax.vmap(fit_one)(lams, B0, P0, prog0)

    fn = jax.shard_map(
        sharded_loop, mesh=mesh,
        in_specs=(P("node"), P("node"), P("node"), P("node"), P("node"),
                  P(), P()),
        out_specs=P(None, "node"))
    return jax.jit(fn)


def _prep(X, W, cfg, schedule):
    if schedule == "ring":
        _assert_ring(W)
    Wj = jnp.asarray(W, X.dtype)
    deg = jnp.sum(Wj, axis=1)
    rho = solver.compute_rho(X, cfg.h, cfg.kernel, cfg.rho_safety,
                             backend=solver.resolve_backend(cfg))
    return Wj, deg, rho


def _lamw(lam_weights, p, dtype):
    return (jnp.ones((p,), dtype) if lam_weights is None
            else jnp.asarray(lam_weights, dtype))


@functools.partial(jax.jit, static_argnames=("h", "kernel", "safety"))
def _fold_rhos(X, folds, h, kernel, safety):
    """Per-fold rho vectors, (k, m).  Module-level jit: the old inline
    ``jax.jit(jax.vmap(...))`` built a fresh jit object (fresh cache) on
    every CV-mode call, recompiling per fit."""
    return jax.vmap(
        lambda mk: solver.compute_rho(X, h, kernel, safety, mask=mk))(folds)


def decsvm_fit_sharded(X: Array, y: Array, W: np.ndarray, cfg: ADMMConfig,
                       mesh: Optional[Mesh] = None,
                       schedule: str = "gather",
                       lam_weights: Optional[Array] = None) -> Array:
    """Run Algorithm 1 with node state sharded across devices.

    X: (m, n, p), y: (m, n), W: (m, m).  m must divide the node-axis size
    — or pass ``schedule="block"`` to run the chunked node-megabatch
    engine (``decsvm_fit_chunked``): any m, ceil(m/ndev) nodes per
    device, block-sparse neighbour sum.
    lam_weights: optional (p,) per-coordinate l1 multipliers (LLA stage 2).
    Returns B: (m, p) (fully replicated on exit).
    """
    if schedule == "block":
        return decsvm_fit_chunked(X, y, W, cfg, mesh=mesh,
                                  lam_weights=lam_weights)
    sanitize.reject_unsupported(cfg, "decsvm_fit_sharded")
    mesh = mesh or make_node_mesh()
    m, _, p = X.shape
    Wj, deg, rho = _prep(X, W, cfg, schedule)
    node_sharded = NamedSharding(mesh, P("node"))
    X = jax.device_put(X.astype(solver.problem_dtype(cfg)), node_sharded)
    y = jax.device_put(y, node_sharded)
    fitted = build_sharded_admm(m, p, cfg, mesh, schedule)
    return _replicated(mesh, fitted(X, y, Wj, deg, rho,
                                    _lamw(lam_weights, p, jnp.float32)))


def decsvm_path_sharded(X: Array, y: Array, W: np.ndarray, lams,
                        cfg: ADMMConfig, mesh: Optional[Mesh] = None,
                        schedule: str = "gather",
                        lam_weights: Optional[Array] = None) -> Array:
    """Run the whole lambda grid with node state sharded across devices.

    X: (m, n, p), y: (m, n), W: (m, m), lams: (L,) decreasing grid.
    Returns the path (L, m, p), replicated on exit; score it with
    ``repro.core.path.score_path`` / select via the modified BIC.
    cfg.lam is ignored (the grid supplies lambda).  Every device carries
    all L grid points — see ``decsvm_path_mesh`` for the 2-D layout that
    shards the grid too.  ``schedule="block"`` routes to the chunked
    engine (``decsvm_path_chunked``): any m, nodes chunked per device.
    """
    if schedule == "block":
        return decsvm_path_chunked(X, y, W, lams, cfg, mesh=mesh,
                                   lam_weights=lam_weights)
    sanitize.reject_unsupported(cfg, "decsvm_path_sharded")
    mesh = mesh or make_node_mesh()
    m, _, p = X.shape
    lams = jnp.asarray(lams, jnp.float32)
    Wj, deg, rho = _prep(X, W, cfg, schedule)
    node_sharded = NamedSharding(mesh, P("node"))
    X = jax.device_put(X.astype(solver.problem_dtype(cfg)), node_sharded)
    y = jax.device_put(y, node_sharded)
    fitted = build_sharded_path(m, p, int(lams.shape[0]), cfg, mesh, schedule)
    return _replicated(mesh, fitted(X, y, Wj, deg, rho, lams,
                                    _lamw(lam_weights, p, jnp.float32)))


# --------------------------------------------------------------------------
# Chunked node-megabatch engine (schedule="block"): m >> devices
# --------------------------------------------------------------------------


def _as_topology(W):
    from repro.core import graph  # local import: avoid cycle
    if isinstance(W, graph.BlockTopology):
        return W
    return graph.BlockTopology.from_dense(np.asarray(W))


def _chunk_prep(X, y, W, cfg, mesh):
    """Pad (X, y) with all-zero ghost nodes to m_pad = ceil(m/ndev)*ndev
    and build the block-sparse neighbour-sum operands, device-placed on
    the ("node_chunk",) mesh.  Ghost rows (X = 0, y = 0, W rows and
    columns 0) are exact fixed points of the Algorithm-1 update: deg =
    rho = 0 and omega = 0 (``_padded_omega``), so their B and P stay
    identically zero through every round — no sample mask needed, which
    keeps the pallas/megakernel fast paths available for padded chunks.
    """
    ndev = mesh.shape["node_chunk"]
    top = _as_topology(W)
    m, _, _ = X.shape
    assert top.m == m, (top.m, m)
    W_diag, offsets, W_off = top.chunk_operands(ndev)
    m_pad = W_diag.shape[0]
    pad = m_pad - m
    Xp = jnp.pad(jnp.asarray(X, jnp.float32), ((0, pad), (0, 0), (0, 0)))
    yp = jnp.pad(jnp.asarray(y, jnp.float32), ((0, pad), (0, 0)))
    deg = np.zeros((m_pad,), np.float32)
    deg[:m] = top.degrees()
    nmask = np.zeros((m_pad,), np.float32)
    nmask[:m] = 1.0
    rho = solver.compute_rho(Xp, cfg.h, cfg.kernel, cfg.rho_safety,
                             backend=solver.resolve_backend(cfg))
    cs = NamedSharding(mesh, P("node_chunk"))
    ops = dict(
        X=jax.device_put(Xp.astype(solver.problem_dtype(cfg)), cs),
        y=jax.device_put(yp, cs),
        W_diag=jax.device_put(jnp.asarray(W_diag), cs),
        W_off=jax.device_put(jnp.asarray(W_off),
                             NamedSharding(mesh, P(None, "node_chunk"))),
        deg=jax.device_put(jnp.asarray(deg), cs),
        rho=jax.device_put(rho, cs),
        nmask=jax.device_put(jnp.asarray(nmask), cs),
    )
    return ops, offsets, m_pad


@functools.lru_cache(maxsize=64)
def build_chunked_admm(m_pad: int, p: int, cfg: ADMMConfig, mesh: Mesh,
                       offsets, tol: Optional[float] = None,
                       stop_rule: str = "kkt", check_every: int = 4):
    """Jitted chunked ADMM loop: ceil(m/ndev) nodes per device, the
    round body vmapped over the chunk by ``solver.make_step`` (the
    megakernel ``csvm_block_update`` path sees the chunk-shaped X, so
    ``megakernel_supported`` re-budgets VMEM per chunk automatically).

    ``tol=None`` runs cfg.max_iter fixed rounds; with a tol the KKT (or
    legacy progress) statistic early-stops, reduced over "node_chunk"
    with the padded ghost rows masked out of the network means.

    Returns a jitted fn (X (m_pad,n,p), y, W_diag (m_pad,mc),
    W_off (K,m_pad,mc), deg, rho, lam_weights (p,), node_mask (m_pad,))
    -> (B (m_pad, p), rounds).
    """
    ndev = mesh.shape["node_chunk"]
    assert m_pad % ndev == 0, (m_pad, ndev)

    def chunk_loop(Xl, yl, Wd, Woff, degl, rhol, lamw, nmask):
        nbr = _block_neighbor_sum_fn("node_chunk", ndev, Wd, Woff, offsets)
        step = solver.make_step(cfg, nbr)
        prob = _padded_problem(Xl, yl, degl, rhol, cfg)
        state = _zero_state((Xl.shape[0], p), Xl.dtype, ("node_chunk",))
        if tol is None:
            # cached-neighbour driver: one ppermute chain per round, not two
            final = solver.run_fixed_cached(step, prob, cfg.lam, lamw,
                                            num_iters=cfg.max_iter,
                                            state=state)
        else:
            residual_fn = (solver.kkt_residual_fn(
                cfg, axis_name="node_chunk", node_mask=nmask)
                if stop_rule == "kkt" else None)
            final = solver.run_tol(step, prob, cfg.lam, lamw,
                                   max_iter=cfg.max_iter, tol=tol,
                                   state=state, residual_fn=residual_fn,
                                   axis_name="node_chunk",
                                   check_every=check_every)
        return final.B, final.t

    fn = jax.shard_map(
        chunk_loop, mesh=mesh, check_vma=False,
        in_specs=(P("node_chunk"), P("node_chunk"), P("node_chunk"),
                  P(None, "node_chunk"), P("node_chunk"), P("node_chunk"),
                  P(), P("node_chunk")),
        out_specs=(P("node_chunk"), P()))
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def build_chunked_path(m_pad: int, p: int, L: int, cfg: ADMMConfig,
                       mesh: Mesh, offsets):
    """Chunked lambda-grid engine: the grid vmapped on top of the node
    chunking (the block-schedule analogue of ``build_sharded_path``).

    Returns a jitted fn (X, y, W_diag, W_off, deg, rho, lams (L,),
    lam_weights (p,)) -> path (L, m_pad, p).
    """
    ndev = mesh.shape["node_chunk"]
    assert m_pad % ndev == 0, (m_pad, ndev)

    def chunk_loop(Xl, yl, Wd, Woff, degl, rhol, lams, lamw):
        nbr = _block_neighbor_sum_fn("node_chunk", ndev, Wd, Woff, offsets)
        step = solver.make_step(cfg, nbr)
        prob = _padded_problem(Xl, yl, degl, rhol, cfg)
        m_local = Xl.shape[0]

        def fit_one(lam, B0, P0, prog0):
            state = solver.SolverState(B0, P0, jnp.zeros((), jnp.int32),
                                       prog0)
            return solver.run_fixed_cached(step, prob, lam, lamw,
                                           num_iters=cfg.max_iter,
                                           state=state).B

        sdt = jnp.promote_types(Xl.dtype, jnp.float32)
        B0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node_chunk",))
        P0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node_chunk",))
        prog0 = _pvary(jnp.full((L,), jnp.inf, sdt), ("node_chunk",))
        return jax.vmap(fit_one)(lams, B0, P0, prog0)

    fn = jax.shard_map(
        chunk_loop, mesh=mesh,
        in_specs=(P("node_chunk"), P("node_chunk"), P("node_chunk"),
                  P(None, "node_chunk"), P("node_chunk"), P("node_chunk"),
                  P(), P()),
        out_specs=P(None, "node_chunk"))
    return jax.jit(fn)


def decsvm_fit_chunked(X: Array, y: Array, W, cfg: ADMMConfig,
                       mesh: Optional[Mesh] = None,
                       lam_weights: Optional[Array] = None,
                       tol: Optional[float] = None,
                       stop_rule: str = "kkt",
                       check_every: int = 4):
    """Run Algorithm 1 with each device owning a contiguous chunk of
    ceil(m/ndev) nodes — m is no longer capped by the device count.

    ``W`` may be a dense (m, m) adjacency or a ``graph.BlockTopology``
    (preferred at large m: no O(m^2) host array is ever built).  m need
    not divide the device count: the tail chunk is padded with all-zero
    ghost nodes that stay exact no-ops (see ``_chunk_prep``).

    Returns B (m, p); with ``tol`` returns (B (m, p), rounds).
    """
    sanitize.reject_unsupported(cfg, "decsvm_fit_chunked")
    mesh = mesh or make_node_chunk_mesh()
    m, _, p = X.shape
    ops, offsets, m_pad = _chunk_prep(X, y, W, cfg, mesh)
    fitted = build_chunked_admm(m_pad, p, cfg, mesh, offsets, tol=tol,
                                stop_rule=stop_rule,
                                check_every=check_every)
    B, t = fitted(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                  ops["deg"], ops["rho"],
                  _lamw(lam_weights, p, jnp.float32), ops["nmask"])
    B = _replicated(mesh, B)[:m]
    return (B, t) if tol is not None else B


def decsvm_path_chunked(X: Array, y: Array, W, lams, cfg: ADMMConfig,
                        mesh: Optional[Mesh] = None,
                        lam_weights: Optional[Array] = None) -> Array:
    """Whole lambda grid through the chunked engine (m >> devices).

    Returns the path (L, m, p); score/select with
    ``repro.core.path.score_path`` or use ``decsvm_path_mesh`` with
    ``schedule="block"`` for fused in-program selection.
    """
    sanitize.reject_unsupported(cfg, "decsvm_path_chunked")
    mesh = mesh or make_node_chunk_mesh()
    m, _, p = X.shape
    lams = jnp.asarray(lams, jnp.float32)
    ops, offsets, m_pad = _chunk_prep(X, y, W, cfg, mesh)
    fitted = build_chunked_path(m_pad, p, int(lams.shape[0]), cfg, mesh,
                                offsets)
    path = fitted(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                  ops["deg"], ops["rho"], lams,
                  _lamw(lam_weights, p, jnp.float32))
    return _replicated(mesh, path)[:, :m]


# --------------------------------------------------------------------------
# True 2-D (node, lam) mesh engine
# --------------------------------------------------------------------------


def make_node_lam_mesh(n_node: int, n_lam: Optional[int] = None) -> Mesh:
    """2-D device mesh with named axes ("node", "lam")."""
    from repro.launch.mesh import make_node_lam_mesh as _make
    return _make(n_node, n_lam)


@functools.lru_cache(maxsize=64)
def build_mesh_path(m: int, p: int, C: int, cfg: ADMMConfig, mesh: Mesh,
                    schedule: str = "gather", mode: str = "batched",
                    tol: float = 1e-6, stop_rule: str = "kkt",
                    with_masks: bool = False, check_every: int = 4,
                    handoff: bool = True, offsets=(),
                    m_real: Optional[int] = None):
    """Build the 2-D (node, lam) shard_map program.  Cached on all
    arguments (jit caches by function identity — a fresh closure per call
    would recompile every time).

    Grid *cells* — (lambda, sample-mask) pairs when ``with_masks``, so CV
    folds ride the same axis as plain grid points — are sharded over
    "lam"; node state over "node".  Fits AND scoring run inside the one
    program: per cell it returns (modified BIC on the in-mask data,
    held-out hinge on the mask complement), reduced over the node axis
    with psum.  Without masks the gradient skips the masking entirely
    (every sample counts; held-out hinge is 0).

    Returns a jitted fn
      (X, y, W, deg, cell_lams (C,), cell_rho (C, m), lam_weights (p,)
       [, cell_masks (C, m, n)]) -> (path (C, m, p), scores (C, 2),
                                     iters (C,)).

    mode "batched": all local cells advance in lockstep (vmap), cold start,
    cfg.max_iter rounds — trajectories match the dense batched engine.
    mode "warm": sequential continuation over each device's local cell
    block with early stop on ``stop_rule`` ("kkt" residual or legacy
    "progress"), the stop decision pmax-agreed across the node axis, the
    statistic evaluated every ``check_every`` rounds (collective-safe
    inner scan — held rounds still run their collectives).
    Continuation follows decreasing lambda; wherever lambda jumps back up
    (a full-data/fold block boundary under CV) the fit restarts cold.

    ``handoff`` (warm mode, lam axis > 1): after the first traversal each
    lam-shard ``ppermute``s its boundary solution (and its lambda) forward
    along "lam" and re-traverses its local block warm-started from the
    neighbouring shard — so continuation crosses shard boundaries exactly
    like the 1-D warm path.  Cells where continuation doesn't apply
    (shard 0, fold-block boundaries) reuse their first-sweep solution, so
    the refinement sweep early-stops almost immediately.

    ``schedule="block"`` runs the chunked node-megabatch layout: the
    node mesh axis is "node_chunk", m is the *padded* node count, the W
    operand is the ``(W_diag, W_off, node_mask)`` triple from
    ``_chunk_prep``-style block operands (``offsets`` holds the kept
    block diagonals), and ``m_real`` (< m when padded) corrects every
    scoring mean for the all-zero ghost rows.
    """
    if mode not in ("warm", "batched"):
        raise ValueError(f"mode {mode!r} not in ('warm', 'batched')")
    if stop_rule not in ("kkt", "progress"):
        raise ValueError(f"stop_rule {stop_rule!r} not in ('kkt', 'progress')")
    nax = "node_chunk" if schedule == "block" else "node"
    nn, nl = mesh.shape[nax], mesh.shape["lam"]
    assert m % nn == 0, f"m={m} must be divisible by node axis={nn}"
    assert C % nl == 0, f"cells={C} must be divisible by lam axis={nl}"
    m_real = m if m_real is None else m_real
    import math as _math

    def prog(Xl, yl, Wop, degl, cell_lams, cell_rho, lamw, cell_masks=None):
        if schedule == "block":
            Wd, Woff, nmask = Wop
            nbr = _block_neighbor_sum_fn(nax, nn, Wd, Woff, offsets)
        else:
            nmask = None
            nbr = _neighbor_sum_fn(schedule, nn, Wop)
        step = solver.make_step(cfg, nbr)
        m_local, n, _ = Xl.shape
        C_local = cell_lams.shape[0]
        cells = ((cell_lams, cell_rho) if cell_masks is None
                 else (cell_lams, cell_rho, cell_masks))

        def cell_problem(rhoc, maskc):
            if schedule == "block":
                return _padded_problem(Xl, yl, degl, rhoc, cfg, mask=maskc)
            return _local_problem(Xl, yl, degl, rhoc, cfg, mask=maskc)

        if mode == "batched":

            def fit_cell(B0, P0, prog0, lam, rhoc, maskc=None):
                prob = cell_problem(rhoc, maskc)
                state = solver.SolverState(B0, P0,
                                           jnp.zeros((), jnp.int32), prog0)
                run = (solver.run_fixed_cached if schedule == "block"
                       else solver.run_fixed)
                final = run(step, prob, lam, lamw,
                            num_iters=cfg.max_iter, state=state)
                return final.B, final.t

            sdt = jnp.promote_types(Xl.dtype, jnp.float32)
            B0 = _pvary(jnp.zeros((C_local, m_local, p), sdt),
                        (nax, "lam"))
            P0 = _pvary(jnp.zeros((C_local, m_local, p), sdt),
                        (nax, "lam"))
            prog0 = _pvary(jnp.full((C_local,), jnp.inf, sdt),
                           (nax, "lam"))
            path, iters = jax.vmap(fit_cell)(B0, P0, prog0, *cells)
        else:
            residual_fn = (solver.kkt_residual_fn(cfg, axis_name=nax,
                                                  node_mask=nmask)
                           if stop_rule == "kkt" else None)
            # The block AND ring schedules' neighbour sums run ppermute
            # inside the while body, and XLA's CollectivePermute
            # rendezvous spans the whole mesh — so under either the stop
            # decision must be agreed across BOTH axes (uniform trip
            # counts mesh-wide); converged lam columns keep refining
            # until all columns stop.  The sub-axis all_gather/psum of
            # the gather schedule rendezvous per lam column, so that one
            # keeps per-column stops.  (tools/meshcheck NONUNIFORM_STOP
            # proves this choice at trace time; ring previously joined
            # only the node axis — the PR 9 deadlock class.)
            stop_axes = (nax, "lam") if schedule in ("block", "ring") else nax
            sdt = jnp.promote_types(Xl.dtype, jnp.float32)

            def fit_from(B_init, lam, rhoc, maskc, t0=None):
                prob = cell_problem(rhoc, maskc)
                P0 = _pvary(jnp.zeros((m_local, p), sdt), (nax, "lam"))
                prog0 = _pvary(jnp.asarray(jnp.inf, sdt), (nax, "lam"))
                t_init = (jnp.zeros((), jnp.int32) if t0 is None
                          else jnp.asarray(t0, jnp.int32))
                state = solver.SolverState(B_init, P0, t_init, prog0)
                return solver.run_tol(step, prob, lam, lamw,
                                      max_iter=cfg.max_iter, tol=tol,
                                      state=state, residual_fn=residual_fn,
                                      axis_name=stop_axes,
                                      check_every=check_every)

            def outer(carry, cell):
                B_prev, lam_prev = carry
                lam, rhoc = cell[0], cell[1]
                maskc = cell[2] if len(cell) == 3 else None
                # Continuation only helps while lambda decreases; at a
                # full-data/fold block boundary lambda jumps back up to
                # lam_max, where warm-starting from a small-lambda dense
                # solution works against convergence — restart cold there.
                B_init = jnp.where(lam <= lam_prev, B_prev,
                                   jnp.zeros_like(B_prev))
                final = fit_from(B_init, lam, rhoc, maskc)
                return (final.B, lam), (final.B, final.t)

            B0 = _pvary(jnp.zeros((m_local, p), sdt), (nax, "lam"))
            lam0 = jnp.asarray(jnp.inf, sdt)
            (B_last, lam_last), (path, iters) = jax.lax.scan(
                outer, (B0, lam0), cells)

            if handoff and nl > 1:
                # Cross-shard warm-start hand-off: the first traversal ran
                # every shard's block cold at its boundary.  Shift each
                # shard's final (B, lambda) one step along "lam" (shard 0
                # receives zeros/lam=0 from the unaddressed permute slot)
                # and re-traverse warm: wherever continuation applies
                # (lambda still decreasing across the boundary) the cell
                # restarts from the neighbouring shard's boundary solution
                # with a full iteration budget — exactly the init the 1-D
                # warm path would have used.  Cells where continuation
                # doesn't apply (shard 0, fold-block boundaries) *resume*
                # their first sweep instead: same iterate, same remaining
                # budget, so a converged cell re-certifies in one
                # ``check_every`` block and a max_iter-capped cell is a
                # no-op.  ``iters`` reports the sweep-2 rounds per cell —
                # the rounds of the final traversal, matching the dense
                # warm path's accounting (sweep 1 is pipeline fill).
                perm = [(j, j + 1) for j in range(nl - 1)]
                B_in = jax.lax.ppermute(B_last, "lam", perm)
                lam_in = jax.lax.ppermute(lam_last, "lam", perm)

                def outer2(carry, xs):
                    B_prev, lam_prev = carry
                    lam, rhoc = xs[0], xs[1]
                    maskc = xs[2] if len(xs) == 5 else None
                    B_sweep1, it1 = xs[-2], xs[-1]
                    cont = lam <= lam_prev
                    B_init = jnp.where(cont, B_prev, B_sweep1)
                    t0 = jnp.where(cont, 0, it1)
                    final = fit_from(B_init, lam, rhoc, maskc, t0=t0)
                    return (final.B, lam), (final.B, final.t)

                _, (path, iters) = jax.lax.scan(
                    outer2, (B_in, lam_in), cells + (path, iters))

        # -- fused scoring (modified BIC + held-out hinge), psum over nodes;
        # accumulated fp32 regardless of the X compute dtype.  Every mean
        # uses the *real* node count: padded ghost rows have margin 0, so
        # their hinge is 1 per sample and must be masked out (their path
        # rows are exactly 0, so supp needs no correction).
        N_total = m_real * n
        f32 = jnp.float32
        margins = jnp.einsum("mnp,cmp->cmn", Xl, path, precision=solver.F32,
                             preferred_element_type=f32) * yl[None]
        hinge = jnp.maximum(1.0 - margins, 0.0)              # (C_local, m, n)
        if nmask is not None:
            hinge = hinge * nmask[None, :, None]
        if cell_masks is None:
            hinge_in = jax.lax.psum(jnp.sum(hinge, axis=(1, 2)), nax)
            n_in = jnp.asarray(N_total, f32)
            val_hinge = jnp.zeros((C_local,), f32)
        else:
            hinge_in = jax.lax.psum(
                jnp.sum(hinge * cell_masks, axis=(1, 2)), nax)
            val = 1.0 - cell_masks
            if nmask is not None:
                val = val * nmask[None, :, None]
            hinge_out = jax.lax.psum(jnp.sum(hinge * val, axis=(1, 2)),
                                     nax)
            n_out = jax.lax.psum(jnp.sum(val, axis=(1, 2)), nax)
            n_in = jax.lax.psum(jnp.sum(cell_masks, axis=(1, 2)), nax)
            val_hinge = hinge_out / jnp.maximum(n_out, 1.0)
        supp = jax.lax.psum(
            jnp.sum((jnp.abs(path) > 1e-8).astype(f32), axis=(1, 2)),
            nax)
        bic = (hinge_in / n_in
               + _math.sqrt(_math.log(N_total)) * _math.log(p)
               * (supp / m_real) / N_total)
        scores = jnp.stack([bic, val_hinge], axis=-1)        # (C_local, 2)
        return path, scores, iters

    wspec = ((P(nax), P(None, nax), P(nax)) if schedule == "block"
             else P(nax))
    base_specs = (P(nax), P(nax), wspec, P(nax),
                  P("lam"), P("lam", nax), P())
    in_specs = base_specs + ((P("lam", nax),) if with_masks else ())
    fn = jax.shard_map(
        prog, mesh=mesh, in_specs=in_specs, check_vma=False,
        out_specs=(P("lam", nax), P("lam"), P("lam")))
    return jax.jit(fn)


def decsvm_path_mesh(X: Array, y: Array, W: np.ndarray, lams,
                     cfg: ADMMConfig, mesh: Optional[Mesh] = None,
                     schedule: str = "gather", mode: str = "batched",
                     tol: float = 1e-6,
                     lam_weights: Optional[Array] = None,
                     stop_rule: str = "kkt", criterion: str = "bic",
                     cv_folds: int = 5, cv_seed: int = 0,
                     check_every: int = 4, handoff: bool = True):
    """Lambda path on a true 2-D (node, lam) device mesh, with selection.

    The L-point grid is sharded over the "lam" mesh axis (today's 1-D
    engine carries all L per device); with ``criterion="cv"`` the k-fold
    train masks join the grid as extra cells — L*(1+k) cells total — so
    full-data fits, fold fits, and both scoring rules run inside one
    shard_map program.  Returns ``repro.core.path.PathResult`` whose
    ``criteria`` is the selected rule's score per grid point.

    Warm mode evaluates the stop statistic every ``check_every`` rounds
    and, with ``handoff`` (default), ppermutes each lam-shard's boundary
    solution forward so continuation matches the 1-D warm path across
    shard boundaries (see ``build_mesh_path``).

    Requires #cells % lam-axis == 0, and m % node-axis == 0 for the
    dense schedules; ``schedule="block"`` (the chunked node-megabatch
    layout on a ("node_chunk", "lam") mesh) takes any m — the tail chunk
    pads with exact-no-op ghost nodes and every score is corrected to
    the real node count.  ``W`` may then be a ``graph.BlockTopology``.
    cfg.lam is ignored (the grid supplies lambda).
    """
    from repro.core.path import PathResult  # local import: avoid cycle

    sanitize.reject_unsupported(cfg, "decsvm_path_mesh")
    m, n, p = X.shape
    lams = np.asarray(lams, np.float32)
    L = len(lams)
    if criterion not in ("bic", "cv"):
        raise ValueError(f"criterion {criterion!r} not in ('bic', 'cv')")
    C = L * (1 + cv_folds) if criterion == "cv" else L
    chunked = schedule == "block"

    if mesh is None:
        nn, nl = _choose_mesh_shape(m, C, len(jax.devices()),
                                    chunked=chunked)
        if chunked:
            from repro.launch.mesh import make_chunk_lam_mesh
            mesh = make_chunk_lam_mesh(nn, nl)
        else:
            mesh = make_node_lam_mesh(nn, nl)
    nax = "node_chunk" if chunked else "node"
    nn = mesh.shape[nax]

    if chunked:
        top = _as_topology(W)
        assert top.m == m, (top.m, m)
        W_diag, offsets, W_off = top.chunk_operands(nn)
        m_work = W_diag.shape[0]
        pad = m_work - m
        X = jnp.pad(jnp.asarray(X, jnp.float32),
                    ((0, pad), (0, 0), (0, 0)))
        y = jnp.pad(jnp.asarray(y, jnp.float32), ((0, pad), (0, 0)))
        deg_np = np.zeros((m_work,), np.float32)
        deg_np[:m] = top.degrees()
        nmask_np = np.zeros((m_work,), np.float32)
        nmask_np[:m] = 1.0
        row_valid = nmask_np
    else:
        if schedule == "ring":
            _assert_ring(W)
        offsets, m_work = (), m
        row_valid = np.ones((m,), np.float32)

    rho_full = solver.compute_rho(X, cfg.h, cfg.kernel, cfg.rho_safety,
                                  backend=solver.resolve_backend(cfg))
    if criterion == "cv":
        from repro.core.tuning import kfold_masks  # local: avoid cycle
        folds = np.asarray(kfold_masks(m, n, cv_folds, seed=cv_seed))
        if chunked:                        # ghost rows: mask 0 everywhere
            folds = np.concatenate(
                [folds, np.zeros((cv_folds, m_work - m, n), folds.dtype)],
                axis=1)
        ones = np.broadcast_to(row_valid[None, :, None], (L, m_work, n))
        cell_masks = jnp.asarray(np.concatenate(
            [ones] + [np.broadcast_to(f, (L, m_work, n)) for f in folds]),
            X.dtype)
        cell_lams = np.concatenate([lams] * (1 + cv_folds))
        fold_rho = _fold_rhos(X, jnp.asarray(folds, X.dtype), cfg.h,
                              cfg.kernel, cfg.rho_safety)     # (k, m_work)
        cell_rho = jnp.concatenate(
            [jnp.broadcast_to(rho_full, (L, m_work))]
            + [jnp.broadcast_to(r, (L, m_work)) for r in fold_rho])
    else:
        cell_masks, cell_lams = None, lams
        cell_rho = jnp.broadcast_to(rho_full, (L, m_work))
    assert C == len(cell_lams)

    node_s = NamedSharding(mesh, P(nax))
    if chunked:
        Wop = (jax.device_put(jnp.asarray(W_diag), node_s),
               jax.device_put(jnp.asarray(W_off),
                              NamedSharding(mesh, P(None, nax))),
               jax.device_put(jnp.asarray(nmask_np), node_s))
        deg = jax.device_put(jnp.asarray(deg_np), node_s)
    else:
        Wop = jnp.asarray(W, X.dtype)
        deg = jnp.sum(Wop, axis=1)

    # X narrows to the backend's compute dtype only now — rho (above) and
    # the scoring operands stay fp32
    X_c = X.astype(solver.problem_dtype(cfg))
    X_s = jax.device_put(X_c, node_s)
    y_s = jax.device_put(y, node_s)
    rho_s = jax.device_put(cell_rho, NamedSharding(mesh, P("lam", nax)))
    lams_s = jax.device_put(jnp.asarray(cell_lams, jnp.float32),
                            NamedSharding(mesh, P("lam")))
    operands = [X_s, y_s, Wop, deg, lams_s, rho_s,
                _lamw(lam_weights, p, jnp.float32)]
    if cell_masks is not None:
        operands.append(jax.device_put(
            cell_masks, NamedSharding(mesh, P("lam", nax))))

    fitted = build_mesh_path(m_work, p, C, cfg, mesh, schedule, mode, tol,
                             stop_rule, with_masks=cell_masks is not None,
                             check_every=check_every, handoff=handoff,
                             offsets=offsets, m_real=m)
    path_cells, scores, iters = (_replicated(mesh, a)
                                 for a in fitted(*operands))

    path = path_cells[:L, :m]
    if criterion == "cv":
        criteria = jnp.mean(
            scores[L:, 1].reshape(cv_folds, L), axis=0)       # held-out hinge
    else:
        criteria = scores[:L, 0]                              # modified BIC
    i = jnp.argmin(criteria)
    lams_j = jnp.asarray(lams, X.dtype)
    return PathResult(lams_j[i], path[i], lams_j, path, criteria, iters[:L])


def _choose_mesh_shape(m: int, C: int, ndev: int, chunked: bool = False):
    """Pick (node, lam) axis sizes: use every device, maximize balance.
    ``chunked`` drops the m-divisibility constraint (the block schedule
    pads the tail chunk), so only the cell count restricts the split."""
    best = None
    for nn in range(1, ndev + 1):
        if ndev % nn:
            continue
        nl = ndev // nn
        if (not chunked and m % nn) or C % nl:
            continue
        key = (min(nn, nl), nl)        # balanced first, then grid-parallel
        if best is None or key > best[0]:
            best = (key, (nn, nl))
    if best is None:
        raise ValueError(
            f"no (node, lam) split of {ndev} devices divides m={m} and "
            f"cells={C}; pass an explicit mesh")
    return best[1]


def _assert_ring(W: np.ndarray) -> None:
    m = W.shape[0]
    expect = np.zeros_like(np.asarray(W))
    for i in range(m):
        expect[i, (i + 1) % m] = expect[i, (i - 1) % m] = 1.0
    if not np.array_equal(np.asarray(W) != 0, expect != 0):
        raise ValueError("schedule='ring' requires a ring-ordered adjacency")


def consensus_mix(grads: Array, Wmix: Array, axis: str = "node") -> Array:
    """One Metropolis mixing round of per-node tensors inside shard_map.

    Beyond-paper utility: applies the paper's one-hop communication pattern
    to arbitrary per-node gradients (no convex-convergence guarantee for
    non-convex losses — see DESIGN.md §3).
    grads: (m_local, ...) local block; Wmix: (m_local, m) local mixing rows.
    """
    flat = grads.reshape(grads.shape[0], -1)
    all_flat = jax.lax.all_gather(flat, axis, axis=0, tiled=True)
    return solver.mm(Wmix, all_flat).reshape(grads.shape)
