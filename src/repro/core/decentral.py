"""Multi-device decentralized ADMM engines (shard_map drivers over the
unified Algorithm-1 step of ``repro.core.solver``).

Semantics are identical to ``repro.core.admm`` *by construction*: the same
``solver.make_step`` runs here with the neighbour sum swapped for a real
collective.  There is one layout and one exchange:

  - Layout: each device owns a contiguous chunk of ceil(m/ndev) nodes on
    the "node_chunk" mesh axis.  m that doesn't divide the chunk count
    pads with all-zero ghost nodes, exact no-ops of the update, so any m
    runs on any device count (m >> devices: 1,024 nodes on 4 chips).
  - Exchange: the W B neighbour sum is computed block-wise
    (``_block_neighbor_sum_fn``).  The diagonal block is a local dense
    dot; for each cross-chunk block diagonal the topology keeps, a
    ppermute carries only the rows of B its blocks read
    (``graph.BlockTopology.chunk_operands``).  The topology decides the
    traffic: a ring at one node a device sends one boundary row each
    way, a dense graph sends whole chunks (the rows an all_gather moves),
    and all-zero block diagonals send nothing.

Engines:

  - ``decsvm_fit_chunked``: one fit, fixed rounds or the KKT stop.
  - ``decsvm_path_chunked``: the lambda grid vmapped on top of the node
    chunks — every device carries all L grid points.
  - ``decsvm_path_mesh``: the 2-D (node_chunk, lam) mesh — grid points
    live on their own mesh axis, with warm-start continuation and fused
    modified-BIC / k-fold-CV scoring inside the same shard_map program.
    Per-device cost scales with L / (lam-axis size).

All engines accept ``lam_weights`` (per-coordinate l1 multipliers), so the
LLA stage-2 re-fit of ``repro.core.penalties`` runs sharded.
"""
from __future__ import annotations

import functools
import math
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import sanitize, solver, trace
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
    result back replicated."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def make_node_chunk_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ("node_chunk",) mesh for the chunked engines (m >> devices)."""
    from repro.launch.mesh import make_node_chunk_mesh as _make
    return _make(n_devices)


def _halo_rows(Bl: Array, rows) -> Array:
    """Rows ``rows`` (sorted, static) of a chunk's B: a static slice where
    they are contiguous (the chunk itself, with no op, where they are all
    of it), else a static gather."""
    lo, hi = rows[0], rows[-1] + 1
    if hi - lo == len(rows):
        return Bl[lo:hi]
    return jnp.take(Bl, np.asarray(rows), axis=0, mode="clip",
                    indices_are_sorted=True, unique_indices=True)


def _block_neighbor_sum_fn(axis: str, ndev: int, Wd_l: Array,
                           Woff_l: Array, halos):
    """Block-sparse chunked neighbour sum: (W B)_l with W viewed as an
    ndev x ndev grid of (mc, mc) blocks.

    The diagonal block is a local dense dot.  Cross-chunk blocks live on
    the statically-kept ring offsets only (``halos``, from the
    topology's block-sparsity pattern — all-zero block diagonals are
    skipped at trace time).  A halo exchange: for each kept offset k
    every device sends only the rows of its B that some block (d, d+k)
    reads (``halos``' rows, ``graph.BlockTopology.chunk_operands``),
    straight to the device k chunks back in one ``ppermute``; the halos
    received then take one (mc, R) x (R, p) dot.  Where a halo is the
    whole chunk, the chunk goes as it is.  The rows carried are recorded
    in ``trace.exchange`` as the sum is built.

    Wd_l: (mc, mc) local diagonal block rows; Woff_l: (mc, R) local rows
    of the kept off-diagonal blocks in their halo columns, side by side.
    """
    mc = Wd_l.shape[1]
    for _, rows in halos:
        trace.exchange.record(len(rows), mc)

    def block_sum(Bl):
        acc = solver.mm(Wd_l, Bl)
        if not halos:
            return acc
        with jax.named_scope(trace.EXCHANGE):
            # device d receives from device (d + k) % ndev, so piece k on
            # device d holds chunk (d + k)'s halo rows of B
            recv = [jax.lax.ppermute(
                        _halo_rows(Bl, rows), axis,
                        [(s, (s - k) % ndev) for s in range(ndev)])
                    for k, rows in halos]
            return acc + solver.mm(Woff_l, jnp.concatenate(recv))

    return block_sum


def _padded_omega(degl, rhol, cfg):
    """omega = 1/(2 tau deg + rho + lam0), but 0 on all-zero padded ghost
    rows (deg = rho = 0), where the dense formula divides by lam0 — inf
    omega turns the ghost rows' 0 * inf update into NaN.  Real rows have
    denom > 0, so this is bit-identical to the dense formula there."""
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


# --------------------------------------------------------------------------
# Chunked node layout: fit and lambda grid
# --------------------------------------------------------------------------


def _as_topology(W):
    from repro.core import graph  # local import: avoid cycle
    if isinstance(W, graph.BlockTopology):
        return W
    return graph.BlockTopology.from_dense(np.asarray(W))


def _chunk_mesh(X, mesh: Optional[Mesh]) -> Mesh:
    """The ("node_chunk",) mesh of a chunked fit: ``mesh`` where given;
    else the devices a ``jax.Array`` X is already sharded over along its
    node axis, in their order, so that X stays where it is; else every
    device (``make_node_chunk_mesh``)."""
    if mesh is not None:
        return mesh
    sh = getattr(X, "sharding", None)
    if (isinstance(sh, NamedSharding) and sh.mesh.devices.ndim == 1
            and tuple(sh.spec)[:1] == sh.mesh.axis_names
            and not any(tuple(sh.spec)[1:])):
        return Mesh(sh.mesh.devices, ("node_chunk",))
    return make_node_chunk_mesh()


# topology -> {mesh: its device operands}; an entry lives as long as the
# caller keeps its topology.
_CHUNK_OPERANDS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _chunk_operands(top, mesh: Mesh):
    """The block-sparse neighbour-sum operands of topology ``top`` for
    ``mesh``'s chunks, device-placed once per (topology, mesh): a caller
    that keeps its ``graph.BlockTopology`` pays no host work for the graph
    per fit.  Returns (W_diag, W_off, deg, node mask, halos)."""
    per_mesh = _CHUNK_OPERANDS.setdefault(top, {})
    if mesh not in per_mesh:
        W_diag, halos, W_off = top.chunk_operands(mesh.shape["node_chunk"])
        m_pad = W_diag.shape[0]
        deg = np.zeros((m_pad,), np.float32)
        deg[:top.m] = top.degrees()
        nmask = np.zeros((m_pad,), np.float32)
        nmask[:top.m] = 1.0
        cs = NamedSharding(mesh, P("node_chunk"))
        per_mesh[mesh] = (
            jax.device_put(W_diag, cs),
            jax.device_put(W_off, cs),
            jax.device_put(deg, cs), jax.device_put(nmask, cs), halos)
    return per_mesh[mesh]


def _chunk_prep(X, y, W, mesh):
    """(X, y) node-sharded over the ("node_chunk",) mesh, and the
    topology's operands (``_chunk_operands``).

    Where m divides over the mesh, an fp32 X already sharded so along its
    node axis is handed on as it is: no pad, no cast, no copy (the
    ``device_put`` is to an equivalent sharding).  Otherwise (X, y) are
    padded with all-zero ghost nodes to m_pad = ceil(m/ndev)*ndev.  Ghost
    rows (X = 0, y = 0, W rows and columns 0) are exact fixed points of
    the Algorithm-1 update: deg = rho = 0 and omega = 0
    (``_padded_omega``), so their B and P stay identically zero through
    every round — no sample mask needed, which keeps the pallas/megakernel
    fast paths available for padded chunks.
    """
    top = _as_topology(W)
    assert top.m == X.shape[0], (top.m, X.shape[0])
    W_diag, W_off, deg, nmask, halos = _chunk_operands(top, mesh)
    m_pad = W_diag.shape[0]
    cs = NamedSharding(mesh, P("node_chunk"))

    def place(a):
        pad = m_pad - a.shape[0]
        if pad:
            a = jnp.pad(jnp.asarray(a, jnp.float32),
                        ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        elif not isinstance(a, jax.Array):
            a = np.asarray(a, np.float32)
        elif a.dtype != jnp.float32:
            a = a.astype(jnp.float32)
        return jax.device_put(a, cs)

    ops = dict(X=place(X), y=place(y), W_diag=W_diag, W_off=W_off, deg=deg,
               nmask=nmask)
    return ops, halos, m_pad


def _chunk_problem(Xl, yl, degl, cfg) -> solver.Problem:
    """A chunk's ``Problem``: rho by the power iteration on the chunk's own
    nodes (in X's fp32, as ``solver.make_problem``), then X in the
    backend's compute dtype."""
    rhol = solver.compute_rho(Xl, cfg.h, cfg.kernel, cfg.rho_safety,
                              backend=solver.resolve_backend(cfg))
    return _padded_problem(Xl.astype(solver.problem_dtype(cfg)), yl, degl,
                           rhol, cfg)


@functools.lru_cache(maxsize=64)
def build_chunked_admm(m_pad: int, p: int, cfg: ADMMConfig, mesh: Mesh,
                       halos, tol: Optional[float] = None,
                       stop_rule: str = "kkt", check_every: int = 4):
    """Jitted chunked ADMM loop: ceil(m/ndev) nodes per device, rho and
    the round body vmapped over the chunk (``solver.compute_rho``,
    ``solver.make_step``; the megakernel ``csvm_block_update`` path sees
    the chunk-shaped X, so ``megakernel_supported`` re-budgets VMEM per
    chunk automatically).

    ``tol=None`` runs cfg.max_iter fixed rounds; with a tol the KKT (or
    legacy progress) statistic early-stops, reduced over "node_chunk"
    with the padded ghost rows masked out of the network means.

    Returns a jitted fn (X (m_pad,n,p) fp32, y, W_diag (m_pad,mc),
    W_off (m_pad,R), deg, lam_weights (p,), node_mask (m_pad,))
    -> (B (m_pad, p), rounds).  ``halos`` are the kept block diagonals
    and their halo rows (``graph.BlockTopology.chunk_operands``).
    """
    ndev = mesh.shape["node_chunk"]
    assert m_pad % ndev == 0, (m_pad, ndev)

    def chunk_loop(Xl, yl, Wd, Woff, degl, lamw, nmask):
        nbr = _block_neighbor_sum_fn("node_chunk", ndev, Wd, Woff, halos)
        step = solver.make_step(cfg, nbr)
        prob = _chunk_problem(Xl, yl, degl, cfg)
        state = _zero_state((Xl.shape[0], p), prob.X.dtype, ("node_chunk",))
        if tol is None:
            # cached-neighbour driver: one halo exchange per round, not two
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
                  P("node_chunk"), P("node_chunk"), P(),
                  P("node_chunk")),
        out_specs=(P("node_chunk"), P()))
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def build_chunked_path(m_pad: int, p: int, L: int, cfg: ADMMConfig,
                       mesh: Mesh, halos):
    """Chunked lambda-grid engine: the grid vmapped on top of the node
    chunking, every device carrying all L grid points.

    Returns a jitted fn (X, y, W_diag, W_off, deg, lams (L,),
    lam_weights (p,)) -> path (L, m_pad, p).
    """
    ndev = mesh.shape["node_chunk"]
    assert m_pad % ndev == 0, (m_pad, ndev)

    def chunk_loop(Xl, yl, Wd, Woff, degl, lams, lamw):
        nbr = _block_neighbor_sum_fn("node_chunk", ndev, Wd, Woff, halos)
        step = solver.make_step(cfg, nbr)
        prob = _chunk_problem(Xl, yl, degl, cfg)
        m_local = Xl.shape[0]

        def fit_one(lam, B0, P0, prog0):
            state = solver.SolverState(B0, P0, jnp.zeros((), jnp.int32),
                                       prog0)
            return solver.run_fixed_cached(step, prob, lam, lamw,
                                           num_iters=cfg.max_iter,
                                           state=state).B

        sdt = jnp.promote_types(prob.X.dtype, jnp.float32)
        B0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node_chunk",))
        P0 = _pvary(jnp.zeros((L, m_local, p), sdt), ("node_chunk",))
        prog0 = _pvary(jnp.full((L,), jnp.inf, sdt), ("node_chunk",))
        return jax.vmap(fit_one)(lams, B0, P0, prog0)

    fn = jax.shard_map(
        chunk_loop, mesh=mesh, check_vma=False,
        in_specs=(P("node_chunk"), P("node_chunk"), P("node_chunk"),
                  P("node_chunk"), P("node_chunk"), P(), P()),
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
    (preferred at large m: no O(m^2) host array is ever built, and its
    device operands are made once, ``_chunk_operands``).  m need not
    divide the device count: the tail chunk is padded with all-zero ghost
    nodes that stay exact no-ops (see ``_chunk_prep``).  Without ``mesh``
    an X sharded along its node axis keeps its devices (``_chunk_mesh``):
    it is read where it lies, and never gathered.

    Returns B (m, p); with ``tol`` returns (B (m, p), rounds).
    """
    sanitize.reject_unsupported(cfg, "decsvm_fit_chunked")
    mesh = _chunk_mesh(X, mesh)
    m, _, p = X.shape
    ops, halos, m_pad = _chunk_prep(X, y, W, mesh)
    fitted = build_chunked_admm(m_pad, p, cfg, mesh, halos, tol=tol,
                                stop_rule=stop_rule,
                                check_every=check_every)
    B, t = fitted(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                  ops["deg"], _lamw(lam_weights, p, jnp.float32),
                  ops["nmask"])
    B = _replicated(mesh, B)[:m]
    return (B, t) if tol is not None else B


def decsvm_path_chunked(X: Array, y: Array, W, lams, cfg: ADMMConfig,
                        mesh: Optional[Mesh] = None,
                        lam_weights: Optional[Array] = None) -> Array:
    """Whole lambda grid through the chunked engine (m >> devices).

    Returns the path (L, m, p); score/select with
    ``repro.core.path.score_path`` or use ``decsvm_path_mesh`` for fused
    in-program selection.
    """
    sanitize.reject_unsupported(cfg, "decsvm_path_chunked")
    mesh = _chunk_mesh(X, mesh)
    m, _, p = X.shape
    lams = jnp.asarray(lams, jnp.float32)
    ops, halos, m_pad = _chunk_prep(X, y, W, mesh)
    fitted = build_chunked_path(m_pad, p, int(lams.shape[0]), cfg, mesh,
                                halos)
    path = fitted(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                  ops["deg"], lams, _lamw(lam_weights, p, jnp.float32))
    return _replicated(mesh, path)[:, :m]




# --------------------------------------------------------------------------
# 2-D (node_chunk, lam) mesh engine
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def build_mesh_path(m: int, p: int, C: int, cfg: ADMMConfig, mesh: Mesh,
                    mode: str = "batched", tol: float = 1e-6,
                    stop_rule: str = "kkt", with_masks: bool = False,
                    check_every: int = 4, handoff: bool = True, halos=(),
                    m_real: Optional[int] = None):
    """Build the 2-D (node_chunk, lam) shard_map program.  Cached on all
    arguments (jit caches by function identity — a fresh closure per call
    would recompile every time).

    Grid *cells* — (lambda, sample-mask) pairs when ``with_masks``, so CV
    folds ride the same axis as plain grid points — are sharded over
    "lam"; node chunks over "node_chunk", with the halo exchange of
    ``_block_neighbor_sum_fn`` (``halos``: the kept block diagonals and
    their halo rows).  Fits AND scoring run inside the one program: per
    cell it returns (modified BIC on the in-mask data, held-out hinge on
    the mask complement), reduced over the node axis with psum.  Without
    masks the gradient skips the masking entirely (every sample counts;
    held-out hinge is 0).  ``m`` is the padded node count and ``m_real``
    (< m when padded) corrects every scoring mean for the all-zero ghost
    rows.

    Returns a jitted fn
      (X, y, (W_diag, W_off, node_mask), deg, cell_lams (C,),
       cell_rho (C, m), lam_weights (p,) [, cell_masks (C, m, n)])
      -> (path (C, m, p), scores (C, 2), iters (C,)).

    mode "batched": all local cells advance in lockstep (vmap), cold start,
    cfg.max_iter rounds — trajectories match the dense batched engine.
    mode "warm": sequential continuation over each device's local cell
    block with early stop on ``stop_rule`` ("kkt" residual or legacy
    "progress"), the statistic evaluated every ``check_every`` rounds
    (collective-safe inner scan — held rounds still run their
    collectives).  The halo exchange is a ppermute inside the while body,
    and XLA's CollectivePermute rendezvous spans the whole mesh, so the
    stop decision is pmax-agreed across BOTH axes (uniform trip counts
    mesh-wide; converged lam columns keep refining until all columns
    stop).  tools/meshcheck NONUNIFORM_STOP proves this at trace time.
    Continuation follows decreasing lambda; wherever lambda jumps back up
    (a full-data/fold block boundary under CV) the fit restarts cold.

    ``handoff`` (warm mode, lam axis > 1): after the first traversal each
    lam-shard ``ppermute``s its boundary solution (and its lambda) forward
    along "lam" and re-traverses its local block warm-started from the
    neighbouring shard — so continuation crosses shard boundaries exactly
    like the 1-D warm path.  Cells where continuation doesn't apply
    (shard 0, fold-block boundaries) reuse their first-sweep solution, so
    the refinement sweep early-stops almost immediately.
    """
    if mode not in ("warm", "batched"):
        raise ValueError(f"mode {mode!r} not in ('warm', 'batched')")
    if stop_rule not in ("kkt", "progress"):
        raise ValueError(f"stop_rule {stop_rule!r} not in ('kkt', 'progress')")
    nax = "node_chunk"
    nn, nl = mesh.shape[nax], mesh.shape["lam"]
    assert m % nn == 0, f"m={m} must be divisible by node axis={nn}"
    assert C % nl == 0, f"cells={C} must be divisible by lam axis={nl}"
    m_real = m if m_real is None else m_real

    def prog(Xl, yl, Wop, degl, cell_lams, cell_rho, lamw, cell_masks=None):
        Wd, Woff, nmask = Wop
        step = solver.make_step(
            cfg, _block_neighbor_sum_fn(nax, nn, Wd, Woff, halos))
        m_local, n, _ = Xl.shape
        C_local = cell_lams.shape[0]
        cells = ((cell_lams, cell_rho) if cell_masks is None
                 else (cell_lams, cell_rho, cell_masks))
        sdt = jnp.promote_types(Xl.dtype, jnp.float32)

        if mode == "batched":

            def fit_cell(B0, P0, prog0, lam, rhoc, maskc=None):
                prob = _padded_problem(Xl, yl, degl, rhoc, cfg, mask=maskc)
                state = solver.SolverState(B0, P0,
                                           jnp.zeros((), jnp.int32), prog0)
                final = solver.run_fixed_cached(step, prob, lam, lamw,
                                                num_iters=cfg.max_iter,
                                                state=state)
                return final.B, final.t

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

            def fit_from(B_init, lam, rhoc, maskc, t0=None):
                prob = _padded_problem(Xl, yl, degl, rhoc, cfg, mask=maskc)
                P0 = _pvary(jnp.zeros((m_local, p), sdt), (nax, "lam"))
                prog0 = _pvary(jnp.asarray(jnp.inf, sdt), (nax, "lam"))
                t_init = (jnp.zeros((), jnp.int32) if t0 is None
                          else jnp.asarray(t0, jnp.int32))
                state = solver.SolverState(B_init, P0, t_init, prog0)
                return solver.run_tol(step, prob, lam, lamw,
                                      max_iter=cfg.max_iter, tol=tol,
                                      state=state, residual_fn=residual_fn,
                                      axis_name=(nax, "lam"),
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
        hinge = (jnp.maximum(1.0 - margins, 0.0)
                 * nmask[None, :, None])                     # (C_local, m, n)
        if cell_masks is None:
            hinge_in = jax.lax.psum(jnp.sum(hinge, axis=(1, 2)), nax)
            n_in = jnp.asarray(N_total, f32)
            val_hinge = jnp.zeros((C_local,), f32)
        else:
            hinge_in = jax.lax.psum(
                jnp.sum(hinge * cell_masks, axis=(1, 2)), nax)
            val = (1.0 - cell_masks) * nmask[None, :, None]
            hinge_out = jax.lax.psum(jnp.sum(hinge * val, axis=(1, 2)),
                                     nax)
            n_out = jax.lax.psum(jnp.sum(val, axis=(1, 2)), nax)
            n_in = jax.lax.psum(jnp.sum(cell_masks, axis=(1, 2)), nax)
            val_hinge = hinge_out / jnp.maximum(n_out, 1.0)
        supp = jax.lax.psum(
            jnp.sum((jnp.abs(path) > 1e-8).astype(f32), axis=(1, 2)),
            nax)
        bic = (hinge_in / n_in
               + math.sqrt(math.log(N_total)) * math.log(p)
               * (supp / m_real) / N_total)
        scores = jnp.stack([bic, val_hinge], axis=-1)        # (C_local, 2)
        return path, scores, iters

    base_specs = (P(nax), P(nax), (P(nax), P(nax), P(nax)), P(nax),
                  P("lam"), P("lam", nax), P())
    in_specs = base_specs + ((P("lam", nax),) if with_masks else ())
    fn = jax.shard_map(
        prog, mesh=mesh, in_specs=in_specs, check_vma=False,
        out_specs=(P("lam", nax), P("lam"), P("lam")))
    return jax.jit(fn)


def decsvm_path_mesh(X: Array, y: Array, W, lams, cfg: ADMMConfig,
                     mesh: Optional[Mesh] = None, mode: str = "batched",
                     tol: float = 1e-6,
                     lam_weights: Optional[Array] = None,
                     stop_rule: str = "kkt", criterion: str = "bic",
                     cv_folds: int = 5, cv_seed: int = 0,
                     check_every: int = 4, handoff: bool = True):
    """Lambda path on the 2-D (node_chunk, lam) device mesh, with selection.

    The L-point grid is sharded over the "lam" mesh axis; with
    ``criterion="cv"`` the k-fold train masks join the grid as extra
    cells — L*(1+k) cells total — so full-data fits, fold fits, and both
    scoring rules run inside one shard_map program.  Returns
    ``repro.core.path.PathResult`` whose ``criteria`` is the selected
    rule's score per grid point.

    Warm mode evaluates the stop statistic every ``check_every`` rounds
    and, with ``handoff`` (default), ppermutes each lam-shard's boundary
    solution forward so continuation matches the 1-D warm path across
    shard boundaries (see ``build_mesh_path``).

    Requires #cells % lam-axis == 0.  Any m: the tail chunk pads with
    exact-no-op ghost nodes and every score is corrected to the real node
    count.  ``W`` may be a dense (m, m) adjacency or a
    ``graph.BlockTopology``; its device operands come from
    ``_chunk_prep``.  cfg.lam is ignored (the grid supplies lambda).
    """
    from repro.core.path import PathResult  # local import: avoid cycle

    sanitize.reject_unsupported(cfg, "decsvm_path_mesh")
    m, n, p = X.shape
    lams = np.asarray(lams, np.float32)
    L = len(lams)
    if criterion not in ("bic", "cv"):
        raise ValueError(f"criterion {criterion!r} not in ('bic', 'cv')")
    C = L * (1 + cv_folds) if criterion == "cv" else L

    if mesh is None:
        from repro.launch.mesh import make_chunk_lam_mesh
        mesh = make_chunk_lam_mesh(*_choose_mesh_shape(
            C, len(jax.devices())))
    # rho per node on the unplaced fp32 X; a ghost node's is 0, as the
    # power iteration gives on its all-zero rows
    X = jnp.asarray(X, jnp.float32)
    rho_full = solver.compute_rho(X, cfg.h, cfg.kernel, cfg.rho_safety,
                                  backend=solver.resolve_backend(cfg))
    ops, halos, m_work = _chunk_prep(X, y, W, mesh)
    ghost = m_work - m
    rho_full = jnp.pad(rho_full, (0, ghost))
    if criterion == "cv":
        from repro.core.tuning import kfold_masks  # local: avoid cycle
        folds = np.asarray(kfold_masks(m, n, cv_folds, seed=cv_seed))
        fold_rho = _fold_rhos(X, jnp.asarray(folds, X.dtype), cfg.h,
                              cfg.kernel, cfg.rho_safety)     # (k, m)
        fold_rho = jnp.pad(fold_rho, ((0, 0), (0, ghost)))
        folds = np.concatenate(             # ghost rows: mask 0 everywhere
            [folds, np.zeros((cv_folds, ghost, n), folds.dtype)], axis=1)
        real = (np.arange(m_work) < m).astype(np.float32)
        ones = np.broadcast_to(real[None, :, None], (L, m_work, n))
        cell_masks = jnp.asarray(np.concatenate(
            [ones] + [np.broadcast_to(f, (L, m_work, n)) for f in folds]),
            X.dtype)
        cell_lams = np.concatenate([lams] * (1 + cv_folds))
        cell_rho = jnp.concatenate(
            [jnp.broadcast_to(rho_full, (L, m_work))]
            + [jnp.broadcast_to(r, (L, m_work)) for r in fold_rho])
    else:
        cell_masks, cell_lams = None, lams
        cell_rho = jnp.broadcast_to(rho_full, (L, m_work))
    assert C == len(cell_lams)

    # X narrows to the backend's compute dtype only now — rho (above) and
    # the scoring operands stay fp32
    cells_s = NamedSharding(mesh, P("lam", "node_chunk"))
    operands = [ops["X"].astype(solver.problem_dtype(cfg)), ops["y"],
                (ops["W_diag"], ops["W_off"], ops["nmask"]), ops["deg"],
                jax.device_put(jnp.asarray(cell_lams, jnp.float32),
                               NamedSharding(mesh, P("lam"))),
                jax.device_put(cell_rho, cells_s),
                _lamw(lam_weights, p, jnp.float32)]
    if cell_masks is not None:
        operands.append(jax.device_put(cell_masks, cells_s))

    fitted = build_mesh_path(m_work, p, C, cfg, mesh, mode, tol, stop_rule,
                             with_masks=cell_masks is not None,
                             check_every=check_every, handoff=handoff,
                             halos=halos, m_real=m)
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


def _choose_mesh_shape(C: int, ndev: int):
    """Pick (node_chunk, lam) axis sizes: use every device, maximize
    balance.  Any m pads its tail chunk, so only the cell count restricts
    the split."""
    best = None
    for nn in range(1, ndev + 1):
        if ndev % nn:
            continue
        nl = ndev // nn
        if C % nl:
            continue
        key = (min(nn, nl), nl)        # balanced first, then grid-parallel
        if best is None or key > best[0]:
            best = (key, (nn, nl))
    return best[1]
