"""The single home of Algorithm 1: one state pytree, one per-node update,
one traced-``(lam, lam_weights)`` step, pluggable everything else.

Every solver surface in this repo — ``admm.decsvm_fit`` (dense),
``admm_adaptive.decsvm_fit_tol`` / ``decsvm_fit_uneven``,
``path.decsvm_path_batched`` / ``decsvm_path_warm`` (lambda grid),
``decentral.decsvm_fit_sharded`` / ``decsvm_path_sharded`` /
``decsvm_path_mesh`` (shard_map engines), the LLA stage-2 re-fit in
``penalties``, and the Pallas oracle in ``kernels.ref`` — is a thin driver
over this module.  The update math exists exactly once
(``local_update`` and the ``soft_threshold(omega * z, ...)`` line inside
it), so the engines are the same algorithm *by construction*; the parity
suite (``tests/test_solver.py``) checks the drivers, not per-pair math.

Pluggable pieces of ``make_step``:

- **neighbour sum** (callable ``B -> (m, p)``): dense ``W @ B``
  (single process), ``all_gather`` + local adjacency rows (sharded, any
  graph), or ``ppermute`` of shard-boundary rows (sharded ring).  The
  step calls it twice per round — once for the primal update, once for
  the dual — exactly update (7a')/(7b).
- **local-gradient backend**: the jnp reference (``local_update``,
  optionally sample-masked for uneven n / cross-validation folds) or the
  fused Pallas TPU kernel (``kernels.ops.csvm_local_update``).  Under
  ``backend="auto"`` every product over X (round, KKT check, power
  iteration) is ``node_xtphi``, which picks by shape between XLA's two
  HIGHEST contractions and the one-pass kernel ``kernels.ops.xpass``.

Update (per node l, with deg_l = |N(l)|):
    grad_l = (1/n) sum_i L_h'(y_i x_i' b_l) y_i x_i
    z_l    = rho_l b_l - grad_l - p_l + tau * (deg_l * b_l + (W B)_l)
    b+_l   = S_{lam * w_l}( w_l * z_l ),   w_l = 1/(2 tau deg_l + rho_l + lam0)
    p+_l   = p_l + tau * (deg_l * b+_l - (W B+)_l)
"""
from __future__ import annotations

import functools
import warnings
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import losses, trace

Array = jax.Array


# fp32 contractions.  On the TPU an f32 dot at default precision rounds its
# operands to bf16: at the paper's Section 4.1 design that moved a 300-round
# fit by 5.7e-2 (max |dB|, v5e) and changed the lambda the BIC selects.  Every
# contraction of the algorithm runs at HIGHEST, which is plain fp32 on the
# CPU as well.
F32 = jax.lax.Precision.HIGHEST


def mm(a: Array, b: Array) -> Array:
    """``a @ b`` in full fp32 on every backend."""
    return jnp.matmul(a, b, precision=F32)


def soft_threshold(v: Array, t) -> Array:
    """Coordinate-wise soft-thresholding S_t(v)."""
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


# A node's X block above this many bytes streams through the one-pass kernel
# (``kernels/xpass.py``) under backend "auto" on a TPU.  On a v5e the kernel
# took half the time of XLA's pair of fusions at every block of a sweep over
# n at p=2001, from 1.6 MB (n=200) to 320 MB (PERF.md); the threshold sits
# below that sweep and above the 0.4 MB blocks of the paper's own design,
# whose latency-bound path keeps XLA's fusions.
XPASS_MIN_BYTES = 2**20


def _platform() -> str:
    return jax.default_backend()


def xpass_applies(backend: str, X: Array, mask: Optional[Array]) -> bool:
    """The shape rule: backend "auto", a TPU, no sample mask, an fp32 node
    block (n, p) over ``XPASS_MIN_BYTES`` whose p fits the kernel's VMEM."""
    if not (backend == "auto" and mask is None and _platform() == "tpu"
            and X.dtype == jnp.float32
            and X.shape[-2] * X.shape[-1] * 4 > XPASS_MIN_BYTES):
        return False
    from repro.kernels import xpass
    return xpass.supported(X.shape[-1])


def node_xtphi(X: Array, y: Optional[Array], v: Array, *, weight: str,
               h: float = 0.0, kernel: str = "epanechnikov",
               mask: Optional[Array] = None, backend: str = "jnp") -> Array:
    """X' phi(X v) for one node (X (n, p), y (n,), v (p,)): the one home of
    the products over X, in the round (``local_update``), the KKT check
    (``kkt_residual``) and the power iteration (``power_iteration_lmax``).

    ``weight="loss"``: phi(u)_i = L_h'(y_i u_i) y_i / n, the smoothed-loss
    gradient; ``weight="linear"``: phi(u) = u / n.  A sample ``mask`` drops
    its rows and n becomes their count.  Where ``xpass_applies`` the
    product is one read of X by the ``decsvm_xpass`` kernel (vmapped over
    nodes by ``pallas_call``'s batching rule), else two HIGHEST ``mm``.
    """
    if xpass_applies(backend, X, mask):
        from repro.kernels import ops
        y = jnp.zeros(X.shape[:1], X.dtype) if y is None else y
        return ops.xpass(X[None], y[None], v[None], weight=weight, h=h,
                         kernel=kernel)[0]
    u = mm(X, v)
    if weight == "loss":
        w = losses.get_kernel(kernel).dloss(y * u, h) * y
    else:
        w = u
    if mask is None:
        n_eff = X.shape[0]
    else:
        w = w * mask
        n_eff = jnp.maximum(jnp.sum(mask), 1.0)
    return mm(X.T, w) / n_eff


def power_iteration_lmax(X: Array, iters: int = 50,
                         backend: str = "jnp") -> Array:
    """Largest eigenvalue of X'X/n, matrix-free (X: (n, p)).

    The start vector is seeded deterministically from the operand *shape*
    (not an implicit global key, and not a constant vector — the old
    all-equal start is orthogonal to any leading eigenvector with zero
    coordinate sum, where the Rayleigh quotient silently returned ~0 and
    ``compute_rho`` under-regularized).  Iterations guard the normalization
    so a degenerate node shard (all-zero rows, e.g. a fully-masked CV
    block) yields lmax = 0 instead of NaN.  Each step is one
    ``node_xtphi`` under ``backend``.
    """
    n, p = X.shape
    key = jax.random.PRNGKey(n * 1000003 + p)
    v = jax.random.normal(key, (p,), jnp.float32).astype(X.dtype)
    v = v / jnp.linalg.norm(v)
    gram = functools.partial(node_xtphi, X, None, weight="linear",
                             backend=backend)

    def body(v, _):
        w = gram(v)
        nrm = jnp.linalg.norm(w)
        safe = jnp.where(nrm > 0.0, nrm, 1.0)
        return jnp.where(nrm > 0.0, w / safe, v), None

    v, _ = jax.lax.scan(body, v, None, length=iters)
    w = gram(v)
    vv = jnp.vdot(v, v, precision=F32)
    return jnp.where(vv > 0.0, jnp.vdot(v, w, precision=F32)
                     / jnp.where(vv > 0.0, vv, 1.0), 0.0)


@functools.partial(jax.jit, static_argnames=("h", "kernel", "safety",
                                             "backend"))
def compute_rho(X: Array, h: float, kernel: str, safety: float = 1.05,
                mask: Optional[Array] = None, backend: str = "jnp") -> Array:
    """rho_l >= c_h * Lmax(X_l'X_l/n_l) per node.  X: (m, n, p).

    With a sample ``mask`` (m, n), masked rows are zeroed and n_l is the
    per-node mask sum (the uneven-n extension of Section 2.1).  ``backend``
    is the resolved ``cfg.backend``: under "auto" an unmasked power
    iteration may take the one-pass kernel (``node_xtphi``).

    Jitted (h/kernel/safety/backend static): the eager vmap-of-scan
    dispatch used to miss the executable cache and recompile on every
    host-side call — the sharded/mesh drivers paid one XLA compile per fit
    even when the lru-cached program builders all hit (caught by the
    compile-guard trace contract in tests/test_solver.py).
    """
    c_h = losses.get_kernel(kernel).lipschitz(h)
    with jax.named_scope(trace.RHO):
        if mask is None:
            lmax = jax.vmap(functools.partial(power_iteration_lmax,
                                              backend=backend))(X)
        else:
            Xm = X * mask[..., None]

            def node_lmax(Xl, ml):
                return power_iteration_lmax(Xl) * Xl.shape[0] / jnp.maximum(
                    jnp.sum(ml), 1.0)

            lmax = jax.vmap(node_lmax)(Xm, mask)
        return safety * c_h * lmax


class SolverState(NamedTuple):
    """Algorithm-1 iterate: shared by every driver in the repo."""
    B: Array          # (m, p) primal node estimates (local block when sharded)
    P: Array          # (m, p) accumulated duals  p_l = sum_k (u_lk + v_lk)
    t: Array          # ()     iteration counter
    progress: Array   # ()     stop statistic: max|B_t - B_{t-1}| (or a
    #                          residual substituted by ``run_tol``)


class Problem(NamedTuple):
    """Static per-fit data: node-local design blocks plus the precomputed
    per-node scalars of update (7a').  ``mask`` (m, n) marks real samples
    for uneven-n / cross-validation fits; None means every row counts."""
    X: Array                     # (m, n, p)
    y: Array                     # (m, n)
    deg: Array                   # (m,)
    rho: Array                   # (m,)
    omega: Array                 # (m,)
    mask: Optional[Array] = None


# Backends of the local update / round, selected by ``cfg.backend``:
#   "jnp"             the reference vmapped ``local_update``
#   "pallas"          the two-pass fused kernel, vmapped over nodes
#   "megakernel"      whole-round fused kernel (fp32 compute)
#   "megakernel_bf16" same, X and MXU operands bf16; accumulators fp32
# "auto" defers to the legacy ``use_pallas`` flag; without it, it is
# ``local_update`` with each product over X picked by shape
# (``xpass_applies``): the one-pass kernel for a large block on a TPU.
MEGAKERNEL_BACKENDS = ("megakernel", "megakernel_bf16")
BACKENDS = ("auto", "jnp", "pallas") + MEGAKERNEL_BACKENDS


def resolve_backend(cfg, use_pallas: Optional[bool] = None) -> str:
    """Normalize ``cfg.backend`` (+ the legacy use_pallas override)."""
    backend = getattr(cfg, "backend", "auto").replace("-", "_")
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    if backend == "auto":
        pallas = cfg.use_pallas if use_pallas is None else use_pallas
        return "pallas" if pallas else "auto"
    return backend


def problem_dtype(cfg):
    """Compute dtype for X (the mixed-precision knob): bf16 only under the
    megakernel_bf16 backend; accumulators stay fp32 regardless."""
    if resolve_backend(cfg) == "megakernel_bf16":
        return jnp.bfloat16
    return jnp.float32


def make_problem(X: Array, y: Array, W: Array, cfg,
                 mask: Optional[Array] = None,
                 rho: Optional[Array] = None) -> Problem:
    """Assemble a ``Problem`` from stacked node blocks and the adjacency.

    rho/omega are always computed in the incoming (fp32) precision; X is
    cast to the backend's compute dtype *afterwards*, so the bf16 mode
    changes only the per-round matmul operands, never the step sizes.
    """
    deg = jnp.sum(W, axis=1)
    if rho is None:
        rho = compute_rho(X, cfg.h, cfg.kernel, cfg.rho_safety, mask=mask,
                          backend=resolve_backend(cfg))
    omega = 1.0 / (2.0 * cfg.tau * deg + rho + cfg.lam0)
    return Problem(X.astype(problem_dtype(cfg)), y, deg, rho, omega, mask)


def local_update(X: Array, y: Array, beta: Array, p_dual: Array,
                 neigh_term: Array, rho, omega, lam_vec, *, h: float,
                 kernel: str, mask: Optional[Array] = None,
                 backend: str = "jnp") -> Array:
    """THE Algorithm-1 primal update (7a') for a single node.

    X: (n, p), y: (n,), beta/p_dual/neigh_term: (p,); rho/omega scalars;
    lam_vec a scalar or (p,) per-coordinate l1 level; ``neigh_term`` is the
    precomputed  tau * (deg_l * beta_l + sum_{k in N(l)} beta_k).
    This function (and the fused Pallas kernel validated against it) is the
    only place the update's math lives; its gradient is ``node_xtphi``.
    """
    grad = node_xtphi(X, y, beta, weight="loss", h=h, kernel=kernel,
                      mask=mask, backend=backend)
    z = rho * beta - grad - p_dual + neigh_term
    return soft_threshold(omega * z, lam_vec * omega)


def make_step(cfg, neighbor_sum: Callable[[Array], Array], *,
              use_pallas: Optional[bool] = None,
              W: Optional[Array] = None):
    """Build one traced-``(lam, lam_weights)`` Algorithm-1 round.

    ``neighbor_sum(B) -> (m, p)`` supplies  (W B)_l = sum_{k in N(l)} b_k
    for the node rows the caller holds (all of them in the dense engine, a
    shard inside ``shard_map``).  The local-update backend comes from
    ``cfg.backend`` (``resolve_backend``): the jnp reference, the two-pass
    Pallas kernel (``use_pallas`` is the legacy override), or the round
    megakernel (fp32 / bf16-compute).

    Dense drivers additionally pass the adjacency ``W`` itself: under a
    megakernel backend the returned step then carries a ``step.round_block``
    attribute — ``round_block(prob, state, lam, lam_weights, num_rounds=,
    rounds_active=, want_kkt=)`` running k fused rounds (and the KKT stop
    statistic) in ONE kernel launch, which ``run_fixed``/``run_tol`` use as
    their fast path.  Sharded engines (no dense W) get the fused
    block-update kernel per round with their collectives in between.

    Returns ``step(prob, state, lam, lam_weights=None) -> SolverState``
    with lam a traced scalar and lam_weights an optional traced (p,)
    per-coordinate multiplier (adaptive/SCAD/MCP via one-step LLA).
    """
    tau, h, kernel = cfg.tau, cfg.h, cfg.kernel
    backend = resolve_backend(cfg, use_pallas)

    def _lam_vec(lam, lam_weights, p_dim):
        if lam_weights is None:
            return jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (p_dim,))
        return jnp.asarray(lam * lam_weights, jnp.float32)

    def _primal(prob, B, P, neigh_term, lam_vec):
        """B_new via the selected backend.  The fused kernels have no
        sample-mask operand: masked fits (uneven n, CV folds) must take the
        jnp reference backend or held-out rows would silently count as real
        samples."""
        if backend == "pallas" and prob.mask is None:
            from repro.kernels import ops  # lazy: kernels dep is optional here
            return jax.vmap(
                lambda Xl, yl, bl, pl_, nl, rl, wl: ops.csvm_local_update(
                    Xl, yl, bl, pl_, nl, rl, wl, lam_vec, h=h, kernel=kernel)
            )(prob.X, prob.y, B, P, neigh_term, prob.rho, prob.omega)
        if backend in MEGAKERNEL_BACKENDS and prob.mask is None:
            from repro.kernels import ops
            if _fits_megakernel(prob.X):
                return ops.csvm_block_update(
                    prob.X, prob.y, B, P, neigh_term, prob.rho, prob.omega,
                    lam_vec, h=h, kernel=kernel)
        in_axes = (0, 0, 0, 0, 0, 0, 0, None)
        args = (prob.X, prob.y, B, P, neigh_term, prob.rho, prob.omega,
                lam_vec)
        if prob.mask is None:
            return jax.vmap(
                lambda *a: local_update(*a, h=h, kernel=kernel,
                                        backend=backend),
                in_axes=in_axes)(*args)
        return jax.vmap(
            lambda *a: local_update(*a[:-1], h=h, kernel=kernel, mask=a[-1]),
            in_axes=in_axes + (0,))(*args, prob.mask)

    def step(prob: Problem, state: SolverState, lam,
             lam_weights: Optional[Array] = None) -> SolverState:
        with jax.named_scope(trace.ROUND):
            B, P = state.B, state.P
            neigh_term = tau * (prob.deg[:, None] * B + neighbor_sum(B))
            lam_vec = _lam_vec(lam, lam_weights, B.shape[-1])
            B_new = _primal(prob, B, P, neigh_term, lam_vec)
            P_new = P + tau * (prob.deg[:, None] * B_new
                               - neighbor_sum(B_new))
            return SolverState(B_new, P_new, state.t + 1,
                               jnp.max(jnp.abs(B_new - B)))

    def cached_round(prob: Problem, state: SolverState, S, lam,
                     lam_weights: Optional[Array] = None):
        """One round with ``S = neighbor_sum(state.B)`` supplied by the
        caller: the dual update's exchange of B_new IS the next round's
        primal exchange of B, so carrying it across rounds
        (``run_fixed_cached``) halves the neighbour exchanges per round
        — the collectives, in the sharded/chunked engines — at
        bit-identical math (same values through the same ops)."""
        with jax.named_scope(trace.ROUND):
            B, P = state.B, state.P
            neigh_term = tau * (prob.deg[:, None] * B + S)
            lam_vec = _lam_vec(lam, lam_weights, B.shape[-1])
            B_new = _primal(prob, B, P, neigh_term, lam_vec)
            S_new = neighbor_sum(B_new)
            P_new = P + tau * (prob.deg[:, None] * B_new - S_new)
            return SolverState(B_new, P_new, state.t + 1,
                               jnp.max(jnp.abs(B_new - B))), S_new

    step.cached_round = cached_round
    step.neighbor_sum = neighbor_sum

    if getattr(cfg, "sanitize", False):
        # Wrap with the E1-E6 term checks and do NOT attach round_block:
        # the fused megakernel hides exactly the per-term dataflow the
        # sanitizer localizes, so sanitizing runs take the streaming
        # per-round path (checks compose through scan/while there).  The
        # False branch returns the step entirely untouched — that is the
        # bit-identity contract tests/test_sanitize.py pins.
        from repro.core import sanitize
        return sanitize.checked_step(step, cfg, neighbor_sum)

    if backend in MEGAKERNEL_BACKENDS and W is not None:

        def round_block(prob, state, lam, lam_weights, *, num_rounds: int,
                        rounds_active, want_kkt: bool) -> SolverState:
            """``num_rounds`` fused rounds in one megakernel launch; the
            first ``rounds_active`` (traced, <= num_rounds) advance the
            iterate, the rest are held.  ``state.progress`` returns as the
            KKT residual (``want_kkt``) or the last active round's max|dB|.
            Falls back to an equivalent scan of single rounds when the
            problem is masked or exceeds the VMEM residency budget."""
            from repro.kernels import ops
            with jax.named_scope(trace.ROUND):
                lam_vec = _lam_vec(lam, lam_weights, state.B.shape[-1])
                if prob.mask is None and _fits_megakernel(prob.X):
                    Bn, Pn, stat = ops.csvm_round_block(
                        prob.X, prob.y, state.B, state.P, W, prob.deg,
                        prob.rho, prob.omega, lam_vec, rounds_active, tau=tau,
                        lam0=cfg.lam0, h=h, kernel=kernel,
                        num_rounds=num_rounds, want_kkt=want_kkt)
                    t_new = state.t + jnp.asarray(rounds_active,
                                                  state.t.dtype)
                    return SolverState(Bn, Pn, t_new, stat)

                def inner(s, i):
                    stepped = step(prob, s, lam, lam_weights)
                    held = jax.tree.map(
                        lambda a, b: jnp.where(i < rounds_active, a, b),
                        stepped, s)
                    return held, None

                new, _ = jax.lax.scan(inner, state,
                                      jnp.arange(num_rounds))
                if want_kkt:
                    stat = kkt_residual(prob, cfg, new.B, lam,
                                        lam_weights)
                    return new._replace(progress=stat)
                return new

        step.round_block = round_block

    return step


def _fits_megakernel(X: Array) -> bool:
    """The megakernel's VMEM residency check, said aloud when it fails: the
    caller then runs the streaming jnp rounds instead of the kernel."""
    from repro.kernels import ops
    if ops.megakernel_supported(*X.shape, X.dtype):
        return True
    _warn_megakernel_fallback(tuple(X.shape), jnp.dtype(X.dtype).name)
    return False


@functools.lru_cache(maxsize=None)
def _warn_megakernel_fallback(shape, dtype: str) -> None:
    from repro.kernels import ops
    need = ops.megakernel_vmem_bytes(*shape, jnp.dtype(dtype).itemsize)
    budget = ops.megakernel_budget()
    warnings.warn(
        f"megakernel backend: X {dtype}{shape} needs {need / 2**20:.1f} MiB "
        f"of VMEM, over the {budget / 2**20:.0f} MiB residency budget; "
        "running the streaming jnp rounds instead", stacklevel=2)


def init_state(prob: Problem, B0: Optional[Array] = None,
               P0: Optional[Array] = None) -> SolverState:
    """Accumulators (B, P, progress) live in fp32 even when X is bf16 —
    the mixed-precision discipline keeps state exact across rounds."""
    m, _, p = prob.X.shape
    dt = jnp.promote_types(prob.X.dtype, jnp.float32)
    B = jnp.zeros((m, p), dt) if B0 is None else B0
    P = jnp.zeros_like(B) if P0 is None else P0
    return SolverState(B, P, jnp.zeros((), jnp.int32),
                       jnp.asarray(jnp.inf, dt))


def run_fixed(step, prob: Problem, lam, lam_weights=None, *,
              num_iters: int, state: Optional[SolverState] = None,
              track_history: bool = False):
    """Drive ``step`` for a fixed number of rounds (lax.scan).

    Returns the final ``SolverState``; with ``track_history`` also the
    (T, m, p) iterate history.

    When ``step`` carries the megakernel's ``round_block`` (dense drivers
    under a megakernel backend) and no history is requested, the whole run
    is ONE kernel launch — the fori-loop over rounds lives on-chip.
    """
    state = init_state(prob) if state is None else state
    round_block = getattr(step, "round_block", None)
    if round_block is not None and not track_history and num_iters > 0:
        return round_block(prob, state, lam, lam_weights,
                           num_rounds=num_iters, rounds_active=num_iters,
                           want_kkt=False)

    def body(state, _):
        new = step(prob, state, lam, lam_weights)
        return new, (new.B if track_history else None)

    final, hist = jax.lax.scan(body, state, None, length=num_iters)
    if track_history:
        return final, hist
    return final


def run_fixed_cached(step, prob: Problem, lam, lam_weights=None, *,
                     num_iters: int,
                     state: Optional[SolverState] = None) -> SolverState:
    """``run_fixed`` through ``step.cached_round``: the neighbour sum of
    the current iterate rides the scan carry, so every round pays ONE
    neighbour exchange instead of two.  Bit-identical to ``run_fixed``
    (the cached value is exactly what the second exchange would
    recompute); the win is the halved collective count in the
    sharded/chunked engines, where an exchange is a ``ppermute`` chain.
    Falls back to ``run_fixed`` when ``step`` carries no ``cached_round``
    (e.g. the sanitizer-wrapped step)."""
    cached = getattr(step, "cached_round", None)
    if cached is None:
        return run_fixed(step, prob, lam, lam_weights, num_iters=num_iters,
                         state=state)
    state = init_state(prob) if state is None else state

    def body(carry, _):
        s, S = carry
        new, S_new = cached(prob, s, S, lam, lam_weights)
        return (new, S_new), None

    S0 = step.neighbor_sum(state.B)
    (final, _), _ = jax.lax.scan(body, (state, S0), None, length=num_iters)
    return final


def run_tol(step, prob: Problem, lam, lam_weights=None, *, max_iter: int,
            tol: float, state: Optional[SolverState] = None,
            residual_fn=None, axis_name: Optional[str] = None,
            check_every: int = 1) -> SolverState:
    """Drive ``step`` until ``max_iter`` OR the stop statistic <= tol.

    The default statistic is iterate progress max|B_t - B_{t-1}|;
    ``residual_fn(prob, state, lam, lam_weights)`` substitutes e.g. the
    KKT residual (``kkt_residual``).  Inside ``shard_map``, pass
    ``axis_name`` (one axis or a tuple) so every shard in the group
    agrees on the stop decision: the whole continue-flag — not just the
    statistic — is pmax-reduced and carried through the loop, so shards
    whose (t, statistic) differ still trip-count in lockstep (any body
    collectives keep rendezvousing).  A shard past its own budget holds
    its rounds (collectives still execute); a shard below tol keeps
    refining until the whole group stops.  When (t, statistic) are
    group-uniform — every dense/1-axis driver — this is bit-identical
    to a local stop decision.

    ``check_every=k`` evaluates the stop statistic only after every k-th
    round: each while-iteration runs an inner k-step scan (rounds past
    ``max_iter`` are held, so the iterate never overshoots) and then one
    statistic evaluation, so stopping can only happen on a *measured*
    value, at rounds k, 2k, ....  With the KKT rule the statistic costs
    a full network-gradient evaluation, so k>1 removes that per-round
    overhead — including under ``vmap`` (a ``lax.cond`` would lower to
    ``select`` there and evaluate the residual every round anyway).
    The inner scan is collective-safe: held rounds still execute their
    collectives unconditionally (``jnp.where`` on the results, never a
    ``lax.cond`` around them), so sharded drivers can run k>1 too.

    When ``step`` carries the megakernel's ``round_block`` and the
    statistic is the KKT residual (or plain progress), each k-round block
    plus its statistic is ONE fused kernel launch.
    """
    state = init_state(prob) if state is None else state

    def _flag(s):
        """Continue?  Collectively agreed across ``axis_name`` so body
        collectives stay aligned (no group member may exit early)."""
        f = (s.t < max_iter) & (s.progress > tol)
        if axis_name is not None:
            f = jax.lax.pmax(f.astype(jnp.int32), axis_name) > 0
        return f

    def cond(carry):
        return carry[1]

    def stat(new):
        if residual_fn is not None:
            return residual_fn(prob, new, lam, lam_weights)
        return new.progress

    round_block = getattr(step, "round_block", None)
    use_fused = (round_block is not None and axis_name is None
                 and prob.mask is None
                 and (residual_fn is None
                      or getattr(residual_fn, "kind", None) == "kkt"))

    def fused_body(carry):
        state = carry[0]
        nact = jnp.minimum(check_every, max_iter - state.t)
        new = round_block(prob, state, lam, lam_weights,
                          num_rounds=check_every, rounds_active=nact,
                          want_kkt=residual_fn is not None)
        return new, _flag(new)

    def body(carry):
        state = carry[0]
        if check_every > 1:
            def inner(s, _):
                stepped = step(prob, s, lam, lam_weights)
                held = jax.tree.map(
                    lambda a, b: jnp.where(s.t < max_iter, a, b), stepped, s)
                return held, None

            new, _ = jax.lax.scan(inner, state, None, length=check_every)
        else:
            stepped = step(prob, state, lam, lam_weights)
            new = (stepped if axis_name is None else jax.tree.map(
                lambda a, b: jnp.where(state.t < max_iter, a, b),
                stepped, state))
        new = new._replace(progress=stat(new))
        if axis_name is not None:
            new = new._replace(
                progress=jax.lax.pmax(new.progress, axis_name))
        return new, _flag(new)

    final, _ = jax.lax.while_loop(cond, fused_body if use_fused else body,
                                  (state, _flag(state)))
    return final


def kkt_residual_fn(cfg, axis_name: Optional[str] = None,
                    node_mask: Optional[Array] = None):
    """Adapter factory: the ``residual_fn`` shape ``run_tol`` expects,
    closing over cfg (and the mesh axis for sharded drivers).  Shared by
    every KKT-stopping driver so the adapter exists once.  ``fn.kind``
    tags the statistic so ``run_tol`` knows the megakernel's in-pass KKT
    epilogue computes the same quantity and may fuse it.  ``node_mask``
    (per-row validity, for the chunked engine's padded ghost nodes) may
    be a traced shard — the closure keeps it row-aligned with B."""
    def fn(prob, state, lam, lam_weights):
        return kkt_residual(prob, cfg, state.B, lam, lam_weights,
                            axis_name=axis_name, node_mask=node_mask)
    fn.kind = "kkt"
    if getattr(cfg, "sanitize", False):
        from repro.core import sanitize
        return sanitize.checked_residual(fn, cfg)
    return fn


def kkt_residual(prob: Problem, cfg, B: Array, lam,
                 lam_weights: Optional[Array] = None, *,
                 axis_name: Optional[str] = None,
                 node_mask: Optional[Array] = None) -> Array:
    """KKT/duality-gap stop statistic for the network problem (eq. 3/4).

    Measures actual optimality of the network-average iterate rather than
    how fast the iterate is moving (the old progress rule stops whenever
    the iterate crawls — even far from the optimum, the ROADMAP's
    warm-path-deviates failure mode):

      stationarity: the unit-step prox-gradient fixed-point residual at
        beta_bar = mean_l b_l,
          max_j | beta_bar_j - S_{lam_j}(beta_bar_j - g_j) |,
        with g the network-mean smoothed-loss gradient plus
        lam0 * beta_bar.  Zero exactly at a KKT point of eq. (3)/(4)
        (summing the node stationarity conditions cancels the duals:
        sum_l p_l = 0 every round), and — unlike the raw subgradient
        residual — continuous in beta_bar, so consensus noise on a
        truly-zero coordinate cannot inflate it by O(lam);
      consensus:  max_l |b_l - beta_bar|.

    Returns max(stationarity, consensus).  Inside ``shard_map`` pass the
    node ``axis_name``; node means/maxes then reduce over the mesh axis.
    ``node_mask`` (0/1 per row of B) restricts every node mean/max to
    real nodes — the chunked engine's zero-padded ghost rows carry zero
    grads and zero B but must not dilute the network means.
    """
    with jax.named_scope(trace.KKT_CHECK):
        if node_mask is not None:
            nm = node_mask.astype(B.dtype)
            b_sum = jnp.sum(B * nm[:, None], axis=0)
            n_real = jnp.sum(nm)
            if axis_name is not None:
                b_sum = jax.lax.psum(b_sum, axis_name)
                n_real = jax.lax.psum(n_real, axis_name)
            beta_bar = b_sum / n_real
        else:
            local_mean = jnp.mean(B, axis=0)
            beta_bar = (local_mean if axis_name is None
                        else jax.lax.pmean(local_mean, axis_name))

        def node_grad(Xl, yl, ml):
            return node_xtphi(Xl, yl, beta_bar, weight="loss", h=cfg.h,
                              kernel=cfg.kernel, mask=ml,
                              backend=resolve_backend(cfg))

        if prob.mask is None:
            grads = jax.vmap(lambda Xl, yl: node_grad(Xl, yl, None))(
                prob.X, prob.y)
        else:
            grads = jax.vmap(node_grad)(prob.X, prob.y, prob.mask)
        if node_mask is not None:
            g_sum = jnp.sum(grads * nm[:, None], axis=0)
            if axis_name is not None:
                g_sum = jax.lax.psum(g_sum, axis_name)
            g = g_sum / n_real
        else:
            g_local = jnp.mean(grads, axis=0)
            g = (g_local if axis_name is None
                 else jax.lax.pmean(g_local, axis_name))
        g = g + cfg.lam0 * beta_bar
        p_dim = beta_bar.shape[-1]
        if lam_weights is None:
            lam_vec = jnp.broadcast_to(jnp.asarray(lam, beta_bar.dtype),
                                       (p_dim,))
        else:
            lam_vec = lam * lam_weights
        stat = jnp.abs(beta_bar - soft_threshold(beta_bar - g, lam_vec))
        dev = jnp.abs(B - beta_bar[None, :])
        if node_mask is not None:
            dev = dev * nm[:, None]
        cons_local = jnp.max(dev)
        cons = (cons_local if axis_name is None
                else jax.lax.pmax(cons_local, axis_name))
        return jnp.maximum(jnp.max(stat), cons)
