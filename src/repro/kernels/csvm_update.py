"""Pallas TPU kernel for the fused deCSVM ADMM local update (eq. 7a').

The update is a matvec chain  margin -> L_h' weight -> X^T w -> soft-threshold.
Arithmetic intensity is ~2 flops per element of X read twice from HBM, i.e.
firmly memory-bound on TPU (197 TFLOP/s vs 819 GB/s); the kernel's job is to
stream X through VMEM exactly twice with no intermediate HBM round-trips:

  pass 1 (grid n_tiles x p_tiles, p fastest): accumulate X @ beta into the
         margin vector, epilogue turns it into w = L_h'(y*margin) * y / n;
  pass 2 (grid p_tiles x n_tiles, n fastest): accumulate X^T w, epilogue
         applies  S_{lam w}[omega (rho b - grad - p + neigh)].

Tiles are (block_n, block_p) with block_p a multiple of 128 (lane width) and
block_n a multiple of 8 (sublane), so both passes feed the MXU with aligned
(8k, 128k) operands.  Scalars (rho, omega) arrive as (1,1) operands so the
kernel stays traceable under vmap over network nodes; lam is a (p, 1) column
so per-coordinate penalty levels (adaptive/SCAD/MCP via one-step LLA) fuse
into the same kernel — a uniform l1 level is just a constant column.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import losses

# fp32 dots at full precision: at default precision Mosaic, like XLA, runs an
# f32 dot as one bf16 pass (2.3e-3 relative error per dot on v5e).
_F32 = jax.lax.Precision.HIGHEST


def _margin_weights_kernel(x_ref, y_ref, beta_ref, w_ref, *, h: float,
                           kernel: str, n_total: int):
    """Accumulate partial X@beta; at the last p-tile convert to weights."""
    j = pl.program_id(1)
    partial = jnp.dot(x_ref[...], beta_ref[...], precision=_F32,
                      preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        w_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        w_ref[...] += partial

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        kern = losses.get_kernel(kernel)
        y = y_ref[...]
        margin = y * w_ref[...]
        w_ref[...] = kern.dloss(margin, h) * y * (1.0 / n_total)


def _grad_update_kernel(x_ref, w_ref, beta_ref, pdual_ref, neigh_ref,
                        rho_ref, omega_ref, lam_ref, out_ref):
    """Accumulate X^T w; at the last n-tile apply the 7a' soft-threshold."""
    k = pl.program_id(1)
    partial = jnp.dot(x_ref[...].T, w_ref[...], precision=_F32,
                      preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += partial

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        rho = rho_ref[0, 0]
        omega = omega_ref[0, 0]
        z = rho * beta_ref[...] - out_ref[...] - pdual_ref[...] + neigh_ref[...]
        zo = omega * z
        t = lam_ref[...] * omega           # (bp, 1) per-coordinate level
        # declint: disable=R1 fused in-kernel prox, parity-tested vs solver.local_update
        out_ref[...] = jnp.sign(zo) * jnp.maximum(jnp.abs(zo) - t, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("h", "kernel", "block_n", "block_p", "interpret"))
def csvm_local_update(X, y, beta, p_dual, neigh, rho, omega, lam, *,
                      h: float, kernel: str = "epanechnikov",
                      block_n: int = 256, block_p: int = 512,
                      interpret: bool | None = None):
    """Fused ADMM local update for one node.  Shapes: X (n, p), vectors (p,).

    lam may be a scalar (uniform l1 level) or a (p,) per-coordinate vector
    (LLA stage 2); either way it is streamed as a (p, 1) column operand.
    n and p are padded to tile multiples inside; padding rows get y=0 so
    their dloss weight contributes sign(y)=0... (we zero w explicitly).
    """
    interpret = _resolve_interpret(interpret)
    n, p = X.shape
    bn, bp = min(block_n, _rup(n, 8)), min(block_p, _rup(p, 128))
    n_pad, p_pad = _rup(n, bn), _rup(p, bp)
    Xp = _pad0(X, ((0, n_pad - n), (0, p_pad - p)))
    yp = _pad0(y, (0, n_pad - n))              # y=0 rows -> w=0 after mask
    bpad = _pad0(beta, (0, p_pad - p))
    ppad = _pad0(p_dual, (0, p_pad - p))
    npad = _pad0(neigh, (0, p_pad - p))
    lam_vec = jnp.broadcast_to(jnp.asarray(lam, jnp.float32).reshape(-1), (p,))
    lpad = _pad0(lam_vec, (0, p_pad - p))

    ycol = yp[:, None].astype(jnp.float32)
    bcol = bpad[:, None].astype(jnp.float32)
    pcol = ppad[:, None].astype(jnp.float32)
    ncol = npad[:, None].astype(jnp.float32)
    lcol = lpad[:, None]
    scal = lambda s: jnp.asarray(s, jnp.float32).reshape(1, 1)

    grid1 = (n_pad // bn, p_pad // bp)
    w = pl.pallas_call(
        functools.partial(_margin_weights_kernel, h=h, kernel=kernel, n_total=n),
        grid=grid1,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(Xp.astype(jnp.float32), ycol, bcol)
    # padded rows have y=0 => margin weight = dloss(0)*0 = 0 already; but
    # dloss(0)*y=0 exactly, so no extra masking is required.

    grid2 = (p_pad // bp, n_pad // bn)
    out = pl.pallas_call(
        _grad_update_kernel,
        grid=grid2,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda j, k: (k, j)),
            pl.BlockSpec((bn, 1), lambda j, k: (k, 0)),
            pl.BlockSpec((bp, 1), lambda j, k: (j, 0)),
            pl.BlockSpec((bp, 1), lambda j, k: (j, 0)),
            pl.BlockSpec((bp, 1), lambda j, k: (j, 0)),
            pl.BlockSpec((1, 1), lambda j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda j, k: (0, 0)),
            pl.BlockSpec((bp, 1), lambda j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bp, 1), lambda j, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
        interpret=interpret,
    )(Xp.astype(jnp.float32), w, bcol, pcol, ncol,
      scal(rho), scal(omega), lcol)
    return out[:p, 0].astype(X.dtype)


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad0(a, widths):
    # dtype-matched zero fill: jnp.pad's default weak-int 0 inserts a
    # convert_element_type into every traced launch (jaxtrace contract d)
    return jnp.pad(a, widths, constant_values=a.dtype.type(0))


def _resolve_interpret(interpret):
    # pallas runs interpreted everywhere but TPU; an unconditional
    # interpret=True default would silently deoptimize TPU runs (R9)
    return jax.default_backend() != "tpu" if interpret is None else interpret


# --------------------------------------------------------------------------
# Round megakernel: the whole check_every=k inner loop in one pallas_call
# --------------------------------------------------------------------------
#
# The two-pass kernel above still leaves the (7a') prox, neighbour sum, dual
# update, and (every k rounds) the KKT statistic in XLA ops between kernel
# launches, with B/P spilled to HBM after every half-round.  The megakernel
# keeps the whole network state — X (m, n, p), labels, W, B, P — resident in
# VMEM and runs k full ADMM rounds in a single on-chip fori_loop, computing
# the KKT stop statistic in the same pass on the way out.  X is streamed
# through the MXU twice per round (margins, then X^T w) and never leaves
# VMEM between rounds.
#
# dtype discipline (the bf16 mode): X and both MXU operand casts take the
# *compute* dtype (X.dtype — fp32 or bf16); every accumulator — B, P, the
# margin/gradient products (via preferred_element_type), and the KKT
# statistic — stays fp32.  See kernels/README.md for the full rules.
#
# Padding semantics (host-side, in the wrapper):
#   n rows:  y = 0  => dloss * y = 0, padded samples never contribute;
#   p cols:  X = lam = 0 => z = 0 stays 0 through the soft-threshold;
#   m rows:  X = y = W = deg = omega = 0 => B, P rows stay identically 0,
#            and the KKT consensus max masks them with an iota row filter.


def _batched_grad(x_ref, y_ref, B, h: float, kern, cd):
    """Per-node smoothed-loss gradients sum_i L_h'(y_i x_i'b_l) y_i x_i.

    B (M, P) fp32 -> (M, P) fp32.  The two batched MXU dots take the shapes
    Mosaic lowers (those of flash attention's q k^T and p v): the margins
    contract b_l (M, 1, P) with X (M, N, P) over P, the gradient contracts
    the weights (M, 1, N) with X over N.  X is read from its VMEM ref here,
    inside the round, so no copy of it is carried across the round loop.

    These dots keep Mosaic's default precision, one bf16 pass even for fp32
    X (1.1e-4 from the fp32 reference after 300 rounds at the paper's design,
    v5e): at HIGHEST Mosaic splits X into bf16 parts in its scoped stack,
    which then no longer fits VMEM at that design.
    """
    X = x_ref[...]
    Y = y_ref[...]                                               # (M, 1, N)
    Mp, _, Pp = X.shape
    marg = jax.lax.dot_general(
        B.astype(cd).reshape(Mp, 1, Pp), X, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                      # (M, 1, N)
    wts = kern.dloss(Y * marg, h) * Y
    g = jax.lax.dot_general(
        wts.astype(cd), X, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                      # (M, 1, P)
    return g.reshape(Mp, Pp)


def _round_megakernel(x_ref, y_ref, wadj_ref, deg_ref, rho_ref, omega_ref,
                      lam_ref, nact_ref, b0_ref, p0_ref,
                      bout_ref, pout_ref, stat_ref, *, tau: float,
                      lam0: float, h: float, kernel: str, num_rounds: int,
                      n_real: int, m_real: int, want_kkt: bool):
    """k full ADMM rounds + optional KKT epilogue, all state in VMEM.

    Shapes (padded): X (M, N, P) compute-dtype; y (M, 1, N); W (M, M);
    deg/rho/omega (M, 1); lam (1, P); nact (1, 1) traced round count
    (rounds past it are held — the while-driver's max_iter guard); B/P
    (M, P) fp32.  Outputs: B, P (M, P) fp32 and the (1, 1) stop statistic
    (KKT residual when ``want_kkt``, else max|B_k - B_{k-1}|).
    """
    kern = losses.get_kernel(kernel)
    A = wadj_ref[...]
    deg = deg_ref[...]
    rho = rho_ref[...]
    omega = omega_ref[...]
    lam = lam_ref[...]
    nact = nact_ref[0, 0]
    cd = x_ref.dtype
    inv_n = 1.0 / n_real

    def round_body(i, carry):
        B, P, delta = carry
        active = i < nact
        WB = jnp.dot(A, B, precision=_F32,
                     preferred_element_type=jnp.float32)
        grad = _batched_grad(x_ref, y_ref, B, h, kern, cd) * inv_n
        z = rho * B - grad - P + tau * (deg * B + WB)
        zo = omega * z
        thr = lam * omega
        # declint: disable=R1 fused in-kernel prox, parity-tested vs solver.local_update
        Bn = jnp.sign(zo) * jnp.maximum(jnp.abs(zo) - thr, 0.0)
        WBn = jnp.dot(A, Bn, precision=_F32,
                      preferred_element_type=jnp.float32)
        Pn = P + tau * (deg * Bn - WBn)
        d = jnp.max(jnp.abs(Bn - B))
        hold = lambda new, old: jnp.where(active, new, old)
        return hold(Bn, B), hold(Pn, P), hold(d, delta)

    B, P, delta = jax.lax.fori_loop(
        0, num_rounds, round_body,
        (b0_ref[...], p0_ref[...], jnp.asarray(jnp.inf, jnp.float32)))
    bout_ref[...] = B
    pout_ref[...] = P

    if want_kkt:
        # Same pass, same VMEM-resident X: stationarity (unit-step
        # prox-gradient fixed point at beta_bar) + consensus, the statistic
        # of ``solver.kkt_residual``.  beta_bar is broadcast to every node
        # row so the network-mean gradient reuses the per-node batched dots
        # (ghost rows have X = y = 0 and add nothing to the sum).
        Mp, Pp = B.shape
        bb = jnp.sum(B, axis=0, keepdims=True) * (1.0 / m_real)   # (1, P)
        g = jnp.sum(_batched_grad(x_ref, y_ref,
                                  jnp.broadcast_to(bb, (Mp, Pp)), h, kern,
                                  cd), axis=0, keepdims=True)
        g = g * (inv_n / m_real) + lam0 * bb
        v = bb - g
        # declint: disable=R1 in-pass KKT prox epilogue, matches solver.kkt_residual
        prox = jnp.sign(v) * jnp.maximum(jnp.abs(v) - lam, 0.0)
        stat = jnp.max(jnp.abs(bb - prox))
        rows = jax.lax.broadcasted_iota(jnp.int32, (Mp, 1), 0)
        cons = jnp.max(jnp.where(rows < m_real, jnp.abs(B - bb), 0.0))
        stat_ref[...] = jnp.maximum(stat, cons).reshape(1, 1)
    else:
        stat_ref[...] = delta.reshape(1, 1)


@functools.partial(
    jax.jit,
    static_argnames=("tau", "lam0", "h", "kernel", "num_rounds", "want_kkt",
                     "interpret"))
def csvm_round_block(X, y, B, P, W, deg, rho, omega, lam_vec, nact, *,
                     tau: float, lam0: float, h: float,
                     kernel: str = "epanechnikov", num_rounds: int = 1,
                     want_kkt: bool = False, interpret: bool | None = None):
    """``num_rounds`` fused ADMM rounds over the whole network.

    X (m, n, p) in the compute dtype (fp32 or bf16 — the mixed-precision
    mode); y (m, n); B/P (m, p) fp32 accumulators; W (m, m); deg/rho/omega
    (m,); lam_vec (p,); nact a traced round count <= num_rounds (rounds
    past it are held, so ``run_tol`` never overshoots max_iter).
    Returns (B, P, stat) with fp32 B/P and stat the KKT residual
    (``want_kkt``) or last-active-round progress max|dB|.
    """
    interpret = _resolve_interpret(interpret)
    m, n, p = X.shape
    cd = jnp.bfloat16 if X.dtype == jnp.bfloat16 else jnp.float32
    sub = 16 if cd == jnp.bfloat16 else 8
    m_pad, n_pad, p_pad = _rup(m, 8), _rup(n, sub), _rup(p, 128)
    f32 = jnp.float32
    Xp = _pad0(X.astype(cd), ((0, m_pad - m), (0, n_pad - n),
                              (0, p_pad - p)))
    yp = _pad0(y.astype(f32), ((0, m_pad - m), (0, n_pad - n)))[:, None, :]
    Bp = _pad0(B.astype(f32), ((0, m_pad - m), (0, p_pad - p)))
    Pp = _pad0(P.astype(f32), ((0, m_pad - m), (0, p_pad - p)))
    Wp = _pad0(W.astype(f32), ((0, m_pad - m), (0, m_pad - m)))
    col = lambda v: _pad0(v.astype(f32), (0, m_pad - m))[:, None]
    lam_row = jnp.broadcast_to(jnp.asarray(lam_vec, f32).reshape(-1), (p,))
    lam_row = _pad0(lam_row, (0, p_pad - p))[None, :]
    nact2 = jnp.asarray(nact, jnp.int32).reshape(1, 1)

    Bn, Pn, stat = pl.pallas_call(
        functools.partial(
            _round_megakernel, tau=tau, lam0=lam0, h=h, kernel=kernel,
            num_rounds=num_rounds, n_real=n, m_real=m, want_kkt=want_kkt),
        out_shape=(
            jax.ShapeDtypeStruct((m_pad, p_pad), f32),
            jax.ShapeDtypeStruct((m_pad, p_pad), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
        ),
        compiler_params=_compiler_params(m, n, p, Xp.dtype.itemsize),
        interpret=interpret,
    )(Xp, yp, Wp, col(deg), col(rho), col(omega), lam_row, nact2, Bp, Pp)
    return Bn[:m, :p], Pn[:m, :p], stat[0, 0]


def _block_update_kernel(x_ref, y_ref, b_ref, p_ref, neigh_ref, rho_ref,
                         omega_ref, lam_ref, out_ref, *, h: float,
                         kernel: str, n_real: int):
    """Fused (7a') for a whole (m_local, n, p) node block: margins ->
    weights -> X^T w -> soft-threshold, one VMEM residency.  The neighbour
    term arrives as an operand so sharded engines can run their collective
    between kernel launches."""
    kern = losses.get_kernel(kernel)
    B = b_ref[...]
    grad = _batched_grad(x_ref, y_ref, B, h, kern, x_ref.dtype) * (
        1.0 / n_real)
    z = rho_ref[...] * B - grad - p_ref[...] + neigh_ref[...]
    zo = omega_ref[...] * z
    thr = lam_ref[...] * omega_ref[...]
    # declint: disable=R1 fused in-kernel prox, parity-tested vs solver.local_update
    out_ref[...] = jnp.sign(zo) * jnp.maximum(jnp.abs(zo) - thr, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("h", "kernel", "interpret"))
def csvm_block_update(X, y, B, P, neigh, rho, omega, lam_vec, *, h: float,
                      kernel: str = "epanechnikov",
                      interpret: bool | None = None):
    """Fused primal update (7a') for a stacked node block.

    X (m, n, p) compute dtype; y (m, n); B/P/neigh (m, p) fp32 (neigh is
    the precomputed tau*(deg*B + (WB)) rows); rho/omega (m,); lam_vec (p,).
    Returns B_new (m, p) fp32.
    """
    interpret = _resolve_interpret(interpret)
    m, n, p = X.shape
    cd = jnp.bfloat16 if X.dtype == jnp.bfloat16 else jnp.float32
    sub = 16 if cd == jnp.bfloat16 else 8
    m_pad, n_pad, p_pad = _rup(m, 8), _rup(n, sub), _rup(p, 128)
    f32 = jnp.float32
    Xp = _pad0(X.astype(cd), ((0, m_pad - m), (0, n_pad - n),
                              (0, p_pad - p)))
    yp = _pad0(y.astype(f32), ((0, m_pad - m), (0, n_pad - n)))[:, None, :]
    pad_mp = lambda a: _pad0(a.astype(f32), ((0, m_pad - m),
                                             (0, p_pad - p)))
    col = lambda v: _pad0(v.astype(f32), (0, m_pad - m))[:, None]
    lam_row = jnp.broadcast_to(jnp.asarray(lam_vec, f32).reshape(-1), (p,))
    lam_row = _pad0(lam_row, (0, p_pad - p))[None, :]

    out = pl.pallas_call(
        functools.partial(_block_update_kernel, h=h, kernel=kernel,
                          n_real=n),
        out_shape=jax.ShapeDtypeStruct((m_pad, p_pad), f32),
        compiler_params=_compiler_params(m, n, p, Xp.dtype.itemsize),
        interpret=interpret,
    )(Xp, yp, pad_mp(B), pad_mp(P), pad_mp(neigh), col(rho), col(omega),
      lam_row)
    return out[:m, :p]


def _tiled_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of one array under Mosaic's tiling of its two minor dims
    into (32 / itemsize, 128) tiles: a (M, 1, N) slab costs 8 sublanes a
    row in fp32, not one."""
    *lead, rows, cols = shape
    return (math.prod(lead) * _rup(rows, 32 // itemsize) * _rup(cols, 128)
            * itemsize)


def megakernel_vmem(m: int, n: int, p: int, itemsize: int = 4):
    """(window_bytes, scoped_bytes) of one round-megakernel residency.

    Windows are the kernel's operands and outputs, each held once in VMEM
    (no grid, so single-buffered), tile-padded.  Scoped bytes are Mosaic's
    stack for the kernel's intermediates: the (M, 1, P) and (M, 1, N) slabs
    of the batched dots and, at some shapes, working copies of X for the
    dots: one in fp32, and under bf16 X one more in bf16 (measured against
    the v5e compiler: kernels/README.md).
    The block-update kernel's footprint is below this one."""
    sub = 16 if itemsize == 2 else 8
    M, N, P = _rup(m, 8), _rup(n, sub), _rup(p, 128)
    windows = (_tiled_bytes((M, N, P), itemsize)          # X
               + _tiled_bytes((M, 1, N))                  # y
               + _tiled_bytes((M, M))                     # W
               + 3 * _tiled_bytes((M, 1))                 # deg, rho, omega
               + _tiled_bytes((1, P))                     # lam
               + 2 * _tiled_bytes((1, 1))                 # nact, stat
               + 4 * _tiled_bytes((M, P)))                # B, P in and out
    copies = 4 + (itemsize if itemsize == 2 else 0)   # fp32 (+ bf16) copy
    scoped = (M * N * P * copies + 3 * _tiled_bytes((M, 1, P))
              + 4 * _tiled_bytes((M, 1, N)) + 2**20)
    return windows, scoped


def megakernel_vmem_bytes(m: int, n: int, p: int, itemsize: int = 4) -> int:
    """Whole VMEM footprint of one megakernel residency (windows + scoped
    stack); ``ops.megakernel_supported`` compares it with the budget."""
    return sum(megakernel_vmem(m, n, p, itemsize))


def _compiler_params(m: int, n: int, p: int, itemsize: int):
    """Raise Mosaic's scoped-VMEM limit (16 MiB by default on v5e) to the
    kernel's modelled stack; the windows are allocated outside it."""
    scoped = megakernel_vmem(m, n, p, itemsize)[1]
    return pltpu.CompilerParams(vmem_limit_bytes=_rup(scoped, 2**20))
