"""A plain deCSVM reference, written from the paper and the documented
behaviour of the entry points, importing nothing of the program.

Algorithm 1 of arXiv:2503.07563 (generalized ADMM for the
convolution-smoothed hinge loss with an l1 penalty), per node l with
deg_l neighbours:

    grad_l = (1/n) sum_i L_h'(y_i x_i' b_l) y_i x_i
    z_l    = rho_l b_l - grad_l - p_l + tau (deg_l b_l + sum_{k~l} b_k)
    b+_l   = S(w_l z_l, lam w_l),          w_l = 1 / (2 tau deg_l + rho_l)
    p+_l   = p_l + tau (deg_l b+_l - sum_{k~l} b+_k)

rho_l = 1.05 c_h lmax(X_l'X_l / n), lmax by 50 power steps from a normal
start vector keyed by (n * 1000003 + p) and one Rayleigh quotient.  The stop
rule is the KKT residual max(stationarity of the network mean, consensus),
evaluated after every ``check_every`` rounds.  The tuned path runs a
decreasing log grid of ``num`` points from lambda_max = |X'y/N|_inf down to
1e-3 lambda_max, warm-started, duals reset at each point, and selects by the
modified BIC (hinge mean plus sqrt(log N) log p mean support / N, p the
column count with the intercept).

``fit`` and ``warm_path`` make their own stop decisions: they are the
control, run in the program's place.  The check of an answer uses
``fit_following`` and ``path_following``, which run the rounds that the
answer reports at each grid point and evaluate the KKT residual of the
answer's own estimates.  A stop decision taken at a residual within rounding
of the tolerance then moves neither side, and a stop the answer claims is
held to the residual the configuration states.

Every contraction goes through ``dot``.  ``"highest"`` is fp32.  The
control ``"bf16x3"`` splits each fp32 operand into a bf16 high and low part
and keeps the three larger products, which is what a TPU's three-pass
``high`` precision computes; the products of bf16 values are exact in fp32,
so the control reads the same on every platform.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16x3")


def _split(a):
    """a = hi + lo: hi is a with the low 16 bits of its fp32 word cleared,
    so exactly a bf16 value, and lo is the rest rounded to bf16.  Masking
    the bits, not a round trip through bf16, keeps a compiler that drops
    such round trips from folding lo to zero."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def dot(spec: str, a, b, precision: str):
    """einsum ``spec`` of fp32 operands at the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ah, al = _split(a)
    bh, bl = _split(b)
    f = functools.partial(jnp.einsum, spec,
                          preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def soft(v, t):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


def epanechnikov_dloss(v, h):
    """L_h'(v) of the hinge smoothed by the Epanechnikov kernel."""
    z = jnp.clip((1.0 - v) / h, -1.0, 1.0)
    return -(2.0 + 3.0 * z - z ** 3) / 4.0


DLOSS = {"epanechnikov": (epanechnikov_dloss, 0.75)}   # (L_h', c_h * h)


def step_sizes(X, W, h, kernel, tau, precision):
    """(deg, rho, omega) of every node."""
    m, n, p = X.shape
    v0 = jax.random.normal(jax.random.PRNGKey(n * 1000003 + p), (p,),
                           jnp.float32)
    v0 = v0 / jnp.linalg.norm(v0)

    def lmax(Xl):
        def apply(v):
            return dot("np,n->p", Xl, dot("np,p->n", Xl, v, precision),
                       precision) / n

        def body(v, _):
            w = apply(v)
            nrm = jnp.linalg.norm(w)
            return jnp.where(nrm > 0.0, w / jnp.where(nrm > 0, nrm, 1.0),
                             v), None

        v, _ = jax.lax.scan(body, v0, None, length=50)
        w = apply(v)
        return jnp.sum(v * w) / jnp.sum(v * v)

    c_h = DLOSS[kernel][1] / h
    rho = 1.05 * c_h * jax.vmap(lmax)(X)
    deg = jnp.sum(W, axis=1)
    return deg, rho, 1.0 / (2.0 * tau * deg + rho)


def admm_round(X, y, W, deg, rho, omega, B, P, lam, h, kernel, tau,
               precision):
    dloss = DLOSS[kernel][0]
    n = X.shape[1]
    margin = y * dot("mnp,mp->mn", X, B, precision)
    g = dot("mnp,mn->mp", X, dloss(margin, h) * y, precision) / n
    nb = dot("lk,kp->lp", W, B, precision)
    z = rho[:, None] * B - g - P + tau * (deg[:, None] * B + nb)
    Bn = soft(omega[:, None] * z, lam * omega[:, None])
    Pn = P + tau * (deg[:, None] * Bn - dot("lk,kp->lp", W, Bn, precision))
    return Bn, Pn


def kkt(X, y, B, lam, h, kernel, precision):
    """max(prox-gradient stationarity at the network mean, consensus)."""
    dloss = DLOSS[kernel][0]
    n = X.shape[1]
    bb = jnp.mean(B, axis=0)
    margin = y * dot("mnp,p->mn", X, bb, precision)
    g = jnp.mean(dot("mnp,mn->mp", X, dloss(margin, h) * y, precision)
                 / n, axis=0)
    stat = jnp.max(jnp.abs(bb - soft(bb - g, lam)))
    return jnp.maximum(stat, jnp.max(jnp.abs(B - bb[None])))


def fit_to_tol(X, y, W, sizes, B0, lam, *, h, kernel, tau, max_iter, tol,
               check_every, precision):
    """Rounds from (B0, P=0) until max_iter or a measured KKT <= tol.
    Returns (B, rounds run)."""
    deg, rho, omega = sizes

    def cond(c):
        _, _, t, stat = c
        return (t < max_iter) & (stat > tol)

    def body(c):
        B, P, t, _ = c
        for _ in range(check_every):
            Bn, Pn = admm_round(X, y, W, deg, rho, omega, B, P, lam, h,
                                kernel, tau, precision)
            live = t < max_iter
            B = jnp.where(live, Bn, B)
            P = jnp.where(live, Pn, P)
            t = jnp.where(live, t + 1, t)
        return B, P, t, kkt(X, y, B, lam, h, kernel, precision)

    B, _, t, _ = jax.lax.while_loop(
        cond, body, (B0, jnp.zeros_like(B0), jnp.int32(0),
                     jnp.float32(jnp.inf)))
    return B, t


@functools.partial(jax.jit, static_argnames=(
    "h", "kernel", "tau", "max_iter", "check_every", "precision"))
def fit(X, y, W, lam, tol, *, h, kernel, tau, max_iter, check_every,
        precision):
    """One fit from zero at ``lam``: (B (m, p), rounds)."""
    sizes = step_sizes(X, W, h, kernel, tau, precision)
    B0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    return fit_to_tol(X, y, W, sizes, B0, lam, h=h, kernel=kernel, tau=tau,
                      max_iter=max_iter, tol=tol, check_every=check_every,
                      precision=precision)


def run_rounds(X, y, W, sizes, B0, lam, rounds, *, h, kernel, tau,
               precision):
    """``rounds`` rounds from (B0, P=0)."""
    deg, rho, omega = sizes

    def body(_, c):
        return admm_round(X, y, W, deg, rho, omega, c[0], c[1], lam, h,
                          kernel, tau, precision)

    B, _ = jax.lax.fori_loop(0, rounds, body, (B0, jnp.zeros_like(B0)))
    return B


@functools.partial(jax.jit, static_argnames=("h", "kernel", "tau"))
def fit_following(X, y, W, lam, rounds, B_answer, *, h, kernel, tau):
    """The fit from zero for the rounds an answer reports, at "highest";
    returns (B, KKT residual of the answer's B)."""
    sizes = step_sizes(X, W, h, kernel, tau, "highest")
    B0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    B = run_rounds(X, y, W, sizes, B0, lam, rounds, h=h, kernel=kernel,
                   tau=tau, precision="highest")
    return B, kkt(X, y, B_answer, lam, h, kernel, "highest")


def bic(X, y, B, precision):
    m, n, p = X.shape
    N = m * n
    margin = y * dot("mnp,mp->mn", X, B, precision)
    hinge = jnp.sum(jnp.maximum(1.0 - margin, 0.0)) / N
    supp = jnp.mean(jnp.sum(jnp.abs(B) > 1e-8, axis=1).astype(jnp.float32))
    return hinge + math.sqrt(math.log(N)) * math.log(p) * supp / N


@functools.partial(jax.jit, static_argnames=(
    "h", "kernel", "tau", "max_iter", "check_every", "precision"))
def warm_path(X, y, W, lams, tol, *, h, kernel, tau, max_iter, check_every,
              precision):
    """The warm path over ``lams`` (decreasing) and its BIC selection.
    Returns (path (L, m, p), rounds (L,), bic (L,), selected index)."""
    sizes = step_sizes(X, W, h, kernel, tau, precision)

    def point(B, lam):
        B, t = fit_to_tol(X, y, W, sizes, B, lam, h=h, kernel=kernel,
                          tau=tau, max_iter=max_iter, tol=tol,
                          check_every=check_every, precision=precision)
        return B, (B, t, bic(X, y, B, precision))

    B0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    _, (path, rounds, crit) = jax.lax.scan(point, B0, lams)
    return path, rounds, crit, jnp.argmin(crit)


@functools.partial(jax.jit, static_argnames=("h", "kernel", "tau"))
def path_following(X, y, W, lams, rounds, path_answer, *, h, kernel, tau):
    """The warm path over ``lams`` for the rounds an answer reports at each
    point, at "highest".  Returns (path, selected index, KKT residual of the
    answer's estimates at each point)."""
    sizes = step_sizes(X, W, h, kernel, tau, "highest")

    def point(B, xs):
        lam, r, B_answer = xs
        B = run_rounds(X, y, W, sizes, B, lam, r, h=h, kernel=kernel,
                       tau=tau, precision="highest")
        return B, (B, bic(X, y, B, "highest"),
                   kkt(X, y, B_answer, lam, h, kernel, "highest"))

    B0 = jnp.zeros((X.shape[0], X.shape[2]), jnp.float32)
    _, (path, crit, res) = jax.lax.scan(point, B0, (lams, rounds,
                                                    path_answer))
    return path, jnp.argmin(crit), res


def lambda_grid(X: np.ndarray, y: np.ndarray, num: int,
                min_frac: float = 1e-3) -> np.ndarray:
    """Decreasing log grid from |X'y/N|_inf to min_frac of it, in float64."""
    X2 = np.asarray(X, np.float64).reshape(-1, X.shape[-1])
    y2 = np.asarray(y, np.float64).reshape(-1)
    lam_max = float(np.max(np.abs(X2.T @ y2)) / len(y2))
    return np.logspace(math.log10(lam_max), math.log10(lam_max * min_frac),
                       num)
