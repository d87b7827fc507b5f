"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the
kernel bodies then execute in Python for bit-accurate validation) and False
on real TPU hardware.
"""
from __future__ import annotations

import jax

from repro.kernels.csvm_update import (csvm_block_update as
                                       _csvm_block_update,
                                       csvm_local_update as
                                       _csvm_local_update,
                                       csvm_round_block as _csvm_round_block,
                                       megakernel_vmem_bytes)
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.xpass import xpass as _xpass
from repro.kernels.xpass import xpass_rows as _xpass_rows


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# Whole-problem VMEM residency budget for the round megakernel.  A v5e
# core has 128 MiB of VMEM; the kernel's operand windows plus its scoped
# stack (``csvm_update.megakernel_vmem``) must fit in it, and 16 MiB stay
# free for the compiler.  Interpret mode emulates VMEM in host memory,
# where the only limit worth enforcing is "don't materialize something
# absurd".
_VMEM_BUDGET_TPU = 112 * 2**20
_VMEM_BUDGET_INTERPRET = 512 * 2**20


def megakernel_budget(interpret=None) -> int:
    """VMEM bytes one megakernel residency may take on this backend."""
    interpret = _default_interpret() if interpret is None else interpret
    return _VMEM_BUDGET_INTERPRET if interpret else _VMEM_BUDGET_TPU


def megakernel_supported(m: int, n: int, p: int, dtype=None,
                         interpret=None) -> bool:
    """True when the (m, n, p) problem fits the megakernel's whole-state
    VMEM residency (drivers fall back to the streaming/jnp path otherwise)."""
    import jax.numpy as jnp
    itemsize = 2 if dtype == jnp.bfloat16 else 4
    return (megakernel_vmem_bytes(m, n, p, itemsize)
            <= megakernel_budget(interpret))


def csvm_local_update(X, y, beta, p_dual, neigh, rho, omega, lam, *,
                      h, kernel="epanechnikov", interpret=None, **kw):
    """Fused deCSVM local update.  lam is a scalar l1 level or a (p,)
    per-coordinate vector (adaptive/SCAD/MCP weights via one-step LLA)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _csvm_local_update(X, y, beta, p_dual, neigh, rho, omega, lam,
                              h=h, kernel=kernel, interpret=interpret, **kw)


def csvm_round_block(X, y, B, P, W, deg, rho, omega, lam_vec, nact, *,
                     tau, lam0, h, kernel="epanechnikov", num_rounds=1,
                     want_kkt=False, interpret=None):
    """Round megakernel: ``num_rounds`` fused ADMM rounds (margins, X^T w
    gradient, (7a') prox, dual update) with the KKT stop statistic computed
    in the same pass when ``want_kkt``.  X in fp32 or bf16 (mixed-precision
    mode); B/P accumulators and the statistic stay fp32."""
    interpret = _default_interpret() if interpret is None else interpret
    return _csvm_round_block(X, y, B, P, W, deg, rho, omega, lam_vec, nact,
                             tau=tau, lam0=lam0, h=h, kernel=kernel,
                             num_rounds=num_rounds, want_kkt=want_kkt,
                             interpret=interpret)


def csvm_block_update(X, y, B, P, neigh, rho, omega, lam_vec, *, h,
                      kernel="epanechnikov", interpret=None):
    """Fused (7a') primal update for a stacked (m, n, p) node block; the
    neighbour term is an operand so sharded engines keep their collectives
    outside the kernel."""
    interpret = _default_interpret() if interpret is None else interpret
    return _csvm_block_update(X, y, B, P, neigh, rho, omega, lam_vec,
                              h=h, kernel=kernel, interpret=interpret)


def xpass(X, y, V, *, weight, h=0.0, kernel="epanechnikov", interpret=None,
          **kw):
    """One-pass G = X' phi(X V) per node (``decsvm_xpass``): X read from
    HBM once per product.  X (m, n, p), y (m, n), V (m, p) -> (m, p)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _xpass(X, y, V, weight=weight, h=h, kernel=kernel,
                  interpret=interpret, **kw)


def xpass_rows(X, y, V, *, weight, h=0.0, kernel="epanechnikov",
               interpret=None, **kw):
    """``xpass`` reading X as it is stored, rows on the sublanes
    (``decsvm_xpass_rows``), for a block whose p pads less than its n."""
    interpret = _default_interpret() if interpret is None else interpret
    return _xpass_rows(X, y, V, weight=weight, h=h, kernel=kernel,
                       interpret=interpret, **kw)


def flash_attention(q, k, v, *, causal=True, window=None, sm_scale=None,
                    interpret=None, **kw):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash_attention(q, k, v, causal=causal, window=window,
                            sm_scale=sm_scale, interpret=interpret, **kw)


def ssd_scan(x, dt, A, B, C, D, *, chunk=64, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)
