"""Faults planted in the program's timed path, each a context manager, for
showing that a cell's check fails them (``tests/test_faults.py`` on the CPU,
``control.py --faults`` on the chip).  The program is patched where its
entry points look the function up, and JAX's caches are cleared on the way
in and out so that no program traced before or under the fault survives.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(obj, attr, make):
    import jax

    old = getattr(obj, attr)
    setattr(obj, attr, make(old))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, attr, old)
        jax.clear_caches()


def state_unchanged(workload):
    """The round hands back the iterate it was given."""
    from repro.core import solver
    return patched(solver, "local_update",
                   lambda orig: lambda X, y, beta, *a, **k: beta)


def half_batch(workload):
    """Half of each node's samples left out, the mean over the rest."""
    from repro.core import solver

    def make(orig):
        def f(X, y, *a, **k):
            half = X.shape[0] // 2
            return orig(X[:half], y[:half], *a, **k)
        return f
    return patched(solver, "local_update", make)


def no_exchange(workload):
    """The neighbour exchange left out: every node sees zero neighbours."""
    from repro.core import solver

    def make(orig):
        def f(cfg, neighbor_sum, **k):
            import jax.numpy as jnp
            return orig(cfg, lambda B: jnp.zeros_like(B), **k)
        return f
    return patched(solver, "make_step", make)


def stop_loose(workload):
    """The stop rule compared with ten times the tolerance."""
    from repro.core import solver

    def make(orig):
        def f(*a, tol, **k):
            return orig(*a, tol=10.0 * tol, **k)
        return f
    return patched(solver, "run_tol", make)


def stop_first_check(workload):
    """The stop rule passes at its first check, whatever the residual."""
    from repro.core import solver

    def make(orig):
        def f(*a, tol, **k):
            return orig(*a, tol=1e30, **k)
        return f
    return patched(solver, "run_tol", make)


def answer_altered(workload):
    """One coordinate of the answer moved by 1e-3 where it is produced."""
    import repro.core as core
    from repro.core import tuning

    if workload.endswith(".path"):
        def make(orig):
            def f(*a, **k):
                best_lam, best_B, table, res = orig(*a, **k)
                return best_lam, best_B, table, res._replace(
                    path=res.path.at[-1, 0, 1].add(1e-3))
            return f
        return patched(tuning, "select_lambda_path", make)

    def make(orig):
        def f(*a, **k):
            B, t = orig(*a, **k)
            return B.at[0, 1].add(1e-3), t
        return f
    return patched(core, "decsvm_fit_tol", make)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, no_exchange,
                                  stop_loose, stop_first_check,
                                  answer_altered)}
