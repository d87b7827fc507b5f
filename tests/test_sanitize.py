"""Sanitizer suite (``ADMMConfig(sanitize=True)`` — ``core.sanitize``).

Three claims are pinned here:

1. **Bit-identity off**: with ``sanitize=False`` every parity driver traces
   to *exactly* the jaxpr it traced before the flag existed — proven by
   re-tracing each driver against a ``LegacyCfg`` frozen dataclass that
   replicates the pre-flag ``ADMMConfig`` field-for-field and comparing
   the printed jaxprs, plus a check-primitive census and a compile-guard
   zero-recompile budget.
2. **Localization on**: each E1-E7 check fires on the input that poisons
   exactly its term, names the term, and carries the round index.
3. **Fail-fast elsewhere**: sharded/mesh/lambda-grid/serving engines
   reject sanitize configs up front instead of silently dropping checks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import checkify

from repro.core import decentral
from repro.core import path as path_mod
from repro.core import sanitize, solver
from repro.core.admm import ADMMConfig, decsvm_fit
from repro.core.admm_adaptive import decsvm_fit_tol, decsvm_fit_uneven
from repro.core.graph import k_regular, ring
from tools.jaxtrace import walk

M, N, P = 4, 12, 8
ITERS = 6
LAM = 0.05


@dataclasses.dataclass(frozen=True)
class LegacyCfg:
    """``ADMMConfig`` exactly as it existed before the ``sanitize`` field —
    the duck-typed stand-in ``sanitize.wants_sanitize`` must treat as False
    and the solver must trace identically to."""
    lam: float = 0.05
    lam0: float = 0.0
    tau: float = 1.0
    h: float = 0.25
    kernel: str = "epanechnikov"
    max_iter: int = 300
    rho_safety: float = 1.05
    use_pallas: bool = False
    backend: str = "auto"


def _data(seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(M, N, P)), jnp.float32)
    beta = rng.normal(size=(P,))
    y = jnp.asarray(np.sign(X.reshape(M, N, P) @ beta + 0.1), jnp.float32)
    return X, y


X0, Y0 = _data()
Wn = np.asarray(ring(M), np.float32)
Wj = jnp.asarray(Wn)
MASK = jnp.ones((M, N), jnp.float32)
LAMS = jnp.asarray([2 * LAM, LAM], jnp.float32)


def _recipes(mk):
    """The 13-driver parity matrix of tests/test_solver.py, parameterized
    by a config factory so the same recipes trace under ``ADMMConfig`` and
    ``LegacyCfg`` (mirrors tools/jaxtrace/drivers.py)."""
    a = mk(lam=LAM, max_iter=ITERS)
    pal = mk(lam=LAM, max_iter=ITERS, use_pallas=True)
    pz = mk(lam=0.0, max_iter=ITERS)
    mkc = mk(lam=LAM, max_iter=ITERS, backend="megakernel")
    mkz = mk(lam=0.0, max_iter=ITERS, backend="megakernel")
    lams_host = [2 * LAM, LAM]
    return {
        "dense": lambda X, y: decsvm_fit(X, y, Wj, a),
        "pallas": lambda X, y: decsvm_fit(X, y, Wj, pal),
        "tol": lambda X, y: decsvm_fit_tol(X, y, Wj, a, tol=1e-6,
                                           stop_rule="kkt",
                                           check_every=2)[0],
        "uneven": lambda X, y: decsvm_fit_uneven(X, y, MASK, Wj, a),
        "path-batched": lambda X, y: path_mod.decsvm_path_batched(
            X, y, Wj, LAMS, pz),
        "path-warm": lambda X, y: path_mod.decsvm_path_warm(
            X, y, Wj, LAMS, pz, tol=1e-6, stop_rule="kkt",
            check_every=2)[0],
        "chunked": lambda X, y: decentral.decsvm_fit_chunked(X, y, Wn, a),
        "chunked-topology": lambda X, y: decentral.decsvm_fit_chunked(
            X, y, k_regular(M, 2), a),
        "mesh-2d": lambda X, y: decentral.decsvm_path_mesh(
            X, y, Wn, lams_host, pz, mode="batched").path,
        "megakernel": lambda X, y: decsvm_fit(X, y, Wj, mkc),
        "megakernel-tol": lambda X, y: decsvm_fit_tol(
            X, y, Wj, mkc, tol=1e-6, stop_rule="kkt", check_every=2)[0],
        "megakernel-path-warm": lambda X, y: path_mod.decsvm_path_warm(
            X, y, Wj, LAMS, mkz, tol=1e-6, stop_rule="kkt",
            check_every=2)[0],
        "mesh-2d-megakernel": lambda X, y: decentral.decsvm_path_mesh(
            X, y, Wn, lams_host, mkz, mode="batched").path,
    }


# -- claim 1: sanitize=False is bit-identical --------------------------------


def test_sanitize_false_traces_identically_to_pre_flag_config():
    """The tentpole proof: every parity driver's jaxpr under
    ``ADMMConfig(sanitize=False)`` equals the jaxpr under a config class
    that predates the flag — the sanitizer costs literally zero when off."""
    new = _recipes(lambda **kw: ADMMConfig(sanitize=False, **kw))
    old = _recipes(lambda **kw: LegacyCfg(**kw))
    assert set(new) == set(old) and len(new) == 13
    for name in new:
        jx_new = str(jax.make_jaxpr(new[name])(X0, Y0))
        jx_old = str(jax.make_jaxpr(old[name])(X0, Y0))
        assert jx_new == jx_old, f"driver {name!r} trace changed"


def test_sanitize_false_traces_contain_no_check_primitive():
    for name, fn in _recipes(
            lambda **kw: ADMMConfig(sanitize=False, **kw)).items():
        prims = walk.primitive_counts(jax.make_jaxpr(fn)(X0, Y0))
        assert "check" not in prims, f"driver {name!r} grew a check"


def test_sanitize_true_trace_contains_checks():
    from repro.core.admm import _decsvm_fit_impl
    cfg = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True)
    jx = jax.make_jaxpr(
        lambda X, y: _decsvm_fit_impl(X, y, Wj, None, None, cfg, False))(
            X0, Y0)
    # E1-E4 + E6 live once inside the scanned round body (E5 is bf16-only)
    assert walk.primitive_counts(jx).get("check", 0) == 5


def test_sanitize_flag_is_compile_cache_transparent(compile_guard):
    cfg = ADMMConfig(lam=LAM, max_iter=3)
    decsvm_fit(X0, Y0, Wj, cfg)                      # warm (may compile)
    with compile_guard.expect(0, what="fresh-but-equal sanitize=False cfg"):
        decsvm_fit(X0, Y0, Wj, ADMMConfig(lam=LAM, max_iter=3,
                                          sanitize=False))
    cfg_s = ADMMConfig(lam=LAM, max_iter=3, sanitize=True)
    decsvm_fit(X0, Y0, Wj, cfg_s)                    # warm the checked program
    with compile_guard.expect(0, what="fresh-but-equal sanitize=True cfg"):
        decsvm_fit(X0, Y0, Wj, ADMMConfig(lam=LAM, max_iter=3,
                                          sanitize=True))
    with compile_guard.expect(0, what="toggle back to sanitize=False"):
        decsvm_fit(X0, Y0, Wj, cfg)                  # True->False leaks nothing


# -- clean-path equivalence --------------------------------------------------


def test_sanitized_fit_matches_unsanitized_on_clean_data():
    cfg = ADMMConfig(lam=LAM, max_iter=ITERS)
    cfg_s = dataclasses.replace(cfg, sanitize=True)
    B = decsvm_fit(X0, Y0, Wj, cfg)
    Bs = decsvm_fit(X0, Y0, Wj, cfg_s)
    np.testing.assert_allclose(np.asarray(Bs), np.asarray(B), rtol=1e-6)

    Bt, t = decsvm_fit_tol(X0, Y0, Wj, cfg, tol=1e-6, stop_rule="kkt",
                           check_every=2)
    Bts, ts = decsvm_fit_tol(X0, Y0, Wj, cfg_s, tol=1e-6, stop_rule="kkt",
                             check_every=2)
    np.testing.assert_allclose(np.asarray(Bts), np.asarray(Bt), rtol=1e-6)
    assert int(ts) == int(t)

    Bu = decsvm_fit_uneven(X0, Y0, MASK, Wj, cfg)
    Bus = decsvm_fit_uneven(X0, Y0, MASK, Wj, cfg_s)
    np.testing.assert_allclose(np.asarray(Bus), np.asarray(Bu), rtol=1e-6)


def test_sanitized_bf16_fit_runs_streaming_fallback_clean():
    # the fused megakernel hides per-term dataflow, so sanitize routes the
    # bf16 mode through the streaming per-round path — and still passes
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, backend="megakernel_bf16",
                       sanitize=True)
    B = decsvm_fit(X0, Y0, Wj, cfg_s)
    assert np.all(np.isfinite(np.asarray(B)))


# -- claim 2: E1-E7 localization ----------------------------------------------


def _fit_raises(code, X, y, W, **cfg_kw):
    cfg = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True, **cfg_kw)
    with pytest.raises(checkify.JaxRuntimeError, match=code):
        decsvm_fit(X, y, W, cfg)


def test_e1_nan_label_localizes_to_margin_weights_at_round_0():
    y = Y0.at[1, 3].set(jnp.nan)
    _fit_raises(r"E1:.*margin weight.*round 0", X0, y, Wj)


def test_e3_nan_adjacency_localizes_to_neighbour_sum():
    W = Wj.at[0, 1].set(jnp.nan)
    _fit_raises(r"E3:.*neighbour sum.*round 0", X0, Y0, W)


def _checked_state_step(cfg_s, step, state, prob):
    err, new = checkify.checkify(
        lambda s: step(prob, s, LAM, None),
        errors=sanitize.USER_CHECKS)(state)
    return err, new


def test_e4_nan_dual_poisons_primal_update_and_reports_round_index():
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True)
    prob = solver.make_problem(X0, Y0, Wj, cfg_s)
    step = solver.make_step(cfg_s, lambda B: Wj @ B, W=Wj)
    state = solver.init_state(prob, P0=jnp.full((M, P), jnp.nan, jnp.float32))
    state = state._replace(t=jnp.asarray(5, jnp.int32))
    err, _ = _checked_state_step(cfg_s, step, state, prob)
    with pytest.raises(checkify.JaxRuntimeError,
                       match=r"E4:.*primal update.*round 5"):
        err.throw()


def test_e5_bf16_overflow_window_is_caught_before_the_cast_saturates():
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, backend="megakernel_bf16",
                       sanitize=True)
    prob = solver.make_problem(X0, Y0, Wj, cfg_s)
    assert prob.X.dtype == jnp.bfloat16
    big = float(jnp.finfo(jnp.bfloat16).max) * 1.001   # finite in f32

    def stub(prob, state, lam, lam_weights=None):      # E4 passes, E5 fires
        return state._replace(B=jnp.full_like(state.B, big),
                              t=state.t + 1)

    step = sanitize.checked_step(stub, cfg_s, lambda B: Wj @ B)
    err, _ = _checked_state_step(cfg_s, step, solver.init_state(prob), prob)
    with pytest.raises(checkify.JaxRuntimeError,
                       match=r"E5:.*bf16 range.*round 0"):
        err.throw()


def test_e6_nan_dual_accumulator_is_named():
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True)
    prob = solver.make_problem(X0, Y0, Wj, cfg_s)

    def stub(prob, state, lam, lam_weights=None):      # finite B, NaN P
        return state._replace(P=jnp.full_like(state.P, jnp.nan),
                              t=state.t + 1)

    step = sanitize.checked_step(stub, cfg_s, lambda B: Wj @ B)
    err, _ = _checked_state_step(cfg_s, step, solver.init_state(prob), prob)
    with pytest.raises(checkify.JaxRuntimeError,
                       match=r"E6:.*dual accumulator.*round 0"):
        err.throw()


def test_e7_kkt_statistic_check_wraps_residual_and_keeps_kind():
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True)
    fn = solver.kkt_residual_fn(cfg_s)
    assert getattr(fn, "kind", None) == "kkt"          # still a KKT rule
    prob = solver.make_problem(X0, Y0, Wj, cfg_s)
    state = solver.init_state(prob,
                              B0=jnp.full((M, P), jnp.nan, jnp.float32))
    err, _ = checkify.checkify(
        lambda s: fn(prob, s, LAM, None),
        errors=sanitize.USER_CHECKS)(state)
    with pytest.raises(checkify.JaxRuntimeError,
                       match=r"E7:.*KKT stop statistic"):
        err.throw()


def test_first_failing_check_wins_when_everything_is_poisoned():
    # NaN X poisons E1 (margins) before E2/E4 can even be evaluated —
    # checkify's first-failure semantics point at the *earliest* term
    X = X0.at[0, 0, 0].set(jnp.nan)
    _fit_raises(r"E1:", X, Y0, Wj)


# -- claim 3: unsupported engines fail fast ----------------------------------


def test_sharded_mesh_and_grid_engines_reject_sanitize():
    cfg_s = ADMMConfig(lam=LAM, max_iter=ITERS, sanitize=True)
    with pytest.raises(NotImplementedError, match="sanitize"):
        decentral.decsvm_fit_chunked(X0, Y0, Wn, cfg_s)
    with pytest.raises(NotImplementedError, match="sanitize"):
        decentral.decsvm_path_mesh(X0, Y0, Wn, [LAM], cfg_s, mode="batched")
    with pytest.raises(NotImplementedError, match="sanitize"):
        decentral.decsvm_path_mesh(X0, Y0, Wn, [LAM], cfg_s, mode="warm")
    with pytest.raises(NotImplementedError, match="sanitize"):
        path_mod.decsvm_path_batched(X0, Y0, Wj, LAMS, cfg_s)
    with pytest.raises(NotImplementedError, match="sanitize"):
        path_mod.decsvm_path_select(X0, Y0, Wj, LAMS, cfg_s)
    with pytest.raises(NotImplementedError, match="sanitize"):
        path_mod.decsvm_path_warm(X0, Y0, Wj, LAMS, cfg_s)


def test_rejection_message_names_the_supported_dense_drivers():
    cfg_s = ADMMConfig(sanitize=True)
    with pytest.raises(NotImplementedError, match="decsvm_fit_tol"):
        path_mod.decsvm_fit_many(
            X0[None], Y0[None], Wj[None], jnp.asarray([LAM]), cfg_s)
