"""The one-pass kernel ``decsvm_xpass`` (interpret mode on the CPU) and the
shape rule that routes the solver's products over X through it.

The kernel is held to the two HIGHEST contractions it replaces; the rule
is steered here by patching ``solver._platform`` (JAX reports the CPU) and,
where a small problem must take the kernel, ``solver.XPASS_MIN_BYTES``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ADMMConfig, decentral, graph, losses, solver
from repro.core.admm_adaptive import decsvm_fit_tol
from repro.core.path import decsvm_fit_many
from repro.kernels import ops, xpass

RNG = np.random.default_rng(14)
H = 0.3


def _inputs(m, n, p, zero_rows=0):
    X = jnp.asarray(RNG.standard_normal((m, n, p)), jnp.float32)
    y = RNG.choice([-1.0, 1.0], (m, n)).astype(np.float32)
    y[:, :zero_rows] = 0.0
    V = jnp.asarray(RNG.standard_normal((m, p)) * 0.05, jnp.float32)
    return X, jnp.asarray(y), V


def _pair(X, y, V, weight, kernel="epanechnikov"):
    """The two HIGHEST products of the jnp path, per node."""
    return jax.vmap(lambda Xl, yl, vl: solver.node_xtphi(
        Xl, yl, vl, weight=weight, h=H, kernel=kernel))(X, y, V)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("m,n,p,tile", [(2, 300, 2001, 128),
                                        (3, 1000, 37, 256),
                                        (1, 50, 130, 1024)],
                         ids=["p2001-ragged-n", "p37-4-tiles", "n-under-tile"])
@pytest.mark.parametrize("weight", xpass.WEIGHTS)
def test_xpass_matches_the_two_highest_products(m, n, p, tile, weight):
    """Both weight maps, p=2001 with no padding of X, an n that leaves the
    last tile part empty, and labels with zero rows."""
    X, y, V = _inputs(m, n, p, zero_rows=7)
    got = ops.xpass(X, y, V, weight=weight, h=H, tile=tile)
    assert got.shape == (m, p) and got.dtype == jnp.float32
    _close(got, _pair(X, y, V, weight))


@pytest.mark.parametrize("kernel", losses.KERNELS)
def test_xpass_loss_map_of_every_smoothing_kernel(kernel):
    X, y, V = _inputs(2, 260, 45)
    _close(ops.xpass(X, y, V, weight="loss", h=H, kernel=kernel, tile=128),
           _pair(X, y, V, "loss", kernel))


def test_xpass_under_vmap():
    """A batch of problems through ``pallas_call``'s batching rule, as the
    fit server vmaps its bucket."""
    X, y, V = _inputs(6, 200, 65)
    shape = lambda a: a.reshape((3, 2) + a.shape[1:])
    got = jax.vmap(lambda X, y, V: ops.xpass(X, y, V, weight="loss", h=H,
                                             tile=128))(
        shape(X), shape(y), shape(V))
    _close(got.reshape(6, 65), _pair(X, y, V, "loss"))


def test_xpass_rejects_an_unknown_map_and_a_ragged_tile():
    X, y, V = _inputs(1, 16, 8)
    with pytest.raises(ValueError, match="weight"):
        ops.xpass(X, y, V, weight="square")
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.xpass(X, y, V, weight="linear", tile=100)


# -- the rule ----------------------------------------------------------------

P500 = jax.ShapeDtypeStruct((200, 501), jnp.float32)        # 0.4 MB
EPSILON = jax.ShapeDtypeStruct((40000, 2001), jnp.float32)  # 320 MB


@pytest.mark.parametrize("backend,block,platform,masked,takes", [
    ("auto", EPSILON, "tpu", False, True),
    ("auto", P500, "tpu", False, False),
    ("auto", EPSILON, "cpu", False, False),
    ("auto", EPSILON, "tpu", True, False),
    ("jnp", EPSILON, "tpu", False, False),
    ("pallas", EPSILON, "tpu", False, False),
    ("megakernel", EPSILON, "tpu", False, False),
], ids=["epsilon-tpu", "p500-tpu", "epsilon-cpu", "epsilon-masked",
        "jnp", "pallas", "megakernel"])
def test_the_rule_takes_the_kernel_for_a_large_block_on_a_tpu(
        monkeypatch, backend, block, platform, masked, takes):
    monkeypatch.setattr(solver, "_platform", lambda: platform)
    mask = jnp.ones(block.shape[:1]) if masked else None
    assert solver.xpass_applies(backend, block, mask) is takes


def _fit_jaxpr(m, n, p, backend):
    from repro.core.admm_adaptive import _fit_tol_jit
    cfg = ADMMConfig(lam=0.05, h=H, max_iter=8, backend=backend)
    f = lambda X, y, W: _fit_tol_jit(X, y, W, cfg, tol=1e-3,
                                     stop_rule="kkt", check_every=4)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return str(jax.make_jaxpr(f)(s(m, n, p), s(m, n), s(m, m)))


@pytest.mark.parametrize("n,p,backend,kernels", [
    (40000, 2001, "auto", 4), (200, 501, "auto", 0), (40000, 2001, "jnp", 0)],
    ids=["epsilon-auto", "p500-auto", "epsilon-jnp"])
def test_the_fit_program_routes_every_product_by_the_rule(
        monkeypatch, n, p, backend, kernels):
    """On a TPU the epsilon fit streams the round, the KKT check and the
    power iteration (in its loop and after it) through the kernel; the
    paper's p500 block and the jnp backend keep XLA's products."""
    monkeypatch.setattr(solver, "_platform", lambda: "tpu")
    jaxpr = _fit_jaxpr(10, n, p, backend)
    assert jaxpr.count(f"name={xpass.NAME}") == kernels


@pytest.fixture
def forced(monkeypatch):
    """Every unmasked "auto" product takes the kernel, in interpret mode;
    yields the weight map of each kernel call traced.  Jit caches are
    dropped around the test so no program traced under the patch outlives
    it."""
    monkeypatch.setattr(solver, "_platform", lambda: "tpu")
    monkeypatch.setattr(solver, "XPASS_MIN_BYTES", 0)
    traced = []
    kernel = ops.xpass

    def spy(*args, **kw):
        traced.append(kw["weight"])
        return kernel(*args, **kw)

    monkeypatch.setattr(ops, "xpass", spy)
    jax.clear_caches()
    yield traced
    jax.clear_caches()


def _problem(m=4, n=150, p=40, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, n, p)).astype(np.float32)
    b = np.zeros(p, np.float32)
    b[:3] = 1.0
    y = np.sign(X @ b + 0.3 * rng.standard_normal((m, n))).astype(np.float32)
    W = graph.erdos_renyi(m, 0.6, seed=seed).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y), W


def test_decsvm_fit_tol_with_the_kernel_matches_jnp(forced):
    X, y, W = _problem()
    cfg = ADMMConfig(lam=0.05, h=H, max_iter=60)
    jnp_cfg = ADMMConfig(lam=0.05, h=H, max_iter=60, backend="jnp")
    B, t = decsvm_fit_tol(X, y, jnp.asarray(W), cfg, tol=1e-4,
                          stop_rule="kkt")
    Bj, tj = decsvm_fit_tol(X, y, jnp.asarray(W), jnp_cfg, tol=1e-4,
                            stop_rule="kkt")
    assert sorted(set(forced)) == ["linear", "loss"]
    assert int(t) == int(tj)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Bj), atol=1e-5)


def test_fit_server_program_takes_the_kernel_under_vmap(forced):
    """``decsvm_fit_many`` (the fit server's bucket program) vmaps the fit
    over problems; the kernel is batched by ``pallas_call``."""
    probs = [_problem(seed=s) for s in (4, 5, 6)]
    Xs = jnp.stack([p[0] for p in probs])
    ys = jnp.stack([p[1] for p in probs])
    Ws = jnp.asarray(np.stack([p[2] for p in probs]))
    lams = jnp.asarray([0.03, 0.05, 0.08], jnp.float32)
    cfg = ADMMConfig(lam=0.0, h=H, max_iter=30)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: decsvm_fit_many(*a, cfg))(Xs, ys, Ws, lams))
    assert xpass.NAME in jaxpr and "loss" in forced
    got = decsvm_fit_many(Xs, ys, Ws, lams, cfg)
    want = decsvm_fit_many(Xs, ys, Ws, lams,
                           ADMMConfig(lam=0.0, h=H, max_iter=30,
                                      backend="jnp"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_chunked_engine_takes_the_kernel_per_chunk(forced):
    """The chunked engine vmaps the round over its chunk inside
    ``shard_map``; the rule applies per chunk as in the dense engine."""
    X, y, W = _problem(m=6, n=130, p=24, seed=7)
    cfg = ADMMConfig(lam=0.05, h=H, max_iter=40)
    got = decentral.decsvm_fit_chunked(X, y, W, cfg)
    want = decentral.decsvm_fit_chunked(
        X, y, W, ADMMConfig(lam=0.05, h=H, max_iter=40, backend="jnp"))
    assert "loss" in forced
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_kernel_share_metric_reads_the_kernel_ops_of_a_trace():
    """``bench/metrics/kernel_share.fit.py`` on a hand-made reduction: the
    kernel's own name or a custom call counts, XLA's fusions do not; no
    trace reads nothing."""
    import types

    from bench import harness
    from bench.trace import Summary
    metric = harness.load_module("metrics", "kernel_share.fit")
    ops_s = {"decsvm_xpass.1 = f32[10,1,2001] custom-call(f32[10,2001,40000]"
             "), custom_call_target=\"tpu_custom_call\"": 3.0,
             "custom-call.7 = f32[8] custom-call(f32[8])": 0.5,
             "fusion.105 = f32[10,1,40000] fusion(f32[10,40000,2001])": 1.0}
    summary = Summary(window_s=5.0, busy_s=5.0, devices=1, op_seconds=ops_s,
                      gap_seconds={})
    assert metric.read(types.SimpleNamespace(trace=summary)) == 70.0
    assert metric.read(types.SimpleNamespace(trace=None)) is None
