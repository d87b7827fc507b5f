"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Shape/dtype sweeps per the deliverable: every kernel is checked across
non-aligned shapes, dtypes, and config axes (kernel family, masks, GQA)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _csvm_inputs(n, p, dtype=jnp.float32):
    X = jnp.asarray(RNG.standard_normal((n, p)), dtype)
    y = jnp.asarray(RNG.choice([-1.0, 1.0], n), dtype)
    beta = jnp.asarray(RNG.standard_normal(p) * 0.1, dtype)
    pd = jnp.asarray(RNG.standard_normal(p) * 0.01, dtype)
    ng = jnp.asarray(RNG.standard_normal(p) * 0.05, dtype)
    return X, y, beta, pd, ng


@pytest.mark.parametrize("n,p", [(8, 8), (100, 37), (256, 512), (53, 700),
                                 (512, 128), (33, 129)])
@pytest.mark.parametrize("kernel", ["epanechnikov", "gaussian", "logistic",
                                    "laplacian", "uniform"])
def test_csvm_update_shapes_kernels(n, p, kernel):
    X, y, beta, pd, ng = _csvm_inputs(n, p)
    got = ops.csvm_local_update(X, y, beta, pd, ng, 2.0, 0.1, 0.05,
                                h=0.25, kernel=kernel)
    want = ref.decsvm_local_update(X, y, beta, pd, ng, 2.0, 0.1, 0.05,
                                   0.25, kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csvm_update_dtypes(dtype):
    X, y, beta, pd, ng = _csvm_inputs(64, 96, dtype)
    got = ops.csvm_local_update(X, y, beta, pd, ng, 2.0, 0.1, 0.05, h=0.25)
    want = ref.decsvm_local_update(X.astype(jnp.float32),
                                   y.astype(jnp.float32),
                                   beta.astype(jnp.float32),
                                   pd.astype(jnp.float32),
                                   ng.astype(jnp.float32),
                                   2.0, 0.1, 0.05, 0.25)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)
    assert got.dtype == dtype


@settings(max_examples=10, deadline=None)
@given(n=st.integers(4, 80), p=st.integers(4, 200),
       rho=st.floats(0.5, 4.0), lam=st.floats(0.0, 0.5))
def test_csvm_update_property(n, p, rho, lam):
    X, y, beta, pd, ng = _csvm_inputs(n, p)
    omega = 1.0 / (rho + 2.0)
    got = ops.csvm_local_update(X, y, beta, pd, ng, rho, omega, lam, h=0.3)
    want = ref.decsvm_local_update(X, y, beta, pd, ng, rho, omega, lam,
                                   0.3, "epanechnikov")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _attn_inputs(B, H, KV, S, D, dtype=jnp.float32):
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, KV, S, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, KV, S, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 2, 2, 128, 64), (2, 4, 2, 256, 64), (1, 4, 1, 128, 32),
    (1, 8, 2, 200, 64), (1, 14, 2, 128, 64),   # internvl2 head config
    (1, 10, 1, 128, 128),                       # MQA wide-head
])
def test_flash_attention_shapes(B, H, KV, S, D):
    q, k, v = _attn_inputs(B, H, KV, S, D)
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.mha(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64), (True, 17)])
def test_flash_attention_masks(causal, window):
    q, k, v = _attn_inputs(1, 4, 2, 160, 32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = ref.mha(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    q, k, v = _attn_inputs(1, 4, 2, 128, 64, jnp.bfloat16)
    got = ops.flash_attention(q, k, v)
    want = ref.mha(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 8, 16, 32), (2, 128, 3, 16, 32, 64),
    (1, 96, 4, 32, 128, 32),   # mamba2-370m head geometry (scaled)
    (1, 128, 1, 8, 16, 128),   # single chunk == whole sequence
])
def test_ssd_scan_kernel(b, s, h, p, n, chunk):
    from repro.models.ssm import ssd_naive
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = -jnp.asarray(np.abs(RNG.standard_normal(h)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    D = jnp.asarray(np.abs(RNG.standard_normal(h)), jnp.float32)
    got = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk)
    want, _ = ssd_naive(x, dt, A, B, C, D=D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_ssd_scan_kernel_matches_model_chunked():
    """Kernel and the model's XLA chunked path agree (interchangeable)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 1, 128, 2, 16, 32
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = -jnp.asarray(np.abs(RNG.standard_normal(h)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    D = jnp.asarray(np.abs(RNG.standard_normal(h)), jnp.float32)
    got = ops.ssd_scan(x, dt, A, B, C, D, chunk=64)
    want, _ = ssd_chunked(x, dt, A, B, C, 64, D=D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_flash_attention_matches_model_attention():
    """The pure-XLA q-chunked path (models.attention) and the Pallas kernel
    agree — they are interchangeable implementations of the same op."""
    from repro.models.attention import _attend
    B, H, KV, S, D = 1, 4, 2, 128, 32
    q4 = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k4 = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    v4 = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    pos = jnp.arange(S)
    out_xla = _attend(q4, k4, v4, pos, pos, causal=True, window=None)
    out_pl = ops.flash_attention(q4.transpose(0, 2, 1, 3),
                                 k4.transpose(0, 2, 1, 3),
                                 v4.transpose(0, 2, 1, 3),
                                 block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out_xla),
                               np.asarray(out_pl.transpose(0, 2, 1, 3)),
                               atol=2e-5)


def test_csvm_update_lam_vector_matches_oracle():
    """Per-coordinate penalty levels (LLA stage 2) through the fused kernel."""
    X, y, beta, pd, ng = _csvm_inputs(64, 96)
    lamv = jnp.asarray(RNG.uniform(0.0, 0.3, 96), jnp.float32)
    got = ops.csvm_local_update(X, y, beta, pd, ng, 2.0, 0.1, lamv, h=0.25)
    want = ref.decsvm_local_update(X, y, beta, pd, ng, 2.0, 0.1, lamv, 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _mega_inputs(m, n, p, dtype=jnp.float32, tau=1.0, lam0=0.0):
    """Stacked node-block problem + ring topology for the round megakernel."""
    from repro.core.graph import ring
    X = jnp.asarray(RNG.standard_normal((m, n, p)), dtype)
    y = jnp.asarray(RNG.choice([-1.0, 1.0], (m, n)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((m, p)) * 0.1, jnp.float32)
    P = jnp.asarray(RNG.standard_normal((m, p)) * 0.01, jnp.float32)
    W = jnp.asarray(ring(m), jnp.float32)
    deg = jnp.sum(W, axis=1)
    rho = jnp.asarray(np.abs(RNG.standard_normal(m)) + 2.0, jnp.float32)
    omega = 1.0 / (2.0 * tau * deg + rho + lam0)
    return X, y, B, P, W, deg, rho, omega


@pytest.mark.parametrize("m,n,p", [(4, 60, 21), (3, 33, 129), (8, 100, 50)])
@pytest.mark.parametrize("want_kkt", [False, True])
def test_megakernel_round_block_matches_oracle(m, n, p, want_kkt):
    """Five fused rounds + in-kernel stop statistic vs the pure-jnp oracle
    (which is itself literally solver.local_update + dense W sums)."""
    X, y, B, P, W, deg, rho, omega = _mega_inputs(m, n, p)
    args = (X, y, B, P, W, deg, rho, omega, 0.05, 5)
    kw = dict(tau=1.0, lam0=0.0, h=0.25, num_rounds=5, want_kkt=want_kkt)
    Bk, Pk, sk = ops.csvm_round_block(*args, **kw)
    Bo, Po, so = ref.decsvm_round_block(*args, **{k: v for k, v in kw.items()
                                                 if k != "num_rounds"})
    np.testing.assert_allclose(np.asarray(Bk), np.asarray(Bo), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Pk), np.asarray(Po), atol=1e-5)
    np.testing.assert_allclose(float(sk), float(so), atol=1e-6)


def test_megakernel_held_rounds():
    """nact < num_rounds: rounds beyond nact must be exact no-ops (the
    held-round semantics run_tol relies on near max_iter)."""
    X, y, B, P, W, deg, rho, omega = _mega_inputs(4, 40, 24)
    kw = dict(tau=1.0, lam0=0.0, h=0.25)
    Bk, Pk, sk = ops.csvm_round_block(X, y, B, P, W, deg, rho, omega,
                                      0.05, 3, num_rounds=6, **kw)
    Bo, Po, so = ref.decsvm_round_block(X, y, B, P, W, deg, rho, omega,
                                        0.05, 3, **kw)
    np.testing.assert_allclose(np.asarray(Bk), np.asarray(Bo), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Pk), np.asarray(Po), atol=1e-5)
    np.testing.assert_allclose(float(sk), float(so), atol=1e-6)


def test_megakernel_lam_vector_and_elastic_net():
    """Per-coordinate l1 levels (LLA stage 2) and lam0 > 0 ride the fused
    rounds and the in-kernel KKT epilogue."""
    m, n, p = 4, 48, 40
    X, y, B, P, W, deg, rho, omega = _mega_inputs(m, n, p, lam0=0.1)
    lamv = jnp.asarray(RNG.uniform(0.01, 0.3, p), jnp.float32)
    kw = dict(tau=1.0, lam0=0.1, h=0.25, want_kkt=True)
    Bk, Pk, sk = ops.csvm_round_block(X, y, B, P, W, deg, rho, omega,
                                      lamv, 4, num_rounds=4, **kw)
    Bo, Po, so = ref.decsvm_round_block(X, y, B, P, W, deg, rho, omega,
                                        lamv, 4, **kw)
    np.testing.assert_allclose(np.asarray(Bk), np.asarray(Bo), atol=1e-5)
    np.testing.assert_allclose(float(sk), float(so), atol=1e-6)


def test_megakernel_bf16_mixed_precision_bound():
    """bf16 X / fp32 accumulators: outputs stay fp32 and the recorded
    parity bound vs the fp32 oracle holds (measured ~5e-4 over 5 rounds)."""
    m, n, p = 4, 60, 32
    X, y, B, P, W, deg, rho, omega = _mega_inputs(m, n, p)
    kw = dict(tau=1.0, lam0=0.0, h=0.25)
    Bk, Pk, sk = ops.csvm_round_block(X.astype(jnp.bfloat16), y, B, P, W,
                                      deg, rho, omega, 0.05, 5,
                                      num_rounds=5, want_kkt=True, **kw)
    Bo, Po, so = ref.decsvm_round_block(X, y, B, P, W, deg, rho, omega,
                                        0.05, 5, want_kkt=True, **kw)
    assert Bk.dtype == jnp.float32 and Pk.dtype == jnp.float32
    assert sk.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(Bk), np.asarray(Bo), atol=5e-2)
    np.testing.assert_allclose(np.asarray(Pk), np.asarray(Po), atol=5e-2)
    np.testing.assert_allclose(float(sk), float(so), atol=5e-2)


def test_megakernel_block_update_matches_oracle():
    """The single-round block kernel (neighbour term as an operand, for
    sharded engines whose collectives live outside the kernel)."""
    from repro.core import solver
    m, n, p = 4, 52, 36
    X, y, B, P, W, deg, rho, omega = _mega_inputs(m, n, p)
    neigh = 1.0 * (deg[:, None] * B + W @ B)
    got = ops.csvm_block_update(X, y, B, P, neigh, rho, omega, 0.05,
                                h=0.25)
    want = jax.vmap(lambda Xl, yl, bl, pl, nl, rl, wl: solver.local_update(
        Xl, yl, bl, pl, nl, rl, wl, 0.05, h=0.25, kernel="epanechnikov")
    )(X, y, B, P, neigh, rho, omega)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_megakernel_vmap_batches_cleanly():
    """vmap over a batch of problems (the path engine's axis) — including
    the traced nact scalar — matches per-problem kernel calls."""
    m, n, p = 3, 30, 16
    X, y, B, P, W, deg, rho, omega = _mega_inputs(m, n, p)
    Xs = jnp.stack([X, X * 1.1])
    nacts = jnp.asarray([3, 2], jnp.int32)
    kw = dict(tau=1.0, lam0=0.0, h=0.25, num_rounds=3, want_kkt=True)
    Bb, Pb, sb = jax.vmap(
        lambda Xb, nb: ops.csvm_round_block(Xb, y, B, P, W, deg, rho,
                                            omega, 0.05, nb, **kw)
    )(Xs, nacts)
    for i in range(2):
        Bi, Pi, si = ops.csvm_round_block(Xs[i], y, B, P, W, deg, rho,
                                          omega, 0.05, nacts[i], **kw)
        np.testing.assert_allclose(np.asarray(Bb[i]), np.asarray(Bi),
                                   atol=1e-6)
        np.testing.assert_allclose(float(sb[i]), float(si), atol=1e-6)


def test_megakernel_vmem_guard():
    """The VMEM residency guard admits the bench shape on-chip budgets and
    rejects problems whose whole-state footprint cannot fit."""
    assert ops.megakernel_supported(8, 100, 50, interpret=False)
    assert not ops.megakernel_supported(64, 4096, 4096, interpret=False)
    # bf16 X halves the X window; on v5e its scoped working copies of X
    # (one fp32, one bf16) cost more than the window saves
    from repro.kernels.csvm_update import megakernel_vmem
    w16, s16 = megakernel_vmem(8, 100, 50, 2)
    w32, s32 = megakernel_vmem(8, 100, 50, 4)
    assert w16 < w32 and s16 > s32


def test_admm_pallas_with_lam_weights_matches_dense():
    """LLA stage 2 (non-uniform lam_weights) no longer silently falls back
    to the dense path: the Pallas route agrees with it."""
    from repro.core import ADMMConfig, SimConfig, decsvm_fit, generate
    from repro.core.graph import erdos_renyi
    cfg = SimConfig(p=20, s=4, m=4, n=60)
    X, y, _ = generate(cfg, seed=1)
    W = jnp.asarray(erdos_renyi(cfg.m, 0.8, seed=0), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.2, 1.0, cfg.p + 1), jnp.float32)
    dense = decsvm_fit(jnp.asarray(X), jnp.asarray(y), W,
                       ADMMConfig(lam=0.08, max_iter=40), lam_weights=w)
    pallas = decsvm_fit(jnp.asarray(X), jnp.asarray(y), W,
                        ADMMConfig(lam=0.08, max_iter=40, use_pallas=True),
                        lam_weights=w)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def _pallas_eqn_bytes(fn, *args):
    """Shape-walk the pallas_call equation inside ``fn``'s jaxpr: total
    VMEM bytes of its operand + output avals, each tile-padded (recursing
    through jit wrappers)."""
    from repro.kernels.csvm_update import _tiled_bytes
    jaxpr = jax.make_jaxpr(fn)(*args)

    def sub_jaxprs(val):
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            name = type(v).__name__
            if name == "ClosedJaxpr":
                yield v.jaxpr
            elif name == "Jaxpr":
                yield v

    def find(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for val in eqn.params.values():
                for sub in sub_jaxprs(val):
                    yield from find(sub)

    eqns = list(find(jaxpr.jaxpr))
    assert len(eqns) == 1, f"expected one pallas_call, found {len(eqns)}"
    (eqn,) = eqns
    return sum(_tiled_bytes(v.aval.shape, v.aval.dtype.itemsize)
               for v in list(eqn.invars) + list(eqn.outvars))


@pytest.mark.parametrize("m,n,p,dtype", [(4, 64, 128, jnp.float32),
                                         (5, 37, 20, jnp.float32),
                                         (4, 64, 128, jnp.bfloat16),
                                         (7, 50, 130, jnp.bfloat16)])
def test_megakernel_vmem_accounting_matches_pallas_operands(m, n, p, dtype):
    """VMEM accounting regression: the window half of
    ``megakernel_vmem`` must equal the tile-padded bytes of the actual
    ``pallas_call`` operands and outputs (each held once in VMEM).  A
    drift here means ``ops.py``'s VMEM guard is admitting (or refusing)
    shapes against a stale footprint.  The scoped half is checked against
    the v5e compiler in tests/test_tpu_compile.py."""
    from repro.kernels.csvm_update import megakernel_vmem

    X = jnp.zeros((m, n, p), dtype)
    y = jnp.zeros((m, n), jnp.float32)
    B = jnp.zeros((m, p), jnp.float32)
    P = jnp.zeros((m, p), jnp.float32)
    W = jnp.zeros((m, m), jnp.float32)
    vec_m = jnp.zeros((m,), jnp.float32)
    lam = jnp.zeros((p,), jnp.float32)

    def run(X, y, B, P, W, deg, rho, omega, lam, nact):
        return ops.csvm_round_block(X, y, B, P, W, deg, rho, omega, lam,
                                    nact, tau=0.5, lam0=1e-4, h=0.5,
                                    num_rounds=2, want_kkt=True)

    window_bytes = _pallas_eqn_bytes(run, X, y, B, P, W, vec_m, vec_m,
                                     vec_m, lam, 2)
    itemsize = jnp.dtype(dtype).itemsize
    assert megakernel_vmem(m, n, p, itemsize)[0] == window_bytes
