"""Chunked node-megabatch engine (schedule="block"): block-sparse
topology operands on the host, and chunked-vs-dense parity on a real
8-device mesh (subprocesses, so the XLA device-count flag never leaks
into the main test process)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import graph

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# -- host-side: BlockTopology and the generators ----------------------------


def test_block_topology_roundtrip_and_invariants():
    W = graph.erdos_renyi(12, 0.4, seed=0)
    top = graph.BlockTopology.from_dense(W)
    assert top.m == 12
    np.testing.assert_array_equal(top.to_dense(), W.astype(np.float32))
    np.testing.assert_array_equal(top.degrees(), W.sum(axis=1))
    assert top.n_edges == int(W.sum()) // 2
    assert top.is_connected() == graph.is_connected(W)


def test_block_topology_rejects_malformed_adjacency():
    with pytest.raises(AssertionError):
        graph.BlockTopology([[0], [0]])          # self-loop at node 0
    with pytest.raises(AssertionError):
        graph.BlockTopology([[1], []])           # asymmetric edge


@pytest.mark.parametrize("make,kwargs,m", [
    (graph.ring_of_cliques, dict(cliques=4, size=5), 20),
    (graph.k_regular, dict(m=20, k=4), 20),
    (graph.watts_strogatz, dict(m=20, k=4, beta=0.3, seed=0), 20),
])
def test_generators_connected_symmetric_no_self_loops(make, kwargs, m):
    top = make(**kwargs)
    assert top.m == m
    assert top.is_connected()
    W = top.to_dense()
    np.testing.assert_array_equal(W, W.T)
    assert np.all(np.diag(W) == 0)
    if make is graph.k_regular:
        np.testing.assert_array_equal(top.degrees(), np.full(m, 4.0))


def test_chunk_operands_reconstruct_dense_adjacency():
    """W_diag + the kept off-diagonal block diagonals ARE the adjacency:
    scatter them back into an (m_pad, m_pad) matrix and compare."""
    top = graph.ring_of_cliques(cliques=3, size=5)   # m=15, uneven over 4
    n_chunks = 4
    W_diag, offsets, W_off = top.chunk_operands(n_chunks)
    mc = -(-top.m // n_chunks)
    m_pad = mc * n_chunks
    assert W_diag.shape == (m_pad, mc)
    assert W_off.shape == (len(offsets), m_pad, mc)
    dense = np.zeros((m_pad, m_pad), np.float32)
    for c in range(n_chunks):
        rows = slice(c * mc, (c + 1) * mc)
        dense[rows, rows] = W_diag[rows]
        for j, k in enumerate(offsets):
            tgt = (c + k) % n_chunks
            dense[rows, tgt * mc:(tgt + 1) * mc] = W_off[j, rows]
    np.testing.assert_array_equal(dense[:top.m, :top.m], top.to_dense())
    assert np.all(dense[top.m:] == 0) and np.all(dense[:, top.m:] == 0)
    # block_mask agrees with the offsets actually kept
    mask = top.block_mask(n_chunks)
    for c in range(n_chunks):
        for t in range(n_chunks):
            k = (t - c) % n_chunks
            blk = dense[c * mc:(c + 1) * mc, t * mc:(t + 1) * mc]
            assert mask[c, t] == bool(blk.any())
            if k not in (0, *offsets):
                assert not blk.any()


def test_block_mask_skips_absent_ring_offsets():
    """A ring keeps only the +-1 block offsets at mc=1 — distant blocks
    are statically absent from the chunked operands."""
    top = graph.BlockTopology.from_dense(graph.ring(8))
    _, offsets, _ = top.chunk_operands(8)
    assert set(offsets) == {1, 7}


# -- 8-device parity: chunked vs dense --------------------------------------


def test_chunked_fit_matches_dense_all_backends_and_drivers():
    """m=16 over 8 devices (2 nodes/chunk): the chunked engine matches
    the dense single-device reference across backends x {fixed, tol,
    path} drivers, to float32 round-off."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ADMMConfig, decsvm_fit, decentral, graph
        rng = np.random.default_rng(0)
        m, n, p = 16, 10, 6
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        W = graph.erdos_renyi(m, 0.4, seed=1)
        for backend in ("jnp", "pallas", "megakernel"):
            cfg = ADMMConfig(lam=0.1, max_iter=40, backend=backend)
            Bd = np.asarray(decsvm_fit(jnp.asarray(X), jnp.asarray(y),
                                       jnp.asarray(W), cfg))
            Bc = np.asarray(decentral.decsvm_fit_chunked(
                jnp.asarray(X), jnp.asarray(y), W, cfg))
            dev = np.abs(Bd - Bc).max()
            print(backend, "fit", dev)
            assert dev <= 1e-5, (backend, dev)
        cfg = ADMMConfig(lam=0.1, max_iter=200)
        Bt, rounds = decentral.decsvm_fit_chunked(
            jnp.asarray(X), jnp.asarray(y), W, cfg, tol=1e-6)
        from repro.core.admm_adaptive import decsvm_fit_tol
        Bdt, rd = decsvm_fit_tol(jnp.asarray(X), jnp.asarray(y),
                                 jnp.asarray(W), cfg, tol=1e-6)
        dev = np.abs(np.asarray(Bt) - np.asarray(Bdt)).max()
        print("tol", dev, int(rounds), int(rd))
        assert dev <= 1e-5, dev
        lams = np.geomspace(0.5, 0.05, 4).astype(np.float32)
        from repro.core.path import decsvm_path_batched
        Pd = np.asarray(decsvm_path_batched(jnp.asarray(X), jnp.asarray(y),
                                            jnp.asarray(W, jnp.float32),
                                            jnp.asarray(lams), cfg))
        Pc = np.asarray(decentral.decsvm_path_chunked(
            jnp.asarray(X), jnp.asarray(y), W, lams, cfg))
        dev = np.abs(Pd - Pc).max()
        print("path", dev)
        assert dev <= 1e-5, dev
    """)
    assert "path" in out


def test_uneven_final_chunk_padding_rows_are_exact_noops():
    """m=13 over 8 devices (mc=2, 3 ghost rows): parity with dense AND
    the padded rows of the raw chunked state stay identically zero."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ADMMConfig, decsvm_fit, decentral, graph
        rng = np.random.default_rng(3)
        m, n, p = 13, 10, 6
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        top = graph.BlockTopology.from_dense(graph.ring(m))
        cfg = ADMMConfig(lam=0.1, max_iter=40)
        Bd = np.asarray(decsvm_fit(jnp.asarray(X), jnp.asarray(y),
                                   jnp.asarray(top.to_dense()), cfg))
        Bc = np.asarray(decentral.decsvm_fit_chunked(
            jnp.asarray(X), jnp.asarray(y), top, cfg))
        dev = np.abs(Bd - Bc).max()
        print("uneven", dev)
        assert dev <= 1e-5, dev
        # raw padded state: ghost rows bit-zero after 40 rounds
        mesh = decentral.make_node_chunk_mesh()
        ops, offsets, m_pad = decentral._chunk_prep(
            jnp.asarray(X), jnp.asarray(y), top, mesh)
        fitted = decentral.build_chunked_admm(m_pad, p, cfg, mesh, offsets)
        Bp, _ = fitted(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                       ops["deg"], jnp.ones((p,), jnp.float32),
                       ops["nmask"])
        ghost = np.asarray(Bp)[m:]
        print("ghost", np.abs(ghost).max(), m_pad - m)
        assert m_pad == 16 and np.all(ghost == 0.0)
    """)
    assert "ghost" in out


def test_mesh_block_schedule_matches_dense_mesh():
    """decsvm_path_mesh(schedule="block") — fused selection on the
    (node_chunk, lam) mesh — agrees with the dense mesh engine, for the
    batched/BIC and warm/CV modes."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ADMMConfig, decentral, graph
        rng = np.random.default_rng(5)
        m, n, p = 16, 12, 6
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        W = graph.ring(m)
        cfg = ADMMConfig(lam=0.1, max_iter=40)
        lams = np.geomspace(0.5, 0.05, 4).astype(np.float32)
        rd = decentral.decsvm_path_mesh(X, y, W, lams, cfg)
        rb = decentral.decsvm_path_mesh(X, y, W, lams, cfg,
                                        schedule="block")
        dev = np.abs(np.asarray(rd.path) - np.asarray(rb.path)).max()
        cdev = np.abs(np.asarray(rd.criteria) - np.asarray(rb.criteria)).max()
        print("bic", dev, cdev)
        assert dev <= 1e-5 and cdev <= 1e-5, (dev, cdev)
        assert float(rd.best_lam) == float(rb.best_lam)
        rcv = decentral.decsvm_path_mesh(X, y, W, lams, cfg,
                                         criterion="cv", cv_folds=3)
        rbc = decentral.decsvm_path_mesh(X, y, W, lams, cfg,
                                         criterion="cv", cv_folds=3,
                                         schedule="block")
        cdev = np.abs(np.asarray(rcv.criteria) - np.asarray(rbc.criteria)).max()
        print("cv", cdev)
        assert cdev <= 1e-5, cdev
    """)
    assert "cv" in out


def test_chunked_smoke_m64_on_8_devices():
    """The CI smoke: a 64-node network — 8x more nodes than devices —
    fits through one compiled program and the result is sane."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ADMMConfig, decentral, graph
        rng = np.random.default_rng(7)
        m, n, p = 64, 12, 8
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:3] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        top = graph.ring_of_cliques(cliques=8, size=8)
        cfg = ADMMConfig(lam=0.05, max_iter=60)
        B = np.asarray(decentral.decsvm_fit_chunked(
            jnp.asarray(X), jnp.asarray(y), top, cfg))
        assert B.shape == (m, p) and np.all(np.isfinite(B))
        gap = np.abs(B - B.mean(axis=0)).max()
        sign_acc = (np.sign(B.mean(axis=0)[:3]) == 1.0).all()
        print("smoke", gap, bool(sign_acc))
        assert gap < 0.5 and sign_acc
    """)
    assert "smoke" in out


def test_chunked_serving_auto_routes_large_m():
    """FitRequest(engine="auto") routes m > ndev to the chunked engine
    and never co-buckets with a dense request."""
    out = run_py("""
        import numpy as np, jax
        from repro.core import ADMMConfig, graph
        from repro.serving.fit import DecsvmFitServer, FitRequest
        rng = np.random.default_rng(9)
        m, n, p = 16, 8, 5
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        top = graph.BlockTopology.from_dense(graph.ring(m))
        lams = np.geomspace(0.5, 0.05, 3)
        cfg = ADMMConfig(lam=0.0, max_iter=30)
        srv = DecsvmFitServer()
        h1 = srv.submit(FitRequest(rid=1, X=X, y=y, W=top, cfg=cfg,
                                   lams=lams, mode="batched"))
        h2 = srv.submit(FitRequest(rid=2, X=X[:8], y=y[:8],
                                   W=graph.ring(8), cfg=cfg, lams=lams,
                                   mode="batched"))
        srv.run()
        r1, r2 = h1.result(), h2.result()
        keys = [k for k, _ in srv.bucket_log]
        assert keys[0][-1] == "chunked" and keys[1][-1] == "dense", keys
        assert np.all(np.isfinite(r1.B)) and r1.B.shape == (m, p)
        print("serving", r1.best_lam, r2.best_lam)
    """)
    assert "serving" in out
