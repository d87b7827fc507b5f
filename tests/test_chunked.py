"""Chunked node-megabatch engine: block-sparse topology operands on the
host, and chunked-vs-dense parity on a real 8-device mesh (subprocesses,
so the XLA device-count flag never leaks into the main test process)."""
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
    """W_diag + the kept off-diagonal blocks, each in its halo columns of
    W_off, ARE the adjacency: scatter them back into an (m_pad, m_pad)
    matrix and compare."""
    top = graph.ring_of_cliques(cliques=3, size=5)   # m=15, uneven over 4
    n_chunks = 4
    W_diag, halos, W_off = top.chunk_operands(n_chunks)
    mc = -(-top.m // n_chunks)
    m_pad = mc * n_chunks
    assert W_diag.shape == (m_pad, mc)
    assert W_off.shape == (m_pad, sum(len(rows) for _, rows in halos))
    dense = np.zeros((m_pad, m_pad), np.float32)
    for c in range(n_chunks):
        rows = slice(c * mc, (c + 1) * mc)
        dense[rows, rows] = W_diag[rows]
        base = 0
        for k, halo in halos:
            assert list(halo) == sorted(set(halo))
            tgt = (c + k) % n_chunks
            dense[rows, [tgt * mc + r for r in halo]] = \
                W_off[rows, base:base + len(halo)]
            base += len(halo)
    np.testing.assert_array_equal(dense[:top.m, :top.m], top.to_dense())
    assert np.all(dense[top.m:] == 0) and np.all(dense[:, top.m:] == 0)
    # block_mask agrees with the offsets actually kept
    offsets = [k for k, _ in halos]
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
    _, halos, _ = top.chunk_operands(8)
    assert {k for k, _ in halos} == {1, 7}


def test_lattice_halo_is_two_rows_per_offset():
    """The p2p cell's graph, k_regular(1024, 4) over 4 chunks of 256:
    block (d, d+1) reads rows 0-1 of chunk d+1, block (d, d+3) rows
    254-255 of chunk d-1, so each offset sends 2 rows."""
    _, halos, W_off = graph.k_regular(1024, 4).chunk_operands(4)
    assert halos == ((1, (0, 1)), (3, (254, 255)))
    assert W_off.shape == (1024, 4)


def test_whole_chunk_halo_where_every_row_is_read():
    """A dense Erdos-Renyi graph reads every row of every other chunk:
    each halo is the whole chunk, and W_off holds the full off-diagonal
    blocks."""
    top = graph.BlockTopology.from_dense(graph.erdos_renyi(16, 0.9, seed=0))
    _, halos, W_off = top.chunk_operands(4)
    assert [k for k, _ in halos] == [1, 2, 3]
    assert all(rows == (0, 1, 2, 3) for _, rows in halos)
    assert W_off.shape == (16, 12)


def test_exchange_counter_reads_the_halo_share(monkeypatch):
    """``trace.exchange`` counts the rows each kept offset sends as the
    neighbour sum is built: 2 of 256 per offset on the p2p lattice
    (0.78%), all of them where the halo is the whole chunk (100%)."""
    from repro.core import decentral, trace
    for top, chunks, share in (
            (graph.k_regular(1024, 4), 4, 100.0 * 4 / 512),
            (graph.BlockTopology.from_dense(
                graph.erdos_renyi(16, 0.9, seed=0)), 4, 100.0)):
        counter = trace.ExchangeCounter()
        monkeypatch.setattr(trace, "exchange", counter)
        assert counter.share() is None
        W_diag, halos, W_off = top.chunk_operands(chunks)
        mc = W_diag.shape[1]
        decentral._block_neighbor_sum_fn("node_chunk", chunks, W_diag[:mc],
                                         W_off[:mc], halos)
        assert counter.share() == pytest.approx(share)


# -- halo exchange: the neighbour sum on 4 devices --------------------------

HALO_CASES = {
    # (topology, chunks): lattice halos of 2 rows, contiguous; on two
    # chunks offset 1 reads both ends of a chunk (a gather); cliques
    # bridged across chunks; a dense Erdos-Renyi graph whose halo is the
    # whole chunk; m = 13 padding the tail chunk with ghost rows
    "lattice": "graph.k_regular(32, 4), 4",
    "lattice_two_chunks": "graph.k_regular(32, 4), 2",
    "ring_of_cliques": "graph.ring_of_cliques(4, 5), 4",
    "erdos_renyi_whole_chunk": (
        "graph.BlockTopology.from_dense(graph.erdos_renyi(16, 0.9, seed=0)), 4"),
    "padded_tail": "graph.k_regular(13, 4), 4",
}

HALO_SCRIPT = """
import numpy as np, jax
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import decentral, graph
for name, (top, chunks) in {cases}.items():
    W_diag, halos, W_off = top.chunk_operands(chunks)
    m_pad = W_diag.shape[0]
    B = np.zeros((m_pad, 7), np.float32)
    B[:top.m] = np.random.default_rng(0).normal(size=(top.m, 7))
    mesh = Mesh(np.array(jax.devices()[:chunks]), ("node_chunk",))
    body = lambda Bl, Wd, Woff: decentral._block_neighbor_sum_fn(
        "node_chunk", chunks, Wd, Woff, halos)(Bl)
    S = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("node_chunk"),) * 3,
        out_specs=P("node_chunk")))(B, W_diag, W_off)
    S = np.asarray(S)
    ref = top.to_dense().astype(np.float64) @ B[:top.m]
    err = np.abs(S[:top.m] - ref).max() / np.abs(ref).max()
    ghost = np.abs(S[top.m:]).max() if m_pad > top.m else 0.0
    rows = max(len(r) for _, r in halos)
    print("HALO", name, err, ghost, rows, m_pad // chunks)
"""


@pytest.fixture(scope="module")
def halo_sums():
    cases = "{" + ", ".join(f"{k!r}: ({v})" for k, v in HALO_CASES.items()) + "}"
    out = run_py(HALO_SCRIPT.format(cases=cases), devices=4)
    return {f[1]: tuple(float(x) for x in f[2:])
            for f in (l.split() for l in out.splitlines())
            if f and f[0] == "HALO"}


@pytest.mark.parametrize("case", list(HALO_CASES))
def test_halo_neighbor_sum_equals_dense(case, halo_sums):
    """The halo exchange's (W B)_l on 4 CPU devices equals the dense
    W @ B to fp32 rounding, ghost rows stay zero, and only the whole-chunk
    case sends whole chunks."""
    err, ghost, rows, mc = halo_sums[case]
    assert err <= 1e-6 and ghost == 0.0, (err, ghost)
    assert (rows == mc) == (case == "erdos_renyi_whole_chunk"), (rows, mc)


def test_halo_fit_equals_whole_chunk_exchange():
    """A tol fit of a 16-node lattice on 4 devices gives the same B, bit
    for bit, and the same rounds, whether the exchange sends each
    offset's halo rows or, as the full-block formulas did, whole chunks
    into (mc, mc) blocks."""
    out = run_py("""
        import numpy as np, jax.numpy as jnp
        from repro.core import ADMMConfig, decentral, graph
        rng = np.random.default_rng(11)
        m, n, p = 16, 10, 6
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        cfg = ADMMConfig(lam=0.1, max_iter=120)
        mesh = decentral.make_node_chunk_mesh(4)

        def fit(top):
            return decentral.decsvm_fit_chunked(
                jnp.asarray(X), jnp.asarray(y), top, cfg, mesh=mesh,
                tol=1e-6)

        halo = graph.k_regular(m, 4)
        assert [len(r) for _, r in halo.chunk_operands(4)[1]] == [2, 2]

        class WholeChunks(graph.BlockTopology):
            def chunk_operands(self, n_chunks):
                W_diag, halos, W_off = super().chunk_operands(n_chunks)
                mc = W_diag.shape[1]
                full = np.zeros((W_off.shape[0], mc * len(halos)),
                                np.float32)
                base = 0
                for j, (_, rows) in enumerate(halos):
                    full[:, [j * mc + r for r in rows]] = \
                        W_off[:, base:base + len(rows)]
                    base += len(rows)
                return (W_diag, tuple((k, tuple(range(mc)))
                                      for k, _ in halos), full)

        B1, t1 = fit(halo)
        B2, t2 = fit(WholeChunks(halo.neighbors))
        print("FIT", int(t1), int(t2), np.abs(np.asarray(B1)).max())
        assert int(t1) == int(t2)
        np.testing.assert_array_equal(np.asarray(B1), np.asarray(B2))
    """, devices=4)
    assert "FIT" in out


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
    """decsvm_path_mesh — fused selection on the (node_chunk, lam) mesh —
    agrees with the dense path engine (``path.decsvm_path_select``) for
    BIC and CV selection."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ADMMConfig, decentral, graph
        from repro.core.path import decsvm_path_select
        rng = np.random.default_rng(5)
        m, n, p = 16, 12, 6
        X = rng.normal(size=(m, n, p)).astype(np.float32)
        b = np.zeros(p, np.float32); b[:2] = 1.0
        y = np.sign(X @ b + 0.1*rng.normal(size=(m, n))).astype(np.float32)
        W = graph.ring(m)
        Wj = jnp.asarray(W, jnp.float32)
        cfg = ADMMConfig(lam=0.1, max_iter=40)
        lams = np.geomspace(0.5, 0.05, 4).astype(np.float32)
        rd = decsvm_path_select(jnp.asarray(X), jnp.asarray(y), Wj,
                                jnp.asarray(lams), cfg, mode="batched")
        rb = decentral.decsvm_path_mesh(X, y, W, lams, cfg)
        dev = np.abs(np.asarray(rd.path) - np.asarray(rb.path)).max()
        cdev = np.abs(np.asarray(rd.criteria) - np.asarray(rb.criteria)).max()
        print("bic", dev, cdev)
        assert dev <= 1e-5 and cdev <= 1e-5, (dev, cdev)
        assert float(rd.best_lam) == float(rb.best_lam)
        rcv = decsvm_path_select(jnp.asarray(X), jnp.asarray(y), Wj,
                                 jnp.asarray(lams), cfg, mode="batched",
                                 criterion="cv", cv_folds=3)
        rbc = decentral.decsvm_path_mesh(X, y, W, lams, cfg,
                                         criterion="cv", cv_folds=3)
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
