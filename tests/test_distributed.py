"""Multi-device behaviour, run in subprocesses so the 8-device XLA flag never
leaks into the main test process."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # Forcing the host-platform device count works on the CPU platform only.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_admm_matches_dense_gather_and_ring():
    """The chunked engine at one node a device: on Erdos-Renyi every kept
    offset sends its whole chunk (the rows an all_gather moves), on a
    ring only offsets 1 and 7 send, one row each (the two boundary-row
    ppermutes); both match the dense fit."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimConfig, generate, ADMMConfig, decsvm_fit
        from repro.core.graph import BlockTopology, erdos_renyi, ring
        from repro.core.decentral import decsvm_fit_chunked
        cfg = SimConfig(p=30, s=5, m=8, n=50)
        X, y, bstar = generate(cfg, seed=2)
        acfg = ADMMConfig(lam=0.05, max_iter=80)
        W = erdos_renyi(8, 0.5, seed=3)
        halos = BlockTopology.from_dense(W).chunk_operands(8)[1]
        assert halos and all(rows == (0,) for _, rows in halos), halos
        Bd = np.asarray(decsvm_fit(jnp.asarray(X), jnp.asarray(y), jnp.asarray(W), acfg))
        Bs = np.asarray(decsvm_fit_chunked(jnp.asarray(X), jnp.asarray(y), W, acfg))
        print("gather", np.max(np.abs(Bd - Bs)))
        Wr = ring(8)
        halos = BlockTopology.from_dense(Wr).chunk_operands(8)[1]
        assert halos == ((1, (0,)), (7, (0,))), halos
        Bdr = np.asarray(decsvm_fit(jnp.asarray(X), jnp.asarray(y), jnp.asarray(Wr), acfg))
        Br = np.asarray(decsvm_fit_chunked(jnp.asarray(X), jnp.asarray(y), Wr, acfg))
        print("ring", np.max(np.abs(Bdr - Br)))
        assert np.max(np.abs(Bd - Bs)) < 1e-4
        assert np.max(np.abs(Bdr - Br)) < 1e-4
    """)
    assert "gather" in out and "ring" in out


def test_sharded_lambda_path_matches_batched_multidevice():
    """The chunked node x lambda path engine (vmap over collectives inside
    shard_map) agrees with the dense batched path on a real 8-device mesh,
    with whole-chunk (Erdos-Renyi) and one-row (ring) halos."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimConfig, generate, ADMMConfig, tuning
        from repro.core.graph import erdos_renyi, ring
        from repro.core.decentral import decsvm_path_chunked
        from repro.core.path import decsvm_path_batched
        cfg = SimConfig(p=30, s=5, m=8, n=50)
        X, y, bstar = generate(cfg, seed=2)
        acfg = ADMMConfig(lam=0.0, max_iter=80)
        lams = tuning.lambda_grid(X, y, num=4)
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        W = erdos_renyi(8, 0.5, seed=3)
        dense = np.asarray(decsvm_path_batched(Xj, yj, jnp.asarray(W), jnp.asarray(lams), acfg))
        shard = np.asarray(decsvm_path_chunked(Xj, yj, W, lams, acfg))
        print("gather", np.max(np.abs(dense - shard)))
        assert np.max(np.abs(dense - shard)) < 1e-4
        Wr = ring(8)
        dense_r = np.asarray(decsvm_path_batched(Xj, yj, jnp.asarray(Wr), jnp.asarray(lams), acfg))
        shard_r = np.asarray(decsvm_path_chunked(Xj, yj, Wr, lams, acfg))
        print("ring", np.max(np.abs(dense_r - shard_r)))
        assert np.max(np.abs(dense_r - shard_r)) < 1e-4
    """)
    assert "gather" in out and "ring" in out


def test_mesh_2d_path_matches_batched_multidevice():
    """The 2-D (node_chunk, lam) mesh engine — grid cells on their own mesh
    axis, fused BIC/CV scoring — agrees with the dense batched path on a
    real 8-device mesh, including warm continuation and lam_weights."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimConfig, generate, ADMMConfig, tuning
        from repro.core.graph import erdos_renyi
        from repro.core import decentral
        from repro.core.path import decsvm_path_batched, decsvm_path_select
        from repro.launch.mesh import make_chunk_lam_mesh
        cfg = SimConfig(p=30, s=5, m=8, n=50)
        X, y, bstar = generate(cfg, seed=2)
        acfg = ADMMConfig(lam=0.0, max_iter=80)
        lams = tuning.lambda_grid(X, y, num=4)
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        W = erdos_renyi(8, 0.5, seed=3)
        dense = np.asarray(decsvm_path_batched(Xj, yj, jnp.asarray(W),
                                               jnp.asarray(lams), acfg))
        ref = decsvm_path_select(Xj, yj, jnp.asarray(W), jnp.asarray(lams),
                                 acfg, mode="batched")
        mesh = make_chunk_lam_mesh(4, 2)
        res = decentral.decsvm_path_mesh(Xj, yj, W, lams, acfg, mesh=mesh)
        print("path", np.max(np.abs(np.asarray(res.path) - dense)))
        assert np.max(np.abs(np.asarray(res.path) - dense)) < 1e-5
        # fused BIC scoring matches the dense criterion, so does the argmin
        assert np.max(np.abs(np.asarray(res.criteria)
                             - np.asarray(ref.criteria))) < 1e-4
        assert abs(float(res.best_lam) - float(ref.best_lam)) < 1e-8
        # warm continuation on the mesh early-stops and lands near batched
        resw = decentral.decsvm_path_mesh(Xj, yj, W, lams, acfg, mesh=mesh,
                                          mode="warm", tol=1e-4)
        assert np.asarray(resw.iters).max() <= 80
        # fused CV scoring: finite, and full-data path unchanged
        # (8 cells = 4 lams x (1 full + 1 fold block)... L*(1+k) % lam axis)
        rescv = decentral.decsvm_path_mesh(Xj, yj, W, lams, acfg, mesh=mesh,
                                           criterion="cv", cv_folds=3)
        assert np.all(np.isfinite(np.asarray(rescv.criteria)))
        assert np.max(np.abs(np.asarray(rescv.path) - dense)) < 1e-5
        # lam_weights parity (LLA stage 2 sharded) on the 2-D mesh
        w = jnp.asarray(np.random.default_rng(0).uniform(0.2, 1.0, 31),
                        jnp.float32)
        dw = np.asarray(decsvm_path_batched(Xj, yj, jnp.asarray(W),
                                            jnp.asarray(lams), acfg,
                                            lam_weights=w))
        rw = decentral.decsvm_path_mesh(Xj, yj, W, lams, acfg, mesh=mesh,
                                        lam_weights=w)
        print("lamw", np.max(np.abs(np.asarray(rw.path) - dw)))
        assert np.max(np.abs(np.asarray(rw.path) - dw)) < 1e-5
    """)
    assert "path" in out and "lamw" in out


def test_mesh_warm_handoff_matches_dense_warm_path():
    """Cross-shard warm-start hand-off on the (node_chunk, lam) mesh: with
    ppermute hand-off the warm path tracks the dense warm reference much
    more closely than cold-started lambda shards (each shard's first cell
    otherwise restarts from zero instead of its left neighbour's solution)."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimConfig, generate, ADMMConfig
        from repro.core.graph import erdos_renyi
        from repro.core import decentral
        from repro.core.path import decsvm_path_warm
        from repro.launch.mesh import make_chunk_lam_mesh
        cfg = SimConfig(p=20, s=4, m=4, n=60)
        X, y, _ = generate(cfg, seed=1)
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        W = erdos_renyi(cfg.m, 0.8, seed=0)
        lams = np.geomspace(0.3, 0.02, 8)     # descending: warm direction
        acfg = ADMMConfig(lam=0.05, max_iter=800)
        dense, it_d = decsvm_path_warm(Xj, yj, jnp.asarray(W, jnp.float32),
                                       jnp.asarray(lams), acfg, tol=1e-5)
        dense = np.asarray(dense)
        mesh = make_chunk_lam_mesh(2, 4)   # 4 lambda shards x 2
        devs = {}
        for handoff in (True, False):
            res = decentral.decsvm_path_mesh(Xj, yj, W, lams, acfg,
                                             mesh=mesh, mode="warm",
                                             tol=1e-5, handoff=handoff)
            devs[handoff] = float(np.max(np.abs(np.asarray(res.path)
                                                - dense)))
            assert np.asarray(res.iters).max() <= 800
        print("on", devs[True], "off", devs[False])
        assert devs[True] < 5e-5, devs             # measured 6.4e-6
        assert devs[True] < devs[False], devs      # measured off 3.2e-4
    """)
    assert "on" in out


def test_sharded_lam_weights_matches_dense_multidevice():
    """Non-uniform per-coordinate penalties through the chunked engine
    (the PR-3 feature gap): dense == whole-chunk halos == one-row halos."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import SimConfig, generate, ADMMConfig, decsvm_fit
        from repro.core.graph import erdos_renyi, ring
        from repro.core.decentral import decsvm_fit_chunked
        cfg = SimConfig(p=30, s=5, m=8, n=50)
        X, y, bstar = generate(cfg, seed=2)
        acfg = ADMMConfig(lam=0.05, max_iter=80)
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        w = jnp.asarray(np.random.default_rng(1).uniform(0.1, 1.0, 31),
                        jnp.float32)
        W = erdos_renyi(8, 0.5, seed=3)
        Bd = np.asarray(decsvm_fit(Xj, yj, jnp.asarray(W), acfg,
                                   lam_weights=w))
        Bs = np.asarray(decsvm_fit_chunked(Xj, yj, W, acfg, lam_weights=w))
        print("gather", np.max(np.abs(Bd - Bs)))
        assert np.max(np.abs(Bd - Bs)) < 1e-4
        Wr = ring(8)
        Bdr = np.asarray(decsvm_fit(Xj, yj, jnp.asarray(Wr), acfg,
                                    lam_weights=w))
        Br = np.asarray(decsvm_fit_chunked(Xj, yj, Wr, acfg, lam_weights=w))
        print("ring", np.max(np.abs(Bdr - Br)))
        assert np.max(np.abs(Bdr - Br)) < 1e-4
    """)
    assert "gather" in out and "ring" in out


def test_jitted_train_step_on_host_mesh():
    """Sharded train step runs end-to-end on an 8-device host mesh and the
    loss decreases over a few steps."""
    run_py("""
        import jax, jax.numpy as jnp, functools
        import repro.configs as configs
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import make_jitted_train_step
        from repro.optim import AdamWConfig, adamw_init
        from repro.models import model
        from repro.data.synthetic import token_stream

        mesh = make_host_mesh(model_axis=2)   # 4 data x 2 model
        cfg = configs.get_reduced("qwen3_14b")
        stream = token_stream(cfg, batch=8, seq=64, seed=0)
        b0 = next(stream)
        jitted, (p_specs, o_specs, b_specs) = make_jitted_train_step(
            cfg, AdamWConfig(lr=1e-3), mesh, b0)
        from repro.launch import sharding as shd
        with jax.set_mesh(mesh):
            params = model.init_params(cfg, jax.random.PRNGKey(0))
            params = jax.device_put(params, shd.to_named(p_specs, mesh))
            opt = jax.device_put(adamw_init(params), shd.to_named(o_specs, mesh))
            losses = []
            for i in range(8):
                batch = jax.device_put(next(stream), shd.to_named(b_specs, mesh))
                params, opt, m = jitted(params, opt, batch)
                losses.append(float(m["loss"]))
        print("losses", losses)
        assert losses[-1] < losses[0], losses
    """)


def test_dryrun_entrypoint_tiny():
    """The dry-run driver itself works end-to-end (tiny arch, 512 devices)."""
    out = run_py("""
        import sys
        sys.argv = ["dryrun", "--arch", "granite-moe-1b-a400m",
                    "--shape", "decode_32k", "--mesh", "single",
                    "--out", "/tmp/dryrun_test"]
        import runpy
        runpy.run_module("repro.launch.dryrun", run_name="__main__")
    """, devices=512)
    import json as _json
    rec = _json.loads(Path("/tmp/dryrun_test/granite_moe_1b_a400m__decode_32k__single.json").read_text())
    assert rec["ok"]
    assert rec["roofline"]["dominant"] in ("compute_s", "memory_s",
                                           "collective_s")
