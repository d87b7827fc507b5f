"""Self-check of the pool a run makes (``bench/design.py`` ``make_pool``):
on one device the data and graph of a one-chip cell as one
``device_design`` call and one Erdos-Renyi draw make them; the graph by
name; on several devices the node-keyed design, sharded along the node
axis, the same bits on any number of devices and the design's moments; the
reference on the sharded data; and a whole run of a four-chip cell.  The
multi-device cases run in subprocesses on four virtual CPU devices.

    JAX_PLATFORMS=cpu PYTHONPATH=.:src python3 -m pytest -q \
        bench/tests/test_pool.py
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import design, harness

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"paper41_p500": {"m": 4, "n": 40, "p": 30},
         "epsilon_m10": {"m": 5, "n": 60, "p": 80}}


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("name", sorted(SMALL))
def test_one_device_pool_is_one_design_call(name):
    cfg = dict(harness.load_json(ROOT / f"bench/configs/{name}.json"),
               **SMALL[name])
    seed = 2**31 + 101
    pool, W = design.make_pool(cfg, seed, 2, jax.devices()[:1])
    np.testing.assert_array_equal(
        W, design.erdos_renyi(cfg["m"], cfg["graph_p"],
                              design.host_rng(seed, 0)))
    for k, (X, y) in enumerate(pool):
        X0, y0 = design.device_design(
            design.device_key(seed, 1 + k), cfg["m"], cfg["n"], cfg["p"],
            cfg["s"], cfg["mu"], cfg["ar_rho"], cfg["flip_rate"])
        np.testing.assert_array_equal(np.asarray(X), np.asarray(X0))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


@pytest.mark.parametrize("m, k", [(8, 2), (12, 4), (1024, 4)])
def test_k_regular_is_the_circulant_lattice(m, k):
    from repro.core import graph

    W = design.make_graph({"graph": "k_regular", "m": m, "graph_k": k}, 1)
    assert W.dtype == np.float32 and W.shape == (m, m)
    np.testing.assert_array_equal(W, W.T)
    np.testing.assert_array_equal(W.sum(axis=1), k)
    assert not np.any(np.diag(W))
    assert design._connected(W)
    np.testing.assert_array_equal(W, graph.k_regular(m, k).to_dense())


def test_unknown_graph_and_odd_k_raise():
    with pytest.raises(ValueError):
        design.make_graph({"graph": "ring", "m": 8}, 1)
    with pytest.raises(ValueError):
        design.k_regular(8, 3)


def test_nodes_must_divide_over_the_devices():
    with pytest.raises(ValueError):
        design.node_design(design.seed_words(1, 1), [jax.devices()[0]] * 3,
                           8, 4, 6, 2, 0.4, 0.5, 0.01)


def test_node_design_is_sharded_the_same_bits_and_the_design():
    out = run_py("""
        import json
        import jax
        import numpy as np
        from bench import design

        m, n, p, s, mu, rho, flip = 8, 64, 40, 5, 0.4, 0.5, 0.01
        cfg = dict(m=m, n=n, p=p, s=s, mu=mu, ar_rho=rho, flip_rate=flip,
                   graph="k_regular", graph_k=2)
        seed = 2**31 + 7
        devs = jax.devices()
        (X4, y4), = design.make_pool(cfg, seed, 1, devs)[0]
        one = design.node_design(design.seed_words(seed, 1), devs[:1], m, n,
                                 p, s, mu, rho, flip)
        two = design.node_design(design.seed_words(seed, 1), devs[:2], m, n,
                                 p, s, mu, rho, flip)
        design.BLOCK_BYTES = 4 * 2 * n * 3      # another block of columns
        design._node_program.cache_clear()
        small = design.node_design(design.seed_words(seed, 1), devs, m, n,
                                   p, s, mu, rho, flip)
        X, y = np.asarray(X4), np.asarray(y4)
        same = all(np.array_equal(X, np.asarray(a))
                   and np.array_equal(y, np.asarray(b))
                   for a, b in (one, two, small))
        shards = sorted((sh.index[0].start, sh.data.shape, sh.device.id)
                        for sh in X4.addressable_shards)
        Xs = X[..., 1:].reshape(m * n, p)
        yv = y.reshape(-1)
        shift = (Xs * yv[:, None]).mean(axis=0)
        rest = Xs[:, s:]
        lag1 = np.mean([np.corrcoef(rest[:, j], rest[:, j + 1])[0, 1]
                        for j in range(p - s - 1)])
        print(json.dumps({
            "spec": str(X4.sharding.spec), "y_spec": str(y4.sharding.spec),
            "shards": shards, "same": same,
            "intercept": bool(np.all(X[..., 0] == 1.0)),
            "labels": sorted(set(yv.tolist())),
            "shift_s": float(shift[:s].mean()),
            "shift_rest": float(np.abs(shift[s:]).max()),
            "var_rest": float(rest.var(axis=0).mean()),
            "lag1": float(lag1),
            "block_corr": float(np.corrcoef(Xs[:, s - 1], Xs[:, s])[0, 1]),
            "distinct_nodes": len({X[l].tobytes() for l in range(m)})}))
    """)
    r = __import__("json").loads(out.strip().splitlines()[-1])
    assert r["spec"] == r["y_spec"] == "PartitionSpec('nodes',)"
    assert r["shards"] == [[2 * i, [2, 64, 41], i] for i in range(4)]
    assert r["same"] and r["intercept"] and r["labels"] == [-1.0, 1.0]
    assert r["distinct_nodes"] == 8
    # E[x_j y] = mu (1 - 2 flip) on the first s covariates, 0 after them.
    assert abs(r["shift_s"] - 0.4 * 0.98) < 0.06
    assert r["shift_rest"] < 0.15
    assert abs(r["var_rest"] - 1.0) < 0.06
    assert abs(r["lag1"] - 0.5) < 0.05        # AR(0.5) within a block
    assert abs(r["block_corr"]) < 0.15         # the second block restarts


def test_a_cell_takes_exactly_its_chips():
    """A four-chip cell on a machine with fewer devices does not run (its
    data would be made by the one-device generator); with enough it gets
    exactly four."""
    assert len(jax.devices()) < 4
    with pytest.raises(harness.NoChip):
        harness.check_devices(4, require_chip=False)
    assert len(harness.check_devices(1, require_chip=False)) == 1
    out = run_py("""
        from bench import harness
        print(len(harness.check_devices(4, require_chip=False)))
    """, devices=6)
    assert out.split() == ["4"]


def test_reference_on_sharded_data_matches_it_gathered():
    out = run_py("""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from bench import design, reference

        cfg = dict(m=8, n=40, p=30, s=5, mu=0.4, ar_rho=0.5, flip_rate=0.01,
                   graph="k_regular", graph_k=2)
        (X, y), = design.make_pool(cfg, 2**31 + 9, 1, jax.devices())[0]
        W = jnp.asarray(design.make_graph(cfg, 0))
        B0 = jnp.zeros((8, 31), jnp.float32)
        kw = dict(h=0.5, kernel="epanechnikov", tau=1.0)
        run = lambda X, y: reference.fit_following(
            X, y, W, 0.02, jnp.int32(40), B0, **kw)
        Bs, rs = run(X, y)
        Bg, rg = run(jnp.asarray(np.asarray(X)), jnp.asarray(np.asarray(y)))
        print(float(jnp.max(jnp.abs(Bs - Bg))), float(abs(rs - rg)),
              float(jnp.max(jnp.abs(Bg))))
    """)
    gap, res_gap, scale = map(float, out.split())
    assert scale > 1e-3
    assert gap <= 1e-6 and res_gap <= 1e-6


def test_four_chip_cell_runs_correct():
    out = run_py("""
        import dataclasses
        import json
        import time
        from bench import harness

        cell = harness.load_cell("epsilon_m10.fit")
        cell = dataclasses.replace(
            cell, name="k_regular_m8.fit", chips=4,
            config=dict(cell.config, m=8, n=40, p=30, graph="k_regular",
                        graph_k=2),
            traffic=dict(cell.traffic, pool=2, warmup=1))
        result, lines = harness.run_cell(cell, 2**31 + 21, 0.3, False,
                                         t0=time.perf_counter(),
                                         require_chip=False)
        print(json.dumps(result))
    """)
    r = __import__("json").loads(out.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
