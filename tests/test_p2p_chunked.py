"""The chunked engine on node-sharded X, as the ``p2p_m1024.fit`` cell runs
it, at a size the CPU holds: X made by ``bench.design.node_design``
(m=16, n=24, p=40, the 4-regular lattice) sharded along the node axis over
4 virtual CPU devices, in one subprocess whose readings the tests below
assert on.

- the fit at tol 1e-3 agrees with ``bench/reference.fit_following`` for
  the rounds it reports, and with the dense ``decsvm_fit_tol``;
- X reaches the program as it lies: no pad, cast or copy of its shards,
  and the mesh is the devices X is sharded over;
- the compiled program holds no whole-X or second-X buffer;
- the cell's check fails the ``no_exchange`` and ``state_unchanged``
  faults;
- ghost padding still serves an m that does not divide;
- every engine replicates its result on the devices, not through the
  host, and a second fit at the same shapes compiles nothing.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = """
import dataclasses, json
import numpy as np, jax, jax.numpy as jnp
from bench import design, harness, reference
from bench.faults import FAULTS
from repro.core import ADMMConfig, decentral, graph
from repro.core.admm_adaptive import decsvm_fit_tol

out = {}
cfg = dict(harness.load_cell("p2p_m1024.fit").config, m=16, n=24, p=40)
m, n, p = cfg["m"], cfg["n"], cfg["p"] + 1
devices = jax.devices()[:4]
(X, y), = design.make_pool(cfg, 2**31 + 21, 1, devices)[0]
W = design.make_graph(cfg, 2**31 + 21)
top = graph.BlockTopology.from_dense(W)
lam, h = design.theory_lambda(cfg), design.bandwidth(cfg)
acfg = ADMMConfig(lam=lam, tau=cfg["tau"], h=h, kernel=cfg["kernel"],
                  max_iter=cfg["max_iter"])

stop = dict(tol=cfg["tol"], stop_rule=cfg["stop_rule"],
            check_every=cfg["check_every"])
B, t = decentral.decsvm_fit_chunked(X, y, top, acfg, **stop)
B = np.asarray(B)
Br, _ = reference.fit_following(X, y, jnp.asarray(W), lam, jnp.int32(t),
                                jnp.asarray(B), h=h, kernel=cfg["kernel"],
                                tau=cfg["tau"])
Bd, td = decsvm_fit_tol(jnp.asarray(np.asarray(X)), jnp.asarray(np.asarray(y)),
                        jnp.asarray(W), acfg, **stop)
out["rounds"], out["dense_rounds"] = int(t), int(td)
out["ref_gap"] = float(np.max(np.abs(B - np.asarray(Br))))
out["dense_gap"] = float(np.max(np.abs(B - np.asarray(Bd))))

mesh = decentral._chunk_mesh(X, None)
out["mesh_devices"] = [d.id for d in mesh.devices]
out["x_devices"] = [d.id for d in X.sharding.mesh.devices]
ops, offsets, m_pad = decentral._chunk_prep(X, y, top, mesh)
ptr = lambda a: sorted(s.data.unsafe_buffer_pointer()
                       for s in a.addressable_shards)
out["x_in_place"] = ptr(ops["X"]) == ptr(X)
out["y_in_place"] = ptr(ops["y"]) == ptr(y)
out["m_pad"] = m_pad

# the program at n=600, where X outweighs the state B, P (m, p)
fitted = decentral.build_chunked_admm(m_pad, p, acfg, mesh, offsets,
                                      **stop)
shard = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding)
big = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                          sharding=ops["X"].sharding)
compiled = fitted.lower(big(m, 600, p), big(m, 600), shard(ops["W_diag"]),
                        shard(ops["W_off"]), shard(ops["deg"]),
                        jnp.ones((p,), jnp.float32),
                        shard(ops["nmask"])).compile()
out["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
out["x_share_bytes"] = 4 * (m // len(devices)) * 600 * p

cell = harness.load_cell("p2p_m1024.fit")
cell = dataclasses.replace(cell, config=cfg)
op = harness.load_module("ops", "decsvm_fit_chunked")
for name in ("no_exchange", "state_unchanged"):
    with FAULTS[name]("p2p_m1024.fit"):
        answer = op.build(cfg, cell.traffic, W)(X, y)[0]
    numbers = op.check(cfg, cell.traffic, W, X, y, answer)
    out[name] = harness.judge(numbers, cell.checks["limits"])[1]
answer = op.build(cfg, cell.traffic, W)(X, y)[0]
numbers = op.check(cfg, cell.traffic, W, X, y, answer)
out["sound"] = harness.judge(numbers, cell.checks["limits"])[1]

# ghost nodes: m=14 over 4 devices pads to 16 and matches the dense fit
Xs, ys = np.asarray(X)[:14], np.asarray(y)[:14]
top14 = graph.k_regular(14, 4)
B14, t14 = decentral.decsvm_fit_chunked(Xs, ys, top14, acfg, **stop)
B14d, _ = decsvm_fit_tol(jnp.asarray(Xs), jnp.asarray(ys),
                         jnp.asarray(top14.to_dense()), acfg, **stop)
out["ghost_gap"] = float(np.max(np.abs(np.asarray(B14) - np.asarray(B14d))))

# the hand-off: each engine's result is replicated on the devices, never
# through the host's slow path of a resharding device_put, and is the
# array that device_put gives, ghost trim and row selection included
import time
import jax._src.array as jarray
from jax.sharding import NamedSharding, PartitionSpec
from repro.core import trace

slow = [0]
slow_path = jarray.shard_sharded_device_array_slow_path
def spy(*args, **kwargs):
    slow[0] += 1
    return slow_path(*args, **kwargs)
jarray.shard_sharded_device_array_slow_path = spy
by_device_put = lambda mesh, x: jax.device_put(
    x, NamedSharding(mesh, PartitionSpec()))

def handoff(name, run, mesh):
    got = {}
    for side in ("device_put", "replicator"):
        saved = decentral._replicated
        if side == "device_put":
            decentral._replicated = by_device_put
        try:
            slow[0] = 0
            res = jax.block_until_ready(run())
            got[side] = (slow[0], [np.asarray(a) for a in res])
        finally:
            decentral._replicated = saved
    out["slow_path_" + name] = got["replicator"][0]
    out["slow_path_device_put_" + name] = got["device_put"][0]
    out["same_as_device_put_" + name] = all(
        np.array_equal(a, b) for a, b in zip(got["device_put"][1],
                                             got["replicator"][1]))
    out["replicated_" + name] = all(
        a.sharding.is_fully_replicated
        and a.sharding.device_set == set(mesh.devices.flat) for a in res)

mesh4 = decentral.make_node_chunk_mesh(4)
lams = [lam, 2 * lam]
fcfg = dataclasses.replace(acfg, max_iter=20)
handoff("fit", lambda: decentral.decsvm_fit_chunked(X, y, top, acfg, **stop),
        mesh)
handoff("fit_ghost", lambda: decentral.decsvm_fit_chunked(
    Xs, ys, top14, acfg, mesh=mesh4, **stop), mesh4)
handoff("path_chunked", lambda: (decentral.decsvm_path_chunked(
    Xs, ys, top14, lams, fcfg, mesh=mesh4),), mesh4)
mesh2d = jax.make_mesh((2, 2), ("node_chunk", "lam"))
handoff("path_mesh", lambda: (lambda r: (r.best_B, r.path, r.criteria))(
    decentral.decsvm_path_mesh(Xs, ys, top14, lams, fcfg, mesh=mesh2d)),
    mesh2d)

# a second fit at the same shapes builds no program: the hand-off's is
# made once per mesh
t0 = time.perf_counter()
jax.block_until_ready(decentral.decsvm_fit_chunked(X, y, top, acfg, **stop))
out["compiles_second_fit"] = trace.compiles.backend_between(
    t0, time.perf_counter())
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    run = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         capture_output=True, text=True, env=env,
                         timeout=600, cwd=ROOT)
    assert run.returncode == 0, run.stderr[-3000:]
    line = [s for s in run.stdout.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_fit_on_node_sharded_x_matches_the_reference(readings):
    assert 0 < readings["rounds"] <= 300
    assert readings["ref_gap"] <= 2e-5, readings


def test_fit_on_node_sharded_x_matches_the_dense_engine(readings):
    assert readings["rounds"] == readings["dense_rounds"]
    assert readings["dense_gap"] <= 2e-5, readings


def test_x_is_read_where_it_lies(readings):
    """The mesh is X's own devices, and the program's X and y are X's and
    y's own buffers: nothing padded, cast or copied."""
    assert readings["mesh_devices"] == readings["x_devices"]
    assert readings["m_pad"] == 16
    assert readings["x_in_place"] and readings["y_in_place"]


def test_compiled_program_holds_no_second_x(readings):
    assert readings["temp_bytes"] < readings["x_share_bytes"] / 4, readings


@pytest.mark.parametrize("fault", ["no_exchange", "state_unchanged"])
def test_the_cells_check_fails_a_fault(readings, fault):
    assert readings["sound"] is True
    assert readings[fault] is False


def test_ghost_nodes_still_pad_an_m_that_does_not_divide(readings):
    assert readings["ghost_gap"] <= 2e-5, readings


@pytest.mark.parametrize("engine", ["fit", "fit_ghost", "path_chunked",
                                    "path_mesh"])
def test_result_is_replicated_on_the_devices(readings, engine):
    """Each engine hands its result back replicated over its mesh without
    the host round trip of a resharding ``device_put`` (whose slow path
    the spy sees), and the result is bit for bit the array that
    ``device_put`` gives: m=16 that divides the 4 devices, m=14 that
    pads ghost rows, and the path engines' trim and selected row."""
    assert readings["slow_path_device_put_" + engine] >= 1, readings
    assert readings["slow_path_" + engine] == 0, readings
    assert readings["same_as_device_put_" + engine] is True
    assert readings["replicated_" + engine] is True


def test_a_second_fit_compiles_nothing(readings):
    assert readings["compiles_second_fit"] == 0, readings


# -- the cell's per-layer metrics on hand-made reductions ---------------------


def _run(op_seconds=None, scope_seconds=None, rounds=(300, 300, 300)):
    import types

    from bench import harness, scopes
    from bench.trace import Summary
    cell = harness.load_cell("p2p_m1024.fit")
    trace = None if op_seconds is None else Summary(
        window_s=10.0, busy_s=9.5, devices=4, op_seconds=op_seconds,
        gap_seconds={})
    program = None if scope_seconds is None else scopes.Summary(
        window_s=10.0, devices=4, scope_seconds=scope_seconds,
        span_idle_seconds={})
    records = [(k, 0.0, 1.0, {}, {"rounds": r}, None)
               for k, r in enumerate(rounds)]
    return types.SimpleNamespace(cell=cell, trace=trace, program=program,
                                 counters=lambda key: [r[4][key]
                                                       for r in records],
                                 peaks=lambda: {"hbm_bytes_per_s": 819e9})


def _metric(name):
    from bench import harness
    return harness.load_module("metrics", name)


def test_xpass_roofline_counts_one_chips_bytes_per_product():
    """426 products a fit of 300 rounds (300 rounds, 75 KKT checks, 51
    power products), each one chip's 4 * 256 * 200 * 20001 bytes; the
    time is that of the ops whose label starts ``decsvm_xpass``."""
    products = 3 * (300 + 75 + 51)
    least_s = products * 4 * 256 * 200 * 20001 / 819e9
    ops = {"decsvm_xpass_rows.11 = f32[256,1,1,20001] custom-call(...)":
           least_s / 0.8,
           "copy.31 = f32[256,200,20001] copy(f32[256,200,20001])": 1.0,
           "collective-permute-done = f32[256,20001] ...": 0.5}
    got = _metric("xpass_roofline.p2p").read(_run(op_seconds=ops))
    assert got == pytest.approx(80.0)
    assert _metric("xpass_roofline.p2p").read(_run()) is None
    assert _metric("xpass_roofline.p2p").read(
        _run(op_seconds={"fusion.1 = f32[8] fusion(f32[8])": 1.0})) is None


def test_exchange_share_reads_the_exchange_scope():
    scoped = {"decsvm.round": 6.0, "decsvm.exchange": 1.0,
              "decsvm.kkt_check": 2.0, "unscoped": 1.0}
    got = _metric("exchange_share.p2p").read(_run(scope_seconds=scoped))
    assert got == pytest.approx(10.0)
    assert _metric("exchange_share.p2p").read(
        _run(scope_seconds={"decsvm.round": 1.0})) is None
    assert _metric("exchange_share.p2p").read(_run()) is None


def test_idle_share_reads_the_trace():
    got = _metric("idle_share.p2p").read(_run(op_seconds={}))
    assert got == pytest.approx(5.0)
    assert _metric("idle_share.p2p").read(_run()) is None
