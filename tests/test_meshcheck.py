"""meshcheck suite: the uniformity lattice (seeding, laundering, loop-
carry fixpoint), one caught-negative per deadlock/well-formedness check,
the drift gate, the shared waiver machinery, and the full-registry gate.

The headline cases are the two ISSUE-mandated proven negatives:

- a replica of the pre-PR-9 ``run_tol`` bug — a per-shard continue flag
  (no ``pmax``) steering a ``while_loop`` whose body ``ppermute``s — is
  flagged NONUNIFORM_STOP, while the reduced twin is clean;
- a non-injective / out-of-range ``ppermute`` chain is flagged
  PPERMUTE_PERM, while the *partial* injection the mesh warm hand-off
  uses (jax zero-fills unaddressed slots) stays clean.

Everything here traces at whatever device count pytest runs under (the
varying-axes analysis is device-count independent); only the CLI test
compares fingerprints against the committed table, in a subprocess that
pins the table's 8 forced host devices.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from tools import meshcheck
from tools.jaxtrace import contracts as jt_contracts
from tools.jaxtrace import drivers, walk
from tools.meshcheck import analyze_driver, diff_fingerprints

ROOT = Path(__file__).resolve().parent.parent


def _mesh(*names):
    devs = np.array(jax.devices()[:1]).reshape((1,) * len(names))
    return Mesh(devs, names)


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# -- walker: axis sizes ------------------------------------------------------


def test_walker_harvests_mesh_axis_sizes():
    mesh = _mesh("node", "lam")

    def f(x):
        return _smap(lambda xl: jax.lax.psum(xl, "node"), mesh,
                     P("node"), P())(x)

    closed = jax.make_jaxpr(f)(jnp.ones((4, 3)))
    inner = [c for _, c in walk.iter_jaxprs(closed) if c.axis_sizes]
    assert inner, "no ctx under the shard_map harvested axis sizes"
    assert inner[0].axis_size("node") == 1
    assert inner[0].axis_size("lam") == 1
    assert inner[0].axis_size("ghost") is None


# -- uniformity lattice: seeding + laundering --------------------------------


def test_axis_index_seeds_varying_and_reduction_launders():
    """A predicate derived from ``axis_index`` is shard-varying (caught);
    the same predicate pushed through ``psum`` is laundered uniform."""
    mesh = _mesh("node")

    def varying(x):
        def inner(xl):
            def body(c):
                xl, _ = c
                g = jax.lax.psum(xl, "node")         # collective in body
                flag = jax.lax.axis_index("node") < 1  # per-shard predicate
                return (xl + g, flag)
            return jax.lax.while_loop(lambda c: c[1], body,
                                      (xl, jnp.bool_(True)))[0]
        return _smap(inner, mesh, P("node"), P("node"))(x)

    found = analyze_driver("syn", jax.make_jaxpr(varying)(
        jnp.ones((4, 3)))).findings
    assert any(f.contract == "NONUNIFORM_STOP" and "'node'" in f.message
               for f in found), [f.format() for f in found]

    def laundered(x):
        def inner(xl):
            def body(c):
                xl, _ = c
                g = jax.lax.psum(xl, "node")
                idx = jax.lax.axis_index("node")
                flag = jax.lax.psum(idx, "node") < 8   # laundered uniform
                return (xl + g, flag)
            return jax.lax.while_loop(lambda c: c[1], body,
                                      (xl, jnp.bool_(True)))[0]
        return _smap(inner, mesh, P("node"), P("node"))(x)

    clean = analyze_driver("syn", jax.make_jaxpr(laundered)(
        jnp.ones((4, 3)))).findings
    assert clean == [], [f.format() for f in clean]


def test_loop_carry_fixpoint_propagates_shard_variation():
    """Variation entering a carry only on iteration 2 (through the
    sharded operand) must still reach the predicate check — the reason
    the carry transfer iterates to fixpoint instead of one pass."""
    mesh = _mesh("node")

    def f(x):
        def inner(xl):
            def body(c):
                acc, _ = c
                acc = acc + jnp.max(xl)        # varying joins the carry
                _ = jax.lax.ppermute(acc, "node", [(0, 0)])
                return (acc, acc < 100.0)      # carry-derived predicate
            return jax.lax.while_loop(lambda c: c[1], body,
                                      (jnp.zeros(()), jnp.bool_(True)))[0]
        return _smap(inner, mesh, P("node"), P())(x)

    found = analyze_driver("syn", jax.make_jaxpr(f)(
        jnp.ones((4, 3)))).findings
    assert any(f.contract == "NONUNIFORM_STOP" for f in found), \
        [f.format() for f in found]


# -- deadlock negative #1: the pre-PR-9 unreduced continue flag --------------


def _flag_loop(reduce_axes):
    """A run_tol-shaped shard_map while loop: ppermute in the body, the
    continue flag pmax-reduced over ``reduce_axes`` (() = pre-PR-9)."""
    mesh = _mesh("node", "lam")

    def prog(x, lams):
        def inner(xl, lamsl):
            def body(c):
                xl, _ = c
                nbr = jax.lax.ppermute(xl, "node", [(0, 0)])
                xl = xl + nbr * lamsl[0]
                flag = jnp.max(jnp.abs(xl)) < 100.0    # per-shard
                for ax in reduce_axes:
                    flag = jax.lax.pmax(flag.astype(jnp.int32), ax) > 0
                return (xl, flag)
            return jax.lax.while_loop(lambda c: c[1], body,
                                      (xl, jnp.bool_(True)))[0]
        return _smap(inner, mesh, (P("node"), P("lam")), P("node"))(x, lams)

    return jax.make_jaxpr(prog)(jnp.ones((4, 3)), jnp.ones((2,)))


def test_unreduced_continue_flag_replica_is_caught():
    found = analyze_driver("pre-pr9", _flag_loop(())).findings
    stops = [f for f in found if f.contract == "NONUNIFORM_STOP"]
    assert stops, [f.format() for f in found]
    assert any("ppermute" in f.message for f in stops)


def test_node_only_reduction_still_deadlocks_ring_mesh_replica():
    """The satellite-2 bug this PR fixed in ``build_mesh_path``: on a
    (node, lam) mesh the flag reduced over "node" only still varies along
    "lam", and CollectivePermute's rendezvous spans the whole mesh."""
    found = analyze_driver("ring-warm", _flag_loop(("node",))).findings
    stops = [f for f in found if f.contract == "NONUNIFORM_STOP"]
    assert stops and all("'lam'" in f.message for f in stops), \
        [f.format() for f in found]


def test_both_axes_reduced_flag_is_clean():
    found = analyze_driver("fixed", _flag_loop(("node", "lam"))).findings
    assert found == [], [f.format() for f in found]


def test_mesh_ring_warm_driver_traces_clean_post_fix():
    """The real code path: decsvm_path_mesh(mode="warm") on a ring W —
    the caller the uniformity pass flagged (stop_axes joined only the
    node axis around a whole-mesh ppermute) — proves uniform, its stop
    pmax-agreed over both mesh axes."""
    from repro.core import graph
    from repro.core.admm import ADMMConfig

    m, n, p = 4, 6, 3
    X = jnp.zeros((m, n, p), jnp.float32)
    y = jnp.ones((m, n), jnp.float32)
    W = np.asarray(graph.ring(m), np.float32)
    cfg = ADMMConfig(lam=0.0, max_iter=4)

    from repro.core import decentral
    closed = jax.make_jaxpr(
        lambda X, y: decentral.decsvm_path_mesh(
            X, y, W, [0.1, 0.05], cfg, mode="warm",
            check_every=2).path)(X, y)
    ana = analyze_driver("mesh-ring-warm", closed)
    assert ana.findings == [], [f.format() for f in ana.findings]
    assert ana.n_while >= 1
    assert any("pmax[lam,node_chunk]" in e for e in ana.fingerprint)
    if jax.device_count() > 1:
        # the halo exchange elides its ppermutes on a 1-device mesh
        assert any("ppermute" in e for e in ana.fingerprint)


# -- deadlock negative #2: non-bijective ppermute chains ---------------------


def _permute_once(perm):
    mesh = _mesh("node")

    def f(x):
        return _smap(lambda xl: jax.lax.ppermute(xl, "node", perm),
                     mesh, P("node"), P("node"))(x)

    return jax.make_jaxpr(f)(jnp.ones((4, 3)))


def test_non_injective_and_out_of_range_perms_are_caught():
    found = analyze_driver("dup", _permute_once(
        [(0, 0), (0, 0)])).findings        # duplicate source AND target
    assert any(f.contract == "PPERMUTE_PERM"
               and "not injective" in f.message for f in found)

    found = analyze_driver("oob", _permute_once([(0, 7)])).findings
    assert any(f.contract == "PPERMUTE_PERM"
               and "out of range" in f.message for f in found)


def test_partial_injection_is_legal():
    """The mesh warm hand-off's shape — fewer pairs than the axis size,
    unaddressed destinations zero-filled by jax — must NOT be flagged."""
    found = analyze_driver("partial", _permute_once([(0, 0)])).findings
    assert found == [], [f.format() for f in found]


def test_block_delta_shift_chain_is_bijective_and_clean():
    """decentral._block_neighbor_sum_fn's halo shift perms, verified on
    the real helper (full-cycle shifts are bijections by construction)."""
    from repro.core.decentral import _block_neighbor_sum_fn
    mesh = _mesh("node_chunk")
    Wd = jnp.zeros((4, 4), jnp.float32)
    Woff = jnp.zeros((4, 6), jnp.float32)

    def f(B):
        def inner(Bl):
            nbr = _block_neighbor_sum_fn(
                "node_chunk", 1, Wd, Woff,
                ((1, (0, 1, 2, 3)), (3, (2, 3))))
            return nbr(Bl)
        return _smap(inner, mesh, P("node_chunk"), P("node_chunk"))(B)

    ana = analyze_driver("blk", jax.make_jaxpr(f)(jnp.ones((4, 3))))
    assert ana.findings == [], [f.format() for f in ana.findings]
    assert sum("ppermute" in e for e in ana.fingerprint) == 2


# -- cond well-formedness ----------------------------------------------------


def test_cond_branches_with_divergent_collectives_are_caught():
    mesh = _mesh("node")

    def f(x):
        def inner(xl):
            flag = jax.lax.pmax(jnp.max(xl), "node") > 0
            return jax.lax.cond(flag,
                                lambda v: jax.lax.psum(v, "node"),
                                lambda v: v * 2.0, xl)
        return _smap(inner, mesh, P("node"), P("node"))(x)

    found = analyze_driver("syn", jax.make_jaxpr(f)(
        jnp.ones((4, 3)))).findings
    assert any(f.contract == "COND_SCHEDULE" for f in found), \
        [f.format() for f in found]


def test_cond_with_identical_schedules_and_uniform_pred_is_clean():
    mesh = _mesh("node")

    def f(x):
        def inner(xl):
            flag = jax.lax.pmax(jnp.max(xl), "node") > 0
            return jax.lax.cond(flag,
                                lambda v: jax.lax.psum(v, "node"),
                                lambda v: jax.lax.psum(v * 2.0, "node"), xl)
        return _smap(inner, mesh, P("node"), P("node"))(x)

    found = analyze_driver("syn", jax.make_jaxpr(f)(
        jnp.ones((4, 3)))).findings
    assert found == [], [f.format() for f in found]


# -- shared waiver machinery (W0) --------------------------------------------


def test_meshcheck_waivers_ride_the_shared_w0_machinery():
    f = jt_contracts.Finding("syn", "NONUNIFORM_STOP", "msg",
                             "shard_map/while::ppermute @ site.py:1")
    ledger = {("NONUNIFORM_STOP", "site.py"): "known-uniform by contract"}
    kept, matched = jt_contracts.apply_waivers([f], ledger)
    assert kept == [] and matched == {("NONUNIFORM_STOP", "site.py")}
    assert jt_contracts.audit_waivers(matched, ledger) == []
    # stale + reasonless entries are W0 errors, same as jaxtrace's ledger
    errs = jt_contracts.audit_waivers(
        set(), {("NONUNIFORM_STOP", "nowhere"): " "})
    assert len(errs) == 2
    # the shipped meshcheck ledger must stay reasoned
    assert all(str(r).strip() for r in meshcheck.WAIVERS.values())


# -- drift gate --------------------------------------------------------------


def _table(fp, dc=8):
    return {"device_count": dc, "drivers": {"d": {"fingerprint": list(fp)}}}


def test_drift_gate_passes_on_identical_and_catches_changes():
    assert diff_fingerprints(_table(["a", "b"]), _table(["a", "b"])) == []
    drift = diff_fingerprints(_table(["a", "b"]), _table(["a", "c"]))
    assert drift and "FINGERPRINT_DRIFT" in drift[0] and "--update" in \
        drift[0]
    # driver-set changes are drift too
    fresh = _table(["a"])
    fresh["drivers"]["new"] = {"fingerprint": []}
    assert any("newly registered" in e
               for e in diff_fingerprints(_table(["a"]), fresh))
    assert any("no longer registered" in e
               for e in diff_fingerprints(fresh, _table(["a"])))


def test_drift_gate_refuses_cross_device_count_comparison():
    errs = diff_fingerprints(_table(["a"], dc=8), _table(["a"], dc=4))
    assert len(errs) == 1 and "8 devices" in errs[0]


# -- registry + the repo gate ------------------------------------------------


def test_registry_covers_gossip_and_chunked_mesh_drivers():
    reg = drivers.build_registry()
    assert {"gossip", "mesh-2d-block"} <= set(reg)
    assert len(reg) >= 19


def test_repo_drivers_prove_uniform():
    """The enforced gate: every registered driver's predicates prove
    mesh-uniform, every perm injective, every axis bound — no waivers
    needed as the tree stands."""
    report, kept, errors = meshcheck.run_report()
    assert kept == [], [f.format() for f in kept]
    assert errors == []
    assert len(report["drivers"]) >= 19
    # the sharded engines' schedules are non-empty and name their axes
    assert any("psum[node_chunk]" in e
               for e in report["drivers"]["mesh-2d"]["fingerprint"])
    if jax.device_count() > 1:
        # the chunked engine elides ALL collectives on a 1-device mesh
        # (every block is local); its schedule only exists multi-device
        assert any("node_chunk" in e
                   for e in report["drivers"]["chunked"]["fingerprint"])
    blk = report["drivers"]["mesh-2d-block"]
    assert blk["while_loops"] >= 1 and blk["collectives"] >= 4
    # dense drivers have empty schedules by definition
    assert report["drivers"]["dense"]["fingerprint"] == []


def test_cli_validates_committed_table(tmp_path):
    """CI parity: the CLI (which pins cpu + 8 forced host devices) must
    exit 0 against the committed meshcheck_contracts.json — i.e. the
    committed fingerprints match a fresh trace."""
    committed = ROOT / "meshcheck_contracts.json"
    assert committed.exists(), "meshcheck_contracts.json must be committed"
    assert json.loads(committed.read_text())["device_count"] == 8
    out = tmp_path / "meshcheck_contracts.json"
    shutil.copy(committed, out)
    run = subprocess.run(
        [sys.executable, "-m", "tools.meshcheck", "--out", str(out)],
        cwd=ROOT, capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "all collective contracts hold" in run.stdout
    # drift gate sanity: a tampered table must fail the same invocation
    table = json.loads(out.read_text())
    name = next(n for n, r in table["drivers"].items() if r["fingerprint"])
    table["drivers"][name]["fingerprint"][0] += "tampered"
    out.write_text(json.dumps(table))
    run = subprocess.run(
        [sys.executable, "-m", "tools.meshcheck", "--out", str(out)],
        cwd=ROOT, capture_output=True, text=True)
    assert run.returncode == 1
    assert "FINGERPRINT_DRIFT" in run.stderr
