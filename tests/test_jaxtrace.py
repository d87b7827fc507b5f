"""jaxtrace suite: the recursive walker, one positive + one negative case
per IR contract, the waiver ledger's W0 semantics, the cost model, the
roofline drift gate, and the driver registry / CLI gate.

The headline case is ``BF16_DOT``: a bf16 matmul missing its f32
``preferred_element_type`` is invisible to declint's AST rule R2 (which
only inspects Pallas kernel bodies under ``kernels/``) but caught here on
the traced IR — the reason the analyzer exists at that level.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tools.declint import lint_source
from tools.jaxtrace import REPO_ROOT, contracts, costmodel, drivers, walk
from tools.jaxtrace.contracts import WAIVERS, Finding, check_driver

ROOT = Path(__file__).resolve().parent.parent


# -- walker ------------------------------------------------------------------


def test_walker_recurses_into_loop_bodies_with_context():
    def f(x):
        def body(c, _):
            return c + jnp.sum(x), None
        out, _ = jax.lax.scan(body, jnp.zeros(()), None, length=7)
        return out

    closed = jax.make_jaxpr(f)(jnp.ones((4, 4)))
    ctxs = [ctx for _, ctx in walk.iter_jaxprs(closed)]
    assert len(ctxs) >= 2                       # root + scan body
    assert any(c.in_loop and c.loop_scale == 7 for c in ctxs)
    assert ctxs[0].in_loop is False


def test_walker_marks_scan_consts_loop_invariant():
    def f(x):
        def body(c, _):
            return c + jnp.sum(x), None         # x closed over -> const
        out, _ = jax.lax.scan(body, jnp.zeros(()), None, length=3)
        return out

    closed = jax.make_jaxpr(f)(jnp.ones((32,)))
    body_ctxs = [ctx for _, ctx in walk.iter_jaxprs(closed) if ctx.in_loop]
    assert body_ctxs and all(c.const_vars for c in body_ctxs)


# -- contract (a): F64 -------------------------------------------------------


def test_f64_aval_flagged_and_f32_clean():
    with jax.enable_x64():
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.ones((3,), jnp.float64))
    found = check_driver("syn", closed, bf16=False)
    assert any(f.contract == "F64" for f in found)

    clean = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((3,), jnp.float32))
    assert check_driver("syn", clean, bf16=False) == []


# -- contract (b): bf16 dot discipline + accumulators ------------------------


def test_bf16_dot_without_preferred_caught_at_ir_missed_by_declint_r2():
    """The acceptance case: IR-level catch of what the AST linter cannot
    see.  ``X @ B`` on bf16 operands emits a dot_general with no
    f32 preferred_element_type — jaxtrace flags it; declint R2, scoped to
    kernel bodies in ``kernels/``, passes the identical source."""
    def net_update(X, B):
        return X @ B

    Xb = jnp.zeros((8, 16), jnp.bfloat16)
    Bb = jnp.zeros((16, 4), jnp.bfloat16)
    found = check_driver("syn", jax.make_jaxpr(net_update)(Xb, Bb),
                         bf16=True)
    assert any(f.contract == "BF16_DOT" for f in found)

    src = "def net_update(X, B):\n    return X @ B\n"
    assert lint_source(src, path="repro/core/consensus.py") == []


def test_bf16_dot_with_f32_preferred_is_clean():
    def good(X, B):
        return jax.lax.dot(X, B, preferred_element_type=jnp.float32)

    Xb = jnp.zeros((8, 16), jnp.bfloat16)
    Bb = jnp.zeros((16, 4), jnp.bfloat16)
    found = check_driver("syn", jax.make_jaxpr(good)(Xb, Bb), bf16=True)
    assert [f for f in found if f.contract == "BF16_DOT"] == []


def test_bf16_scan_carry_accumulator_flagged():
    def f(x):
        def body(c, _):
            return c + jnp.sum(x), None
        out, _ = jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), None,
                              length=3)
        return out

    found = check_driver("syn",
                         jax.make_jaxpr(f)(jnp.ones((4,), jnp.bfloat16)),
                         bf16=True)
    assert any(f.contract == "BF16_ACCUM" and "loop carry" in f.message
               for f in found)


# -- contract (d): cast / pad churn ------------------------------------------


def test_cast_roundtrip_through_narrower_dtype_flagged():
    def f(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    found = check_driver("syn", jax.make_jaxpr(f)(jnp.ones((8,))),
                         bf16=False)
    assert any(f.contract == "CAST_ROUNDTRIP" for f in found)


def test_loop_invariant_cast_inside_scan_flagged_scalars_ignored():
    def f(x):
        def body(c, _):
            return c + jnp.sum(x.astype(jnp.bfloat16)), None
        out, _ = jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), None,
                              length=4)
        return out

    found = check_driver("syn", jax.make_jaxpr(f)(jnp.ones((32,))),
                         bf16=False)
    assert any(f.contract == "LOOP_CONST_CAST" for f in found)

    def g(x):  # sub-threshold operand: weak-type scalar promotion, ignored
        def body(c, _):
            return c + jnp.sum(x.astype(jnp.bfloat16)), None
        out, _ = jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), None,
                              length=4)
        return out

    small = check_driver("syn", jax.make_jaxpr(g)(jnp.ones((4,))),
                         bf16=False)
    assert [f for f in small if f.contract == "LOOP_CONST_CAST"] == []


def test_loop_invariant_pad_inside_scan_flagged():
    def f(x):
        def body(c, _):
            padded = jnp.pad(x, ((0, 4),), constant_values=x.dtype.type(0))
            return c + jnp.sum(padded), None
        out, _ = jax.lax.scan(body, jnp.zeros(()), None, length=4)
        return out

    found = check_driver("syn", jax.make_jaxpr(f)(jnp.ones((32,))),
                         bf16=False)
    assert any(f.contract == "LOOP_CONST_PAD" for f in found)


# -- waiver ledger (W0 semantics) --------------------------------------------


def test_waiver_suppresses_matching_finding_and_is_marked_matched():
    f = Finding("megakernel", "LOOP_CONST_PAD", "re-padded ...",
                "scan/while::pad @ ops.py:61 (csvm_round_block)")
    kept, matched = contracts.apply_waivers([f])
    assert kept == []
    assert ("LOOP_CONST_PAD", "csvm_round_block") in matched


def test_unmatched_or_reasonless_waivers_are_w0_errors(monkeypatch):
    # a full match set audits clean
    assert contracts.audit_waivers(set(WAIVERS)) == []
    # every ledger entry unmatched -> one stale error each
    stale = contracts.audit_waivers(set())
    assert len(stale) == len(WAIVERS)
    assert all("stale" in e for e in stale)
    # a reasonless entry is an error even when matched
    key = ("F64", "synthetic-site")
    monkeypatch.setitem(contracts.WAIVERS, key, "   ")
    errs = contracts.audit_waivers(set(WAIVERS))
    assert any("no reason" in e for e in errs)


def test_every_shipped_waiver_has_a_reason():
    assert all(str(r).strip() for r in WAIVERS.values())


# -- cost model + roofline gate ----------------------------------------------


def test_dot_flops_counts_2mnk_and_scales_by_scan_length():
    def one(a, b):
        return a @ b

    closed = jax.make_jaxpr(one)(jnp.ones((3, 5)), jnp.ones((5, 7)))
    assert costmodel.summarize(closed)["dot_flops"] == 2 * 3 * 7 * 5

    def looped(a, b):
        def body(c, _):
            return c + a @ b, None
        out, _ = jax.lax.scan(body, jnp.zeros((3, 7)), None, length=6)
        return out

    closed = jax.make_jaxpr(looped)(jnp.ones((3, 5)), jnp.ones((5, 7)))
    assert costmodel.summarize(closed)["dot_flops"] == 6 * 2 * 3 * 7 * 5


def test_roofline_gate_passes_on_shipped_bench_and_catches_tampering():
    bench = json.loads((REPO_ROOT / "BENCH_megakernel.json").read_text())
    assert costmodel.roofline_gate(bench) == []
    bench["roofline"]["flops_per_round"] += 1
    drift = costmodel.roofline_gate(bench)
    assert drift and "flops_per_round" in drift[0]


# -- registry + the repo gate ------------------------------------------------


def test_registry_covers_the_parity_matrix_plus_bf16_and_serving():
    reg = drivers.build_registry()
    assert set(drivers.PARITY_DRIVERS) <= set(reg)
    assert len(drivers.PARITY_DRIVERS) == 13
    assert {"megakernel-bf16", "uneven-bf16", "serving-bucket"} <= set(reg)
    assert all(reg[n].bf16 for n in reg if "bf16" in n)


def test_repo_drivers_satisfy_all_contracts():
    """The enforced gate: every registered driver traces clean (waived
    findings excepted) and the roofline block has not drifted."""
    from tools.jaxtrace import run_report
    report, kept, errors = run_report()
    assert kept == [], [f.format() for f in kept]
    assert errors == []
    assert report["roofline_gate"]["ok"]
    assert len(report["drivers"]) >= 19


def test_cli_exits_zero_and_writes_artifact(tmp_path):
    out = tmp_path / "contracts.json"
    run = subprocess.run(
        [sys.executable, "-m", "tools.jaxtrace", "--out", str(out)],
        cwd=ROOT, capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "all IR contracts hold" in run.stdout
    table = json.loads(out.read_text())
    assert set(drivers.PARITY_DRIVERS) <= set(table["drivers"])
