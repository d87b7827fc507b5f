"""declint suite: one positive and one negative case per rule (R1-R8),
waiver semantics (suppression + the W0 reasonless-waiver error), the
repo-clean gate, the CLI entry point, the BENCH artifact schema, and the
compile-guard runtime harness.

Rule motivations live in ``tools/declint/README.md``.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tools.declint import EXEMPT, lint_paths, lint_source, load_allowed_axes
from tools.declint.bench_schema import validate, validate_file
from tools.declint.core import check_exempt_list
from tools.declint.rules import default_rules

ROOT = Path(__file__).resolve().parent.parent
AXES = {"pod", "data", "model", "node_chunk", "lam"}


def _rules_of(violations):
    return sorted({v.rule for v in violations})


def lint(src, path="repro/core/some_module.py", axes=AXES):
    return lint_source(textwrap.dedent(src), path=path, allowed_axes=axes)


# -- rule catalogue ---------------------------------------------------------


def test_catalogue_has_at_least_eight_documented_rules():
    rules = default_rules()
    assert len(rules) >= 8
    assert len({r.id for r in rules}) == len(rules)
    assert all(r.doc for r in rules)


# -- R1: prox home ----------------------------------------------------------


def test_r1_flags_update_prox_outside_solver():
    bad = """
    def soft_threshold(v, t):      # re-definition (body doesn't matter)
        return v

    def local(z, omega, lam):
        return soft_threshold(omega * z, lam * omega)

    def inline(v, t):
        return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)
    """
    got = lint(bad, path="repro/core/path.py")
    assert _rules_of(got) == ["R1"]
    assert len(got) == 3          # re-definition, (7a') call, inline pattern

def test_r1_allows_solver_home_and_plain_calls():
    ok_in_solver = """
    def soft_threshold(v, t):
        return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)

    def local_update(z, omega, lam):
        return soft_threshold(omega * z, lam * omega)
    """
    assert lint(ok_in_solver, path="repro/core/solver.py") == []
    # a plain soft-threshold call (not the (7a') application) is fine anywhere
    assert lint("""
    from repro.core.solver import soft_threshold

    def shrink(v, t):
        return soft_threshold(v, t)
    """, path="repro/core/penalties.py") == []


# -- R2: kernel dot precision -----------------------------------------------


def test_r2_flags_unpinned_kernel_dots():
    bad = """
    import jax.numpy as jnp

    def _kern(x_ref, o_ref):
        a = x_ref[...]
        o_ref[...] = jnp.dot(a, a)

    def _kern2(x_ref, o_ref):
        a = x_ref[...]
        o_ref[...] = a @ a
    """
    got = lint(bad, path="repro/kernels/foo.py")
    assert _rules_of(got) == ["R2"] and len(got) == 2

def test_r2_allows_pinned_dots_and_non_kernel_code():
    ok = """
    import jax.numpy as jnp

    def _kern(x_ref, o_ref):
        a = x_ref[...]
        o_ref[...] = jnp.dot(a, a, preferred_element_type=jnp.float32)

    def host_math(a):
        return jnp.dot(a, a)       # not a kernel body
    """
    assert lint(ok, path="repro/kernels/foo.py") == []
    # the same unpinned dot outside kernels/ is out of R2's scope
    assert lint("""
    import jax.numpy as jnp

    def _kern(x_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], x_ref[...])
    """, path="repro/core/foo.py") == []


# -- R3: rho before cast ----------------------------------------------------


def test_r3_flags_rho_after_compute_dtype_cast():
    bad = """
    def make(X, cfg):
        X = X.astype(problem_dtype(cfg))
        rho = compute_rho(X, cfg.h, cfg.kernel)
        return X, rho

    def direct(X, cfg):
        return compute_rho(X.astype(jnp.bfloat16), cfg.h, cfg.kernel)
    """
    got = lint(bad)
    assert _rules_of(got) == ["R3"] and len(got) == 2

def test_r3_allows_rho_from_fp32_then_cast():
    ok = """
    def make(X, cfg):
        rho = compute_rho(X, cfg.h, cfg.kernel)
        X = X.astype(problem_dtype(cfg))
        return X, rho
    """
    assert lint(ok) == []


# -- R4: tracer branches ----------------------------------------------------


def test_r4_flags_python_branch_on_traced_param():
    bad = """
    import jax

    def body(carry, x):
        if x > 0:
            carry = carry + x
        return carry, x

    out = jax.lax.scan(body, 0.0, xs)

    def wbody(v):
        while v > 1.0:
            v = v * 0.5
        return v

    r = jax.lax.while_loop(cond, wbody, v0)
    """
    got = lint(bad)
    assert _rules_of(got) == ["R4"] and len(got) == 2

def test_r4_allows_static_uses_of_traced_params():
    ok = """
    import jax

    def body(carry, x):
        if x.shape[0] > 2:
            carry = carry * 2.0
        if x is None:
            return carry, x
        k = 3 if len(x.shape) == 2 else 4
        return carry + k, x

    out = jax.lax.scan(body, 0.0, xs)
    """
    assert lint(ok) == []


# -- R5: kernel collectives -------------------------------------------------


def test_r5_flags_collective_inside_kernel_body():
    bad = """
    import jax

    def _kern(x_ref, o_ref):
        o_ref[...] = jax.lax.psum(x_ref[...], "node_chunk")
    """
    got = lint(bad, path="repro/kernels/foo.py")
    assert _rules_of(got) == ["R5"]

def test_r5_allows_collectives_between_launches():
    ok = """
    import jax

    def neighbour_sum(B):
        return jax.lax.psum(B, "node_chunk")  # mesh level, not a kernel body
    """
    assert lint(ok) == []


# -- R6: mesh axis names ----------------------------------------------------


def test_r6_flags_unknown_axis_names():
    bad = """
    import jax
    from jax.sharding import PartitionSpec as P

    def f(x):
        y = jax.lax.psum(x, "nodes")            # typo: not a mesh axis
        return jax.lax.all_gather(y, axis_name="lambda")

    spec = P("banana", None)
    """
    got = lint(bad)
    assert _rules_of(got) == ["R6"] and len(got) == 3

def test_r6_allows_known_axes_and_skips_without_vocabulary():
    ok = """
    import jax
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "node_chunk")

    spec = P("lam", "node_chunk")
    """
    assert lint(ok) == []
    # no launch/mesh.py vocabulary (axes=None): the rule stands down
    bad = 'import jax\ndef f(x):\n    return jax.lax.psum(x, "wat")\n'
    assert lint_source(bad, allowed_axes=None) == []

def test_r6_vocabulary_loads_from_mesh_module():
    assert load_allowed_axes(ROOT / "src") == AXES


# -- R7: host math in traced scope ------------------------------------------


def test_r7_flags_numpy_and_float64_in_jitted_path():
    bad = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def f(x):
        y = np.sum(x)                    # host sync / constant fold
        return jnp.asarray(y, jnp.float64)
    """
    got = lint(bad)
    assert _rules_of(got) == ["R7"] and len(got) == 2

def test_r7_allows_host_numpy_outside_traced_scope():
    ok = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    TABLE = np.linspace(0.0, 1.0, 8)     # module level: host side is fine

    @jax.jit
    def f(x):
        return jnp.sum(x) + jnp.asarray(TABLE)[0]

    def host_prep(X):
        return np.float64(X.sum())       # not traced
    """
    assert lint(ok) == []


# -- R8: cached program builders --------------------------------------------


def test_r8_flags_uncached_shard_map_jit_builder():
    bad = """
    import jax
    from jax import shard_map

    def build(mesh, m):
        def fn(X):
            return X * 2.0
        sm = shard_map(fn, mesh=mesh, in_specs=None, out_specs=None)
        return jax.jit(sm)
    """
    got = lint(bad)
    assert _rules_of(got) == ["R8"]

def test_r8_allows_lru_cached_builder():
    ok = """
    import functools
    import jax
    from jax import shard_map

    @functools.lru_cache(maxsize=64)
    def build(mesh, m):
        def fn(X):
            return X * 2.0
        sm = shard_map(fn, mesh=mesh, in_specs=None, out_specs=None)
        return jax.jit(sm)
    """
    assert lint(ok) == []


# -- waivers ----------------------------------------------------------------


def test_waiver_with_reason_suppresses_named_rule():
    src = """
    def f(v, t):
        # declint: disable=R1 fused prox needed here, parity-tested
        return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)
    """
    assert lint(src) == []
    # same-line placement works too
    src2 = ("def f(v, t):\n"
            "    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)"
            "  # declint: disable=R1 fused prox, parity-tested\n")
    assert lint_source(src2, path="repro/core/x.py", allowed_axes=AXES) == []

def test_waiver_without_reason_is_w0_and_does_not_suppress():
    # the reasonless marker is concatenated so this file itself stays W0-clean
    src = ("def f(v, t):\n"
           "    # declint: dis" "able=R1\n"
           "    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)\n")
    got = lint(src)
    assert _rules_of(got) == ["R1", "W0"]

def test_waiver_only_covers_named_rules():
    src = """
    def f(v, t):
        # declint: disable=R2 wrong rule named
        return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)
    """
    assert _rules_of(lint(src)) == ["R1"]


# -- R9: interpret literals + the relaxed tier ------------------------------


def test_r9_flags_literal_interpret_true_in_call_and_default():
    bad = """
    def csvm(x, interpret=True):
        return pl.pallas_call(body, out_shape=x, interpret=True)(x)
    """
    got = lint(bad, path="repro/kernels/csvm_update.py")
    assert _rules_of(got) == ["R9"]
    assert len(got) == 2          # the param default and the call keyword

def test_r9_clean_on_backend_resolved_interpret():
    ok = """
    def csvm(x, interpret=None):
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return pl.pallas_call(body, out_shape=x, interpret=interpret)(x)
    """
    assert lint(ok, path="repro/kernels/csvm_update.py") == []

def test_relaxed_tier_skips_test_only_idioms():
    # prox oracle (R1), tracer-branch oracle (R4), pinned interpret (R9):
    # all fine in a test file under the relaxed tier
    src = textwrap.dedent("""
    def soft_threshold(v, t):
        return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)

    def run(x, interpret=True):
        return pl.pallas_call(body, out_shape=x, interpret=True)(x)
    """)
    relaxed = lint_source(src, path="tests/test_x.py", relaxed=True)
    assert relaxed == []
    strict = lint_source(src, path="repro/core/x.py", allowed_axes=AXES)
    assert {"R1", "R9"} <= set(_rules_of(strict))

def test_relaxed_tier_still_fires_on_real_bugs():
    # a kernel body with an unqualified dot is a bug even in a test file
    src = textwrap.dedent("""
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] @ x_ref[...]
    """)
    got = lint_source(src, path="tests/kernels/test_k.py", relaxed=True)
    assert _rules_of(got) == ["R2"]

def test_lint_paths_applies_relaxed_tier_to_tests_dir():
    # the repo's own tests/ tree, linted via lint_paths, must come back
    # clean under the relaxed tier (this is the CI invocation)
    assert lint_paths([ROOT / "tests"]) == []


# -- R10: collective loops need a reduced predicate --------------------------


def test_r10_flags_unreduced_predicate_over_collective_loop():
    # the PR 9 deadlock class at AST level: ppermute in the while body,
    # continue flag never reduced over the axis
    bad = """
    def run(xl):
        def cond(c):
            return c[1]
        def body(c):
            xl, _ = c
            xl = xl + jax.lax.ppermute(xl, "node", perm)
            return (xl, jnp.max(xl) < 100.0)
        return jax.lax.while_loop(cond, body, (xl, True))
    """
    got = lint(bad)
    assert "R10" in _rules_of(got)
    assert any("rendezvous" in v.message for v in got if v.rule == "R10")

def test_r10_clean_when_flag_is_axis_reduced_in_scope():
    # run_tol's shape: the reduction lives in a helper beside the loop
    ok = """
    def run(xl):
        def _flag(x):
            return jax.lax.pmax(jnp.max(x), "node") < 100.0
        def cond(c):
            return c[1]
        def body(c):
            xl, _ = c
            xl = xl + jax.lax.ppermute(xl, "node", perm)
            return (xl, _flag(xl))
        return jax.lax.while_loop(cond, body, (xl, True))
    """
    assert [v for v in lint(ok) if v.rule == "R10"] == []

def test_r10_sees_through_ifexp_body_selection():
    # solver.run_tol passes `fused_body if use_fused else body`
    bad = """
    def run(xl, use_fused):
        def cond(c):
            return c[1]
        def body(c):
            return (jax.lax.psum(c[0], "node"), jnp.max(c[0]) < 1.0)
        def fused_body(c):
            return (jax.lax.psum(c[0], "node"), jnp.max(c[0]) < 1.0)
        return jax.lax.while_loop(cond,
                                  fused_body if use_fused else body,
                                  (xl, True))
    """
    assert "R10" in _rules_of(lint(bad))

def test_r10_flags_cond_branch_with_collective_and_waiver_suppresses():
    bad = """
    def pick(flag, xl):
        return jax.lax.cond(flag, lambda v: jax.lax.psum(v, "node"),
                            lambda v: v, xl)
    """
    assert "R10" in _rules_of(lint(bad))
    waived = """
    def pick(flag, xl):
        # declint: disable=R10 flag is an all-reduce result upstream
        return jax.lax.cond(flag, lambda v: jax.lax.psum(v, "node"),
                            lambda v: v, xl)
    """
    assert [v for v in lint(waived) if v.rule == "R10"] == []


# -- repo gate + CLI --------------------------------------------------------


def test_repo_src_is_lint_clean():
    """The enforced gate: ``python -m tools.declint src`` must stay clean
    (violations are fixed or carry reasoned waivers — never ignored)."""
    assert lint_paths([ROOT / "src"]) == []

def test_exempt_list_is_current_and_stale_entries_error(tmp_path):
    assert check_exempt_list(ROOT / "src") == []
    # against an empty tree every quarantine entry is stale
    assert set(check_exempt_list(tmp_path)) == set(EXEMPT)

def test_cli_exits_zero_on_clean_tree_and_lists_rules():
    run = subprocess.run([sys.executable, "-m", "tools.declint", "src"],
                         cwd=ROOT, capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "clean" in run.stderr
    listing = subprocess.run(
        [sys.executable, "-m", "tools.declint", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True)
    assert listing.returncode == 0
    assert all(f"R{i}:" in listing.stdout for i in range(1, 9))

def test_cli_exits_nonzero_on_violation(tmp_path):
    bad = tmp_path / "repro" / "core" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def soft_threshold(v, t):\n    return v\n")
    run = subprocess.run(
        [sys.executable, "-m", "tools.declint", str(bad)],
        cwd=ROOT, capture_output=True, text=True)
    assert run.returncode == 1
    assert "R1" in run.stdout


# -- bench schema -----------------------------------------------------------


def _valid_bench():
    return {
        "bench": "megakernel",
        "config": {"m": 8, "backend": "cpu"},
        "end_to_end_s": {"jnp": 1.0, "megakernel": 0.5,
                         "by_split": {"4x2": 0.4, "2x4": 0.3}},
        "steady_state_s": {"jnp": 0.2, "megakernel": 0.1},
        "speedup_megakernel_vs_jnp": 2.0,
        "criteria": {"speedup_ge_1.5": True},
    }

def test_bench_schema_accepts_valid_artifact():
    assert validate(_valid_bench(), name="megakernel") == []

@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("criteria"), "missing required key"),
    (lambda d: d.pop("speedup_megakernel_vs_jnp"), "speedup_"),
    (lambda d: d.__setitem__("speedup_megakernel_vs_jnp", float("nan")),
     "finite positive"),
    (lambda d: d["config"].pop("backend"), "config.backend"),
    (lambda d: d["steady_state_s"].__setitem__("jnp", -1.0),
     "finite positive"),
    (lambda d: d["criteria"].__setitem__("bound", 0.25), "bool"),
    (lambda d: d.__setitem__("bench", "other"), "filename"),
])
def test_bench_schema_rejects_malformed_artifacts(mutate, needle):
    doc = _valid_bench()
    mutate(doc)
    problems = validate(doc, name="megakernel")
    assert problems and any(needle in p for p in problems), problems

def test_bench_schema_validates_checked_in_artifacts():
    artifacts = sorted(ROOT.glob("BENCH_*.json"))
    assert artifacts, "no BENCH_*.json artifacts at repo root"
    for f in artifacts:
        assert validate_file(f) == [], f

def test_bench_schema_speedups_must_be_derivable_from_timings():
    # the valid fixture's 2.0 equals steady jnp/megakernel — accepted;
    # a hand-edited headline number no timing pair explains is rejected
    doc = _valid_bench()
    doc["speedup_megakernel_vs_jnp"] = 3.7
    problems = validate(doc, name="megakernel")
    assert any("derivable" in p for p in problems), problems
    # nested per-split leaves count as provenance too (0.4 / 0.1 = 4.0)
    doc["speedup_megakernel_vs_jnp"] = 4.0
    assert validate(doc, name="megakernel") == []


# -- compile guard ----------------------------------------------------------


def test_compile_guard_counts_compiles_and_cache_hits(compile_guard):
    x = jnp.ones((3, 11))
    f = jax.jit(lambda v: v * 2.5 + 0.5)
    snap = compile_guard.snapshot()
    f(x).block_until_ready()
    assert compile_guard.new_since(snap) >= 1     # cold: really compiled
    with compile_guard.expect(0, what="same-shape cache hit"):
        f(x).block_until_ready()                  # warm: zero new programs

def test_compile_guard_budget_violation_raises(compile_guard):
    x = jnp.ones((3, 11))
    with pytest.raises(AssertionError, match="compile budget exceeded"):
        with compile_guard.expect(0, what="fresh program"):
            jax.jit(lambda v: v - 1234.5)(x).block_until_ready()
