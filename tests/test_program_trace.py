"""The program's trace names and its compile counter (``repro.core.trace``).

- The optimized HLO of the fit and path programs carries the device scopes
  in its ``op_name`` metadata, and every operation that holds a scope holds
  exactly one.
- The scopes are metadata only: with ``jax.named_scope`` made a no-op the
  compiled code is the same once the metadata is stripped.
- ``select_lambda_path`` emits its host spans into a profiler trace.
- The compile counter rises on a new shape and stays put on a repeat.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ADMMConfig, admm_adaptive, path, trace, tuning

M, N, P = 4, 30, 12
CFG = ADMMConfig(lam=0.05, tau=1.0, h=0.5, kernel="epanechnikov",
                 max_iter=20)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(M, N, P)), jnp.float32)
    y = jnp.asarray(np.where(rng.normal(size=(M, N)) > 0, 1.0, -1.0),
                    jnp.float32)
    W = jnp.asarray(np.ones((M, M)) - np.eye(M), jnp.float32)
    lams = jnp.asarray(np.logspace(-1, -3, 5), jnp.float32)
    return X, y, W, lams


def _lowered(program, data):
    X, y, W, lams = data
    if program == "fit_tol":
        return admm_adaptive._fit_tol_jit.lower(
            X, y, W, CFG, tol=1e-3, stop_rule="kkt", check_every=4)
    return path._path_select.lower(X, y, W, lams, CFG, "warm", 1e-3, None,
                                   "kkt", None, 4)


EXPECTED = {
    "fit_tol": {trace.ROUND, trace.KKT_CHECK, trace.RHO},
    "path_select": {trace.ROUND, trace.KKT_CHECK, trace.RHO, trace.BIC},
}
INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def _scopes(line: str):
    """The distinct innermost decsvm scopes of an instruction's op_name
    (merged instructions join their names with ';')."""
    found = re.search(r'op_name="([^"]*)"', line)
    inner = set()
    for name in (found.group(1).split(";") if found else ()):
        segs = [s for s in name.split("/") if s.startswith("decsvm.")]
        if segs:
            inner.add(segs[-1])
    return inner


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_optimized_hlo_carries_the_scopes(program, data):
    text = _lowered(program, data).compile().as_text()
    names = set(re.findall(r"decsvm\.\w+", text))
    assert names == EXPECTED[program]


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_each_scoped_op_lies_under_one_scope(program, data):
    text = _lowered(program, data).compile().as_text()
    dots = fusions = 0
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        scopes = _scopes(line)
        if m.group(1) == "dot":
            dots += 1
            assert len(scopes) == 1, line.strip()
        elif m.group(1) == "fusion" and scopes:
            fusions += 1
            assert len(scopes) == 1, line.strip()
    assert dots and fusions


def _code(text: str) -> str:
    """The computations of an HLO module without their metadata (and the
    table of source frames it points into), each name replaced by the
    order of its first use: names are numbered while tracing."""
    body = re.sub(r",? metadata=\{[^}]*\}", "", text[text.index("\n%"):])
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  body)


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_scopes_change_no_compiled_code(program, data, monkeypatch):
    scoped = _code(_lowered(program, data).compile().as_text())
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        plain = _lowered(program, data).compile().as_text()
    finally:
        jax.clear_caches()
    assert "decsvm." not in plain
    assert _code(plain) == scoped


def test_select_lambda_path_emits_its_host_spans(data, tmp_path):
    from jax.profiler import ProfileData

    X, y, W, _ = data
    tuning.select_lambda_path(X, y, W, CFG, num=3, tol=1e-3)   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        tuning.select_lambda_path(X, y, W, CFG, num=3, tol=1e-3)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for e in line.events]
    spans = [n for n in names if n.startswith("decsvm:")]
    assert spans == [trace.SPAN_LAMBDA_GRID, trace.SPAN_PATH_PROGRAM,
                     trace.SPAN_BIC_TABLE]


def test_compile_counter_rises_on_a_new_shape_only():
    f = jax.jit(lambda v: v * 3.25 - 0.75)
    counter = trace.compiles
    before = counter.backend
    f(jnp.ones((3, 17))).block_until_ready()
    mid = counter.backend
    assert mid >= before + 1
    f(jnp.ones((3, 17))).block_until_ready()
    assert counter.backend == mid
    f(jnp.ones((5, 17))).block_until_ready()
    assert counter.backend >= mid + 1


def test_compile_counter_counts_loads_from_the_persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    counter = trace.compiles
    g = lambda v: jnp.cos(v) * 1.375 + 0.125
    loads = []

    def on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            loads.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        x = jnp.ones((2, 23))
        jax.jit(g)(x).block_until_ready()                   # compiled
        jax.clear_caches()
        backend, loaded = counter.backend, len(loads)
        jax.jit(g)(x).block_until_ready()                   # loaded
        # every load raises the count, and nothing compiled
        assert len(loads) - loaded >= 1
        assert counter.backend - backend == len(loads) - loaded
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()
        jax.clear_caches()


def test_compile_counter_counts_inside_a_window():
    import time

    f = jax.jit(lambda v: v / 7.5 + 2.0)
    t0 = time.perf_counter()
    f(jnp.ones((2, 19))).block_until_ready()
    t1 = time.perf_counter()
    f(jnp.ones((2, 19))).block_until_ready()
    t2 = time.perf_counter()
    assert trace.compiles.backend_between(t0, t1) >= 1
    assert trace.compiles.backend_between(t1, t2) == 0


EXCHANGE_SCRIPT = """
import re
import numpy as np, jax, jax.numpy as jnp
from repro.core import ADMMConfig, decentral, graph
m, n, p = 16, 12, 8
top = graph.k_regular(m, 4)
mesh = decentral.make_node_chunk_mesh(4)
rng = np.random.default_rng(0)
X = jnp.asarray(rng.normal(size=(m, n, p)), jnp.float32)
y = jnp.asarray(np.where(rng.normal(size=(m, n)) > 0, 1.0, -1.0), jnp.float32)
cfg = ADMMConfig(lam=0.05, h=0.5, max_iter=8)
ops, offsets, m_pad = decentral._chunk_prep(X, y, top, mesh)
fitted = decentral.build_chunked_admm(m_pad, p, cfg, mesh, offsets,
                                      tol=1e-3, check_every=4)
text = fitted.lower(ops["X"], ops["y"], ops["W_diag"], ops["W_off"],
                    ops["deg"], jnp.ones((p,), jnp.float32),
                    ops["nmask"]).compile().as_text()
permutes = [l for l in text.splitlines()
            if re.search(r"= .*collective-permute(-start)?\\(", l)]
print("PERMUTES", len(permutes))
print("SCOPED", sum("decsvm.exchange" in l for l in permutes))
"""


def test_chunked_programs_collective_permutes_carry_the_exchange_scope():
    """The chunked engine on 4 CPU devices: every ``collective-permute`` of
    its optimized HLO (the neighbour exchange between node chunks) lies
    under ``decsvm.exchange``."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    run = subprocess.run([sys.executable, "-c",
                          textwrap.dedent(EXCHANGE_SCRIPT)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    counts = dict(line.split() for line in run.stdout.splitlines()
                  if line.startswith(("PERMUTES", "SCOPED")))
    assert int(counts["PERMUTES"]) > 0
    assert counts["SCOPED"] == counts["PERMUTES"]
