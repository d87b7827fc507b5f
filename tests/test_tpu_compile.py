"""Ahead-of-time compiles of the deCSVM Pallas kernels for a described TPU
v5e chip, with the chip's own compiler (Mosaic) and no chip attached.

Interpret-mode parity (tests/test_kernels.py) cannot see what Mosaic
refuses: contraction layouts it does not lower, primitives with no TPU
lowering (erf), and kernels that need more VMEM than the core has or
than the scoped limit they pass.  Each compile here takes a few seconds.
The topology is described inside a fixture, never at import time, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import losses
from repro.kernels import csvm_update, ops


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host; the persistent compilation cache is off
    around these compiles (an entry written without a chip cannot be read
    back, and would warn on every later run)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the wrappers off interpret mode: JAX still reports the CPU as
    its backend here, so their default would interpret the kernels."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _round_block(chip, m, n, p, dtype, kernel="epanechnikov"):
    f = lambda X, y, B, P, W, deg, rho, om, lam, nact: \
        csvm_update.csvm_round_block(
            X, y, B, P, W, deg, rho, om, lam, nact, tau=1.0, lam0=0.0,
            h=0.25, kernel=kernel, num_rounds=4, want_kkt=True,
            interpret=False)
    s = lambda *shape: _spec(chip, shape)
    return jax.jit(f).lower(
        _spec(chip, (m, n, p), dtype), s(m, n), s(m, p), s(m, p), s(m, m),
        s(m), s(m), s(m), s(p), _spec(chip, (), jnp.int32)).compile()


def _block_update(chip, m, n, p, dtype, kernel="epanechnikov"):
    f = lambda X, y, B, P, ng, rho, om, lam: csvm_update.csvm_block_update(
        X, y, B, P, ng, rho, om, lam, h=0.25, kernel=kernel,
        interpret=False)
    s = lambda *shape: _spec(chip, shape)
    return jax.jit(f).lower(
        _spec(chip, (m, n, p), dtype), s(m, n), s(m, p), s(m, p), s(m, p),
        s(m), s(m), s(p)).compile()


def test_two_pass_local_update_compiles_at_scale(chip):
    f = lambda X, y, b, pd, ng, rho, om, lam: csvm_update.csvm_local_update(
        X, y, b, pd, ng, rho, om, lam, h=0.25, interpret=False)
    s = lambda *shape: _spec(chip, shape)
    compiled = jax.jit(f).lower(s(2000, 10000), s(2000), s(10000),
                                s(10000), s(10000), s(), s(), s()).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("p,dtype", [(500, jnp.float32),
                                     (1000, jnp.bfloat16)])
@pytest.mark.parametrize("build", [_round_block, _block_update],
                         ids=["round_block", "block_update"])
def test_fused_kernels_compile_at_paper_shape(chip, build, p, dtype):
    """The paper's m=10 nodes of n=200 samples: the round megakernel and
    the fused block update lower through Mosaic in fp32 and bf16."""
    assert _has_kernel(build(chip, 10, 200, p, dtype))


@pytest.mark.parametrize("kernel", losses.KERNELS)
def test_every_smoothing_kernel_lowers_in_the_fused_kernel(chip, kernel):
    assert _has_kernel(_block_update(chip, 10, 200, 500, jnp.float32,
                                     kernel))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_megakernel_compiles_at_the_edge_of_its_vmem_budget(chip, dtype):
    """The largest p that ``megakernel_supported`` admits at m=16,
    n=2000 compiles within the scoped-VMEM limit the kernel passes; one
    lane tile more is refused by the guard.  (At n=2000 Mosaic keeps
    working copies of X, so this is where the VMEM model is tight.)"""
    m, n, p = 16, 2000, 128
    while ops.megakernel_supported(m, n, p + 128, dtype, interpret=False):
        p += 128
    assert p >= 256
    assert _has_kernel(_round_block(chip, m, n, p, dtype))


def test_decsvm_fit_pallas_backend_holds_the_kernel(chip, mosaic):
    from repro.core import ADMMConfig, decsvm_fit
    cfg = ADMMConfig(lam=0.05, h=0.3, max_iter=10, backend="pallas")
    s = lambda *shape: _spec(chip, shape)
    compiled = jax.jit(lambda X, y, W: decsvm_fit(X, y, W, cfg)).lower(
        s(10, 200, 501), s(10, 200), s(10, 10)).compile()
    assert _has_kernel(compiled)


def test_one_pass_kernel_compiles_at_the_epsilon_node_shape(chip):
    """``decsvm_xpass`` at the epsilon cell's (10, 40000, 2001): p=2001 and
    the ragged last row tile lower through Mosaic, and X reaches the
    kernel through its transposed view with no copy (the TPU stores this
    X n-minor, so the view is a bitcast)."""
    from repro.kernels import xpass
    m, n, p = 10, 40000, 2001
    for weight in xpass.WEIGHTS:
        f = lambda X, y, V, w=weight: xpass.xpass(X, y, V, weight=w, h=0.25,
                                                  interpret=False)
        compiled = jax.jit(f).lower(_spec(chip, (m, n, p)),
                                    _spec(chip, (m, n)),
                                    _spec(chip, (m, p))).compile()
        assert _has_kernel(compiled)
        assert compiled.memory_analysis().temp_size_in_bytes < n * p * 4


def test_row_kernel_compiles_at_the_peer_node_shape(chip):
    """``decsvm_xpass_rows`` at a peer network's node block (200, 20001):
    p=20001 and the masked last row group lower through Mosaic, and X
    (stored p-minor at this shape) reaches the kernel with no copy."""
    from repro.kernels import xpass
    m, n, p = 4, 200, 20001
    for weight in xpass.WEIGHTS:
        f = lambda X, y, V, w=weight: xpass.xpass_rows(
            X, y, V, weight=w, h=0.25, interpret=False)
        compiled = jax.jit(f).lower(_spec(chip, (m, n, p)),
                                    _spec(chip, (m, n)),
                                    _spec(chip, (m, p))).compile()
        assert _has_kernel(compiled)
        assert compiled.memory_analysis().temp_size_in_bytes < n * p * 4


def test_epsilon_fit_program_reads_x_in_place(chip, mosaic, monkeypatch):
    """The fit program of the epsilon cell with the rule on (a TPU): every
    product over X is the one-pass kernel (round, KKT check, power
    iteration in and after its loop), and no X-shaped copy is left; XLA's
    own fusions relayout X once per fit."""
    from repro.core import ADMMConfig, solver
    from repro.core.admm_adaptive import _fit_tol_jit
    m, n, p = 10, 40000, 2001
    cfg = ADMMConfig(lam=0.01, h=0.3, max_iter=300)
    f = lambda X, y, W: _fit_tol_jit(X, y, W, cfg, tol=1e-3,
                                     stop_rule="kkt", check_every=4)
    s = lambda *shape: _spec(chip, shape)
    copies = {}
    for platform in ("cpu", "tpu"):
        monkeypatch.setattr(solver, "_platform", lambda: platform)
        jax.clear_caches()                  # the rule is read when traced
        text = jax.jit(f).lower(s(m, n, p), s(m, n), s(m, m)).compile(
        ).as_text()
        copies[platform] = sum(
            1 for line in text.splitlines()
            if " copy(" in line and f"f32[{m},{n},{p}]" in line.split("=")[1])
        kernels = sum(1 for line in text.splitlines()
                      if "custom-call(" in line and "decsvm_xpass" in line)
        assert kernels == (4 if platform == "tpu" else 0)
    jax.clear_caches()
    assert copies == {"cpu": 1, "tpu": 0}


def test_p2p_fit_program_compiles_for_four_chips(topo, mosaic, monkeypatch):
    """The chunked fit program of the ``p2p_m1024.fit`` cell, X (1024, 200,
    20001) sharded by node over a described v5e:2x2: every product over X
    (round, KKT check, power iteration in and after its loop) is the row
    kernel, the neighbour exchange is collective-permutes, and beside the
    argument the program holds at most one X-sized temporary (a relayout
    of the chip's X, where the compiler plans X node-minor), so that with
    the pool's second data set it fits a chip's 16 GB.  The exchange sends
    each offset's 2 halo rows of B, f32[2,20001], never a whole chunk."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ADMMConfig, decentral, graph, solver
    from repro.kernels import xpass
    m, n, p = 1024, 200, 20001
    monkeypatch.setattr(solver, "_platform", lambda: "tpu")
    jax.clear_caches()
    mesh = Mesh(np.array(topo.devices), ("node_chunk",))
    W_diag, halos, W_off = graph.k_regular(m, 4).chunk_operands(4)
    cfg = ADMMConfig(lam=0.0035, h=0.14, max_iter=300)
    fitted = decentral.build_chunked_admm(m, p, cfg, mesh, halos,
                                          tol=1e-3, check_every=4)
    rows, rep = (NamedSharding(mesh, P("node_chunk")),
                 NamedSharding(mesh, P()))
    s = lambda sharding, *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                                      sharding=sharding)
    compiled = fitted.lower(
        s(rows, m, n, p), s(rows, m, n), s(rows, *W_diag.shape),
        s(rows, *W_off.shape),
        s(rows, m), s(rep, p), s(rows, m)).compile()
    jax.clear_caches()
    text = compiled.as_text()
    kernels = re.findall(r"(decsvm_xpass\w*)\.\d+ = \S+ custom-call\(",
                         text)
    assert sorted(set(kernels)) == [xpass.ROWS_NAME] and len(kernels) == 4
    sent = {shape for line in text.splitlines()
            if re.search(r" collective-permute(-start)?\(", line)
            for shape in re.findall(r"f32\[[\d,]+\]",
                                    line.split(" collective-permute")[0])}
    assert sent == {f"f32[2,{p}]"}, sent
    share = 4 * (m // 4) * n * p
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.1 * share
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + share < 16e9


def test_p2p_handoff_is_one_all_gather_on_four_chips(topo):
    """The chunked fit's result, B (1024, 20001) sharded by node over a
    described v5e:2x2, is replicated by ``decentral._replicated``'s program
    on the chips: one all-gather of the chip's (256, 20001) rows, no
    temporary and no host transfer."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import decentral
    m, p = 1024, 20001
    mesh = Mesh(np.array(topo.devices), ("node_chunk",))
    B = jax.ShapeDtypeStruct((m, p), jnp.float32,
                             sharding=NamedSharding(mesh, P("node_chunk")))
    compiled = decentral._replicator(mesh).lower(B).compile()
    text = compiled.as_text()
    gathers = re.findall(r"= (f32\[[\d,]+\])\S* all-gather(?:-start)?\(",
                         text)
    assert gathers == [f"f32[{m},{p}]"], gathers
    assert not re.search(r"custom-call|infeed|outfeed|send|recv", text)
    mem = compiled.memory_analysis()      # sizes of (8, 128) tiles
    share = 4 * (m // 4) * p
    assert share <= mem.argument_size_in_bytes < 1.01 * share
    assert 4 * share <= mem.output_size_in_bytes < 4.04 * share
    assert mem.temp_size_in_bytes == 0
