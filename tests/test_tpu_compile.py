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
def chip():
    """One described v5e chip; the persistent compilation cache is off
    around these compiles (an entry written without a chip cannot be read
    back, and would warn on every later run)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
