"""Runtime trace-contract harness: count XLA backend compilations.

``jax.monitoring`` fires ``/jax/core/compile/backend_compile_duration``
once per *actual* backend compilation — a jit cache hit does not fire.
The program's compile counter (``repro.core.trace.compiles``, the
process's one listener) accumulates the count, and the ``compile_guard``
pytest fixture hands tests a delta-based view of it.

The enforceable contract is **steady state**: cold-start counts include
version-dependent internal helper jits (empirically ~2.5 events per
user-visible program on the pinned jax), so budget tests warm up first
and then assert ZERO new compilations for subsequent same-shape work::

    def test_no_recompiles(compile_guard):
        warm_up()                               # cold compiles land here
        with compile_guard.expect(0, what="second same-shape pass"):
            steady_state_work()

Loaded as a pytest plugin from ``tests/conftest.py``
(``pytest_plugins = ("tools.declint.compile_guard",)``).
"""
from __future__ import annotations

import contextlib
from typing import Iterator

import pytest


class CompileGuard:
    """Delta-based view of the program's monotone backend-compile count."""

    def __init__(self, counter) -> None:
        self._counter = counter

    @property
    def count(self) -> int:
        return self._counter.backend

    def snapshot(self) -> int:
        return self.count

    def new_since(self, snap: int) -> int:
        return self.count - snap

    @contextlib.contextmanager
    def expect(self, max_compiles: int,
               what: str = "block") -> Iterator["CompileGuard"]:
        """Assert at most ``max_compiles`` backend compilations happen
        inside the ``with`` block (0 = everything must hit the cache)."""
        start = self.count
        yield self
        n = self.count - start
        assert n <= max_compiles, (
            f"compile budget exceeded for {what}: {n} XLA backend "
            f"compilation(s), budget {max_compiles}.  A steady-state "
            f"budget of 0 means same-shape work must reuse the cached "
            f"program — look for jit cache misses: non-hashable static "
            f"args, closures rebuilt per call, or a shard_map/jit "
            f"program builder missing @functools.lru_cache (declint R8).")


@pytest.fixture
def compile_guard() -> CompileGuard:
    """Delta-based view of the process compile counter (see module doc)."""
    from repro.core.trace import compiles

    return CompileGuard(compiles)
