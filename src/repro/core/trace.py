"""The program's trace names and its compile counter.

Device scopes (``jax.named_scope``) mark the single home of each piece of
device work.  They are HLO metadata only: the ``op_name`` of every
instruction traced inside one carries the scope as a path segment
(``jit(_fit_tol_jit)/while/body/decsvm.kkt_check/dot_general``), and the
compiled code is the same with or without them.  A profiler trace records
that path as the ``tf_op`` of each device operation.

    ROUND      decsvm.round      ``solver.make_step``: step, cached_round,
                                 round_block (primal update, neighbour
                                 sums, dual update; every driver)
    KKT_CHECK  decsvm.kkt_check  ``solver.kkt_residual``: the network
                                 gradient and the stop statistic
    RHO        decsvm.rho        ``solver.compute_rho``: the power
                                 iteration over each node's X
    BIC        decsvm.bic        ``path.score_path``: path scoring
    EXCHANGE   decsvm.exchange   ``decentral``'s neighbour sums across
                                 devices: the halo ``ppermute``s and the
                                 off-diagonal block dots of the chunked
                                 engine, the gather or ring of the
                                 sharded one (inside ``decsvm.round``)

Kernel name (the ``name`` of a ``pallas_call``, which names its HLO
custom call and so its device operation):

    decsvm_xpass                 ``kernels/xpass.py``, its two
    decsvm_xpass_rows            orientations, called by
                                 ``solver.node_xtphi`` inside the round,
                                 KKT and rho scopes where
                                 ``xpass_applies``; read by
                                 ``bench/metrics/kernel_share.fit`` and
                                 ``xpass_roofline.p2p``

Host spans (``jax.profiler.TraceAnnotation``) cost nothing unless a
profiler session is active:

    SPAN_LAMBDA_GRID   decsvm:lambda_grid   ``tuning.select_lambda_path``:
                                            the host copy of X, lambda_max
    SPAN_PATH_PROGRAM  decsvm:path_program  its dispatch of the path program
                                            (every engine)
    SPAN_BIC_TABLE     decsvm:bic_table     its table and copy of best_B:
                                            the host's wait for the path

``exchange`` counts the rows of B that the chunked engine's cross-chip
exchange carries (``decentral._block_neighbor_sum_fn``), against the rows
that whole chunks would carry, summed over every kept block diagonal of
every neighbour sum the process has built.  It counts when a program is
traced, never when it runs; read by ``bench/metrics/halo_share.p2p``.

``compiles`` is the process's one ``jax.monitoring`` listener, installed
on import.  It keeps one monotone count, ``backend``: every backend
compile.  JAX records that event around the persistent-cache lookup, so a
program loaded from that cache counts here as well.  JAX fires it only
when a program is built, never on a call that hits the jit cache.
"""
from __future__ import annotations

import bisect
import threading
import time
from typing import Optional

import jax.monitoring

ROUND = "decsvm.round"
KKT_CHECK = "decsvm.kkt_check"
RHO = "decsvm.rho"
BIC = "decsvm.bic"
EXCHANGE = "decsvm.exchange"

SPAN_LAMBDA_GRID = "decsvm:lambda_grid"
SPAN_PATH_PROGRAM = "decsvm:path_program"
SPAN_BIC_TABLE = "decsvm:bic_table"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """A monotone count of the programs this process compiled or loaded."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._backend_at: list = []        # time.perf_counter() of each

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self._backend_at.append(time.perf_counter())

    @property
    def backend(self) -> int:
        with self._lock:
            return len(self._backend_at)

    def backend_between(self, t0: float, t1: float) -> int:
        """Backend compiles (and cache loads) that ended between two
        ``time.perf_counter()`` readings of this process."""
        with self._lock:
            return (bisect.bisect_right(self._backend_at, t1)
                    - bisect.bisect_left(self._backend_at, t0))


class ExchangeCounter:
    """Rows sent by the cross-chip halo exchanges built so far, and the
    rows that whole chunks would have sent in their place."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.sent_rows = 0
        self.chunk_rows = 0

    def record(self, sent: int, chunk: int) -> None:
        """One kept block diagonal: ``sent`` halo rows of a ``chunk``-row
        chunk cross the chips."""
        with self._lock:
            self.sent_rows += sent
            self.chunk_rows += chunk

    def share(self) -> Optional[float]:
        """Rows sent as a percentage of whole chunks' rows; None before
        any exchange was built."""
        with self._lock:
            if not self.chunk_rows:
                return None
            return 100.0 * self.sent_rows / self.chunk_rows


exchange = ExchangeCounter()
compiles = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(compiles._on_duration)
