"""Fit-serving endpoint: tuned deCSVM fits as batched, async infrastructure.

The token engine (``repro.serving.engine``) serves *inference* for the
language models; this module is the corresponding surface for the paper's
technique itself — a queue of fit requests (features + labels + network
adjacency), each answered with a lambda-tuned, optionally folded-concave
(LLA) deCSVM head.

Scheduling is **request-batched**: ``submit()`` returns a future-like
``FitHandle`` immediately, and the scheduler groups queued requests into
buckets keyed by (shapes, config, grid, criterion, mode, penalty, ...).
Each bucket — up to ``max_batch`` same-shape problems — resolves through
ONE compiled program, the problem-batched path engine
(``tuning.select_lambda_path_many`` over
``path.decsvm_path_select_many``): all fits, their BIC/CV scoring, and
every per-problem argmin run in a single ``vmap``-batched traversal, with
per-problem rho/omega from ``solver.make_problem``.  LLA stage-2 re-fits
batch the same way (``path.decsvm_fit_many`` traces per-problem
(lambda, weights), so a bucket of re-fits never recompiles).  A stream of
same-shaped requests therefore compiles once and then pays one program
execution per *bucket*, not per request.

Large-m requests (m above the device count) route to the chunked
node-megabatch engine instead (``engine="auto"`` on ``FitRequest``;
``decentral.decsvm_path_mesh``): one problem then spans every device via
the node-chunk mesh, so such buckets execute per request while still
sharing one cached compiled program across the bucket.

The server shares the ``FifoEngine`` scheduling surface with the token
engine (submit / step / run / pending / utilization) and adds an async
mode: ``start()`` spawns a background worker that drains the queue as
buckets; ``FitHandle.result()`` blocks until its request resolves.
Results are delivered exactly once — ``run()`` returns (and drops) the
results completed since the last drain, and a ``FitHandle`` hands its
result out independently — so a long-lived server's memory stays bounded.
Submitting a request id that is still pending or undelivered raises.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics, tuning
from repro.core.admm import ADMMConfig, hard_threshold_final
from repro.serving.engine import FifoEngine


@dataclasses.dataclass
class FitRequest:
    """One decentralized fit job.

    X: (m, n, p) node-partitioned design (include the intercept column);
    y: (m, n) labels in {-1, +1}; W: (m, m) adjacency — or a
    ``graph.BlockTopology`` for large-m chunked fits (no dense O(m^2)
    host matrix required).
    lams: explicit lambda grid, or None to build ``lambda_grid(num)``
    from this request's data at submit time (note: requests only share a
    bucket when their resolved grids coincide — pass an explicit common
    grid to batch across datasets).
    criterion: "bic" | "cv"; penalty: None (plain l1) or one of
    ``repro.core.penalties.PENALTIES`` for a one-step-LLA stage-2 re-fit.
    engine: "auto" | "dense" | "chunked".  "auto" resolves at submit
    time: "chunked" (the node-megabatch mesh engine) when m exceeds the
    device count — such problems cannot run through
    the dense problem-batched program at all — else "dense".  The
    resolved engine is part of the bucket key, so dense and chunked
    requests never co-batch.
    """
    rid: int
    X: np.ndarray
    y: np.ndarray
    W: np.ndarray
    cfg: ADMMConfig = ADMMConfig(lam=0.0)
    lams: Optional[Sequence[float]] = None
    num: int = 12
    mode: str = "warm"
    criterion: str = "bic"
    cv_folds: int = 5
    cv_seed: int = 0
    penalty: Optional[str] = None
    threshold: bool = False          # Theorem-4 hard thresholding of B
    tol: float = 1e-6
    stop_rule: str = "kkt"
    check_every: int = 4
    engine: str = "auto"


@dataclasses.dataclass
class FitResult:
    rid: int
    best_lam: float
    B: np.ndarray                    # (m, p) per-node estimates
    beta: np.ndarray                 # (p,) network-average estimate
    table: List[Tuple[float, float, float]]   # (lambda, criterion, supp)
    criterion: str
    lam_weights: Optional[np.ndarray]         # LLA stage-2 weights, if any
    train_accuracy: float
    consensus_gap: float
    wall_s: float                    # wall-clock of the bucket that ran it
    batch_size: int = 1              # problems co-batched in that bucket


class FitHandle:
    """Future-like handle for a submitted ``FitRequest``.

    ``done()`` polls; ``result(timeout)`` blocks until the request
    resolves (driving the server inline when no background worker is
    running) and returns the ``FitResult``.  A bucket failure surfaces
    here as the raised exception.
    """

    def __init__(self, rid: int, server: "DecsvmFitServer") -> None:
        self.rid = rid
        self._server = server
        self._event = threading.Event()
        self._result: Optional[FitResult] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> FitResult:
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        if not self._event.is_set():
            self._server._drive(self, timeout)
        remaining = (None if deadline is None
                     else max(0.0, deadline - time.monotonic()))
        if not self._event.wait(remaining):
            raise TimeoutError(f"fit request {self.rid} not done "
                               f"within {timeout}s")
        if self._error is not None:
            raise self._error
        self._server._mark_delivered(self.rid)
        return self._result

    # called by the server, under its lock
    def _set(self, result: Optional[FitResult],
             error: Optional[BaseException] = None) -> None:
        self._result, self._error = result, error
        self._event.set()


class DecsvmFitServer(FifoEngine):
    """Batched, optionally asynchronous fit server.

    Synchronous use::

        srv = DecsvmFitServer()
        h = srv.submit(FitRequest(rid=0, ...))
        done = srv.run()        # drains the queue bucket-by-bucket

    Asynchronous use::

        srv.start()             # background worker resolves buckets
        h = srv.submit(...)     # returns immediately
        res = h.result()        # blocks until this request resolves
        srv.stop()

    ``max_batch`` caps how many same-key requests co-batch into one
    program execution.  ``bucket_log`` records (key, size) per executed
    bucket — buckets never mix shapes/configs by construction of the key.
    """

    def __init__(self, max_batch: int = 16) -> None:
        super().__init__()
        self.max_batch = max_batch
        # rolling (key, size) of recent buckets; bounded so a long-lived
        # server's scheduling telemetry cannot grow with total traffic
        self.bucket_log: deque = deque(maxlen=256)
        # rid -> (request, handle, bucket key, resolved lambda grid)
        self._reqs: Dict[int, Tuple[FitRequest, FitHandle, tuple,
                                    np.ndarray]] = {}
        self._completed: Dict[int, FitResult] = {}
        # bucket failures awaiting a run() drain; bounded — every failure
        # is also delivered to its handles at completion time
        self._errors: deque = deque(maxlen=16)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._inflight: set = set()          # rids popped into a running bucket
        self._last_bucket = 0
        self._worker: Optional[threading.Thread] = None
        self._stop = False

    # -- public API ---------------------------------------------------------

    def submit(self, req: FitRequest) -> FitHandle:
        """Enqueue; returns a ``FitHandle`` future.  Raises ``ValueError``
        if ``req.rid`` is already pending, in flight, or
        completed-but-undelivered (the old server silently overwrote the
        earlier result).  The request object is not mutated: a
        ``lams=None`` grid is resolved into the server's own record."""
        from repro.core import sanitize
        sanitize.reject_unsupported(req.cfg, "DecsvmFitServer.submit")
        lams = (tuning.lambda_grid(np.asarray(req.X), np.asarray(req.y),
                                   num=req.num)
                if req.lams is None else np.asarray(req.lams))
        key = self._bucket_key(req, lams)
        handle = FitHandle(req.rid, self)
        with self._cv:
            if (req.rid in self._reqs or req.rid in self._inflight
                    or req.rid in self._completed):
                raise ValueError(
                    f"duplicate fit request rid={req.rid}: still pending or "
                    f"undelivered (drain with run() / handle.result() first)")
            self._reqs[req.rid] = (req, handle, key, lams)
            self.queue.append(req.rid)
            self._cv.notify_all()
        return handle

    def run(self) -> Dict[int, FitResult]:
        """Drain the queue and return the results completed since the last
        drain, removing them from the server (bounded memory for
        long-lived servers; each result is returned by ``run()`` at most
        once — ``FitHandle``s keep their own reference).  If any bucket
        failed since the last drain, the first failure is re-raised here
        (after the queue drains; the affected handles carry the same
        exception, and buffered results stay for the next ``run()``)."""
        while True:
            if self._worker is None:
                while self.step():
                    pass
            with self._cv:
                if self.queue or self._inflight:
                    if self._worker is None and self.queue:
                        # a concurrent submit() landed after our step loop
                        # drained: resolve it inline rather than waiting
                        # on a worker that doesn't exist
                        continue
                    # a worker (or another thread's inline step) owns the
                    # in-flight bucket: sleep until its completion notify
                    self._cv.wait()
                    continue
                if self._errors:
                    err = self._errors.popleft()
                    self._errors.clear()
                    raise err
                out, self._completed = self._completed, {}
                return out

    def step(self) -> int:
        """Resolve ONE bucket: pop up to ``max_batch`` queued requests
        sharing the queue head's bucket key and run them through the
        problem-batched path program.  Returns the bucket size (0 if the
        queue was empty).  A bucket failure is recorded (re-raised by
        ``run()``) and delivered to the affected handles, not raised
        here, so one poisoned bucket cannot wedge the worker loop."""
        with self._cv:
            batch = self._pop_bucket_locked()
        if not batch:
            return 0
        try:
            results = self._run_bucket([req for req, _, _, _ in batch],
                                       batch[0][3])
            error = None
        except Exception as e:              # deliver failure to every handle
            results, error = None, e
        with self._cv:
            for i, (req, handle, _, _) in enumerate(batch):
                if error is None:
                    self._completed[req.rid] = results[i]
                    handle._set(results[i])
                else:
                    handle._set(None, error)
                self._inflight.discard(req.rid)
            if error is not None:
                self._errors.append(error)
            self._cv.notify_all()
        return len(batch)

    def start(self) -> None:
        """Spawn the background worker (async mode): queued buckets
        resolve off-thread and handles unblock as they complete."""
        if self._worker is not None:
            return
        self._stop = False
        self._worker = threading.Thread(target=self._worker_loop,
                                        name="decsvm-fit-worker",
                                        daemon=True)
        self._worker.start()

    def stop(self) -> None:
        """Stop the worker after the queue drains."""
        if self._worker is None:
            return
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._worker.join()
        self._worker = None

    @property
    def utilization(self) -> float:
        """Batch-slot occupancy of the most recent bucket while work is
        queued or in flight; 0.0 once the server is idle."""
        with self._lock:
            if not self.queue and not self._inflight:
                return 0.0
            return self._last_bucket / self.max_batch

    # -- scheduling internals ------------------------------------------------

    @staticmethod
    def _w_shape(W) -> tuple:
        """Adjacency shape without densifying: a ``BlockTopology`` keys by
        its (m, m) logical shape, never materialized."""
        if hasattr(W, "neighbors"):
            return (W.m, W.m)
        return np.asarray(W).shape

    @staticmethod
    def _resolve_engine(req: FitRequest) -> str:
        """"auto" -> "chunked" iff the network is larger than the device
        count (dense request-batching cannot shard such a problem);
        explicit "dense"/"chunked" pass through."""
        if req.engine != "auto":
            if req.engine not in ("dense", "chunked"):
                raise ValueError(f"engine {req.engine!r} not in "
                                 f"('auto', 'dense', 'chunked')")
            return req.engine
        m = DecsvmFitServer._w_shape(req.W)[0]
        return "chunked" if m > len(jax.devices()) else "dense"

    @staticmethod
    def _bucket_key(req: FitRequest, lams: np.ndarray) -> tuple:
        return (np.asarray(req.X).shape,
                DecsvmFitServer._w_shape(req.W), req.cfg,
                tuple(float(l) for l in np.asarray(lams).ravel()),
                req.mode, req.criterion, req.cv_folds, req.cv_seed,
                req.penalty, req.threshold, req.tol, req.stop_rule,
                req.check_every, DecsvmFitServer._resolve_engine(req))

    def _pop_bucket_locked(self) -> List[Tuple[FitRequest, FitHandle,
                                               tuple, np.ndarray]]:
        if not self.queue:
            return []
        key = self._reqs[self.queue[0]][2]      # computed once, at submit
        rids = [r for r in self.queue if self._reqs[r][2] == key]
        rids = rids[:self.max_batch]
        taken = set(rids)
        self.queue = type(self.queue)(r for r in self.queue
                                      if r not in taken)
        batch = [self._reqs.pop(r) for r in rids]
        self._inflight |= taken
        self._last_bucket = len(batch)
        self.bucket_log.append((key, len(batch)))
        return batch

    def _worker_loop(self) -> None:
        while True:
            with self._cv:
                while not self.queue and not self._stop:
                    self._cv.wait()
                if self._stop and not self.queue:
                    return
            self.step()         # bucket failures are recorded, not raised

    def _drive(self, handle: FitHandle, timeout: Optional[float]) -> None:
        """Resolve buckets inline until ``handle`` is done (sync mode);
        with a worker running, just let ``result()`` wait on the event.
        The deadline is honoured at bucket granularity: a bucket already
        started cannot be preempted, so one oversized bucket can still
        overshoot ``timeout`` — but no *new* bucket starts past it."""
        if self._worker is not None:
            return
        deadline = None if timeout is None else time.monotonic() + timeout
        while not handle.done():
            if deadline is not None and time.monotonic() >= deadline:
                break                   # result() raises TimeoutError
            if self.step() == 0:
                break                   # rid not queued here; wait/timeout

    def _mark_delivered(self, rid: int) -> None:
        with self._cv:
            self._completed.pop(rid, None)

    # -- bucket execution ----------------------------------------------------

    def _run_bucket(self, reqs: List[FitRequest],
                    lams: np.ndarray) -> List[FitResult]:
        if self._resolve_engine(reqs[0]) == "chunked":
            return self._run_bucket_chunked(reqs, lams)
        t0 = time.perf_counter()
        r0 = reqs[0]
        # stack host-side once; the jitted entry points move it on-device,
        # and the margins einsum below reuses this same host copy
        Xs = np.stack([np.asarray(r.X, np.float32) for r in reqs])
        ys = np.stack([np.asarray(r.y, np.float32) for r in reqs])
        Ws = np.stack([np.asarray(r.W, np.float32) for r in reqs])
        best_lams, best_Bs, tables, res = tuning.select_lambda_path_many(
            Xs, ys, Ws, r0.cfg, lams=lams, mode=r0.mode,
            tol=r0.tol, criterion=r0.criterion, cv_folds=r0.cv_folds,
            cv_seed=r0.cv_seed, stop_rule=r0.stop_rule,
            check_every=r0.check_every)
        lam_weights = None
        best_Bj = jnp.asarray(best_Bs)
        best_lj = jnp.asarray(best_lams, np.float32)
        if r0.penalty is not None:
            # One-step LLA stage 2, whole bucket at once: the batched path
            # result *is* the stage-1 pilot at each problem's best_lam, so
            # only the weighted re-fit runs — vmapped, with per-problem
            # (lambda, weights) traced (no per-lambda recompiles).
            from repro.core import penalties  # local import: keep serving light
            from repro.core.path import decsvm_fit_many
            pilots = jnp.mean(best_Bj, axis=1)              # (B, p)
            wfun = penalties.PENALTIES[r0.penalty]
            ws = jax.vmap(wfun)(pilots, best_lj)            # (B, p)
            best_Bj = decsvm_fit_many(Xs, ys, Ws, best_lj, r0.cfg,
                                      lam_weights=ws)
            lam_weights = np.asarray(ws)
        if r0.threshold:
            # Theorem-4 hard thresholding at each problem's selected lambda
            best_Bj = jax.vmap(hard_threshold_final)(best_Bj, best_lj)
        best_B = np.asarray(best_Bj)                        # one transfer
        margins = np.einsum("bmnp,bmp->bmn", Xs, best_B)
        wall = time.perf_counter() - t0
        out = []
        for i, req in enumerate(reqs):
            out.append(FitResult(
                rid=req.rid, best_lam=float(best_lams[i]), B=best_B[i],
                beta=best_B[i].mean(axis=0), table=tables[i],
                criterion=req.criterion,
                lam_weights=(None if lam_weights is None else lam_weights[i]),
                train_accuracy=metrics.margin_accuracy(margins[i], ys[i]),
                consensus_gap=metrics.consensus_gap(best_B[i]),
                wall_s=wall, batch_size=len(reqs)))
        return out

    def _run_bucket_chunked(self, reqs: List[FitRequest],
                            lams: np.ndarray) -> List[FitResult]:
        """Chunked (m > devices) bucket executor.  One problem already
        occupies every device through the node-chunk mesh, so requests
        resolve sequentially — but the whole bucket shares ONE compiled
        program (same shapes/config/grid by bucket-key construction, so
        the lru-cached chunked builders hit after the first request)."""
        from repro.core import decentral   # local import: keep serving light

        t0 = time.perf_counter()
        r0 = reqs[0]
        best_lams, best_Bs, tables = [], [], []
        for req in reqs:
            bl, bB, table, _ = tuning.select_lambda_path(
                np.asarray(req.X, np.float32), np.asarray(req.y, np.float32),
                req.W, r0.cfg, lams=lams, mode=r0.mode, tol=r0.tol,
                criterion=r0.criterion, cv_folds=r0.cv_folds,
                cv_seed=r0.cv_seed, stop_rule=r0.stop_rule,
                engine="mesh")
            best_lams.append(bl)
            best_Bs.append(bB)
            tables.append(table)
        lam_weights = None
        if r0.penalty is not None:
            # One-step LLA stage 2: the chunked path engine traces lambda
            # AND the weight vector, so the single-point re-fit grid below
            # reuses one executable across the bucket and across lambdas.
            from repro.core import penalties  # local import: keep serving light
            wfun = penalties.PENALTIES[r0.penalty]
            ws_list, refits = [], []
            for req, bl, bB in zip(reqs, best_lams, best_Bs):
                ws = wfun(jnp.asarray(bB).mean(axis=0), jnp.float32(bl))
                path = decentral.decsvm_path_chunked(
                    jnp.asarray(req.X, jnp.float32),
                    jnp.asarray(req.y, jnp.float32), req.W,
                    np.asarray([bl], np.float32), r0.cfg, lam_weights=ws)
                refits.append(np.asarray(path[0]))
                ws_list.append(np.asarray(ws))
            best_Bs, lam_weights = refits, ws_list
        if r0.threshold:
            best_Bs = [np.asarray(hard_threshold_final(jnp.asarray(bB),
                                                       jnp.float32(bl)))
                       for bB, bl in zip(best_Bs, best_lams)]
        wall = time.perf_counter() - t0
        out = []
        for i, req in enumerate(reqs):
            Bi = np.asarray(best_Bs[i])
            yi = np.asarray(req.y, np.float32)
            margins = np.einsum("mnp,mp->mn", np.asarray(req.X, np.float32),
                                Bi)
            out.append(FitResult(
                rid=req.rid, best_lam=float(best_lams[i]), B=Bi,
                beta=Bi.mean(axis=0), table=tables[i],
                criterion=req.criterion,
                lam_weights=(None if lam_weights is None
                             else lam_weights[i]),
                train_accuracy=metrics.margin_accuracy(margins, yi),
                consensus_gap=metrics.consensus_gap(Bi),
                wall_s=wall, batch_size=len(reqs)))
        return out
