"""Batched vs serial fit serving, 16 same-shape requests on one device.

  - serial  : ``DecsvmFitServer(max_batch=1)`` — every request resolves
              through its own path-program execution (the PR-4 behavior:
              one compiled program, 16 sequential runs)
  - batched : ``DecsvmFitServer(max_batch=16)`` — the scheduler buckets
              the whole queue into ONE problem-batched program
              (``path.decsvm_path_select_many``): all 16 fits, their BIC
              scoring, and each argmin in a single vmapped execution

Emits ``BENCH_fit_serving.json`` at the repo root with the same field
conventions as ``BENCH_lambda_path.json`` (end-to-end = compile + run,
steady-state = post-compile min over reps).  Headline criteria: batched
steady-state >= 3x serial on the 16-request queue, with batched-vs-serial
max abs deviation <= 1e-5.

    PYTHONPATH=src python benchmarks/bench_fit_serving.py
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import ADMMConfig, SimConfig, generate, tuning
from repro.core.graph import erdos_renyi
from repro.serving import DecsvmFitServer, FitRequest

M, N, P, GRID, MAX_ITER, NREQ = 4, 80, 24, 8, 200, 16
MODE = "warm"           # the server default: continuation + KKT early stop
#   (vmapped while_loop freezes converged problems, so batched warm results
#    match per-request serial warm results exactly)
STEADY_REPS = 3
OUT = Path(__file__).resolve().parent.parent / "BENCH_fit_serving.json"


def make_requests(probs, lams, acfg):
    return [FitRequest(rid=i, X=X, y=y, W=W, cfg=acfg, lams=lams, mode=MODE)
            for i, (X, y, W) in enumerate(probs)]


def drain(max_batch, probs, lams, acfg):
    srv = DecsvmFitServer(max_batch=max_batch)
    for req in make_requests(probs, lams, acfg):
        srv.submit(req)
    t0 = time.perf_counter()
    done = srv.run()
    return done, time.perf_counter() - t0, [s for _, s in srv.bucket_log]


def run() -> dict:
    cfg = SimConfig(p=P, s=5, m=M, n=N, rho=0.5)
    probs = []
    for s in range(NREQ):
        X, y, _ = generate(cfg, seed=s)
        W = erdos_renyi(cfg.m, cfg.p_connect, seed=s)
        probs.append((X, y, W))
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    # one shared grid so the whole queue is a single bucket
    lams = tuning.shared_lambda_grid(
        np.stack([p[0] for p in probs]), np.stack([p[1] for p in probs]),
        num=GRID)

    # end-to-end first (includes compile), then post-compile steady state
    done_ser, ser_e2e, buckets_ser = drain(1, probs, lams, acfg)
    done_bat, bat_e2e, buckets_bat = drain(NREQ, probs, lams, acfg)
    assert buckets_ser == [1] * NREQ, buckets_ser
    assert buckets_bat == [NREQ], buckets_bat
    ser_ss = min(drain(1, probs, lams, acfg)[1] for _ in range(STEADY_REPS))
    bat_ss = min(drain(NREQ, probs, lams, acfg)[1]
                 for _ in range(STEADY_REPS))

    dev = max(float(np.max(np.abs(done_bat[i].B - done_ser[i].B)))
              for i in range(NREQ))
    lam_match = all(done_bat[i].best_lam == done_ser[i].best_lam
                    for i in range(NREQ))
    result = {
        "bench": "fit_serving",
        "config": {"m": M, "n": N, "p": P, "grid": GRID,
                   "max_iter": MAX_ITER, "requests": NREQ, "mode": MODE,
                   "backend": jax.devices()[0].platform,
                   "device_kind": jax.devices()[0].device_kind},
        "end_to_end_s": {"serial": ser_e2e, "batched": bat_e2e},
        "steady_state_s": {"serial": ser_ss, "batched": bat_ss},
        "throughput_fits_per_s": {"serial": NREQ / ser_ss,
                                  "batched": NREQ / bat_ss},
        "speedup_batched_vs_serial": ser_ss / bat_ss,
        "max_abs_dev_batched_vs_serial": dev,
        "criteria": {
            "batched_ge_3x_serial": ser_ss / bat_ss >= 3.0,
            "batched_matches_serial_1e-5": dev <= 1e-5 and lam_match,
        },
    }
    return result


def main() -> None:
    result = run()
    OUT.write_text(json.dumps(result, indent=2) + "\n")
    ss = result["steady_state_s"]
    thr = result["throughput_fits_per_s"]
    print(f"serial  {ss['serial']:7.3f}s  ({thr['serial']:6.2f} fits/s)")
    print(f"batched {ss['batched']:7.3f}s  ({thr['batched']:6.2f} fits/s, "
          f"{result['speedup_batched_vs_serial']:.2f}x, "
          f"dev {result['max_abs_dev_batched_vs_serial']:.2e})")
    print(f"criteria: {result['criteria']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
