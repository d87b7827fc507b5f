"""Bring-up smoke test: the deCSVM main path on a TPU, through the entry
points a user calls, each result checked against a reference.

    python3 chip_smoke.py              # phases a-d on one chip
    python3 chip_smoke.py --chips 4    # the multi-chip engines only

One chip (the default):

  a  The paper's Section 4.1 design at high dimension (m=10, n=200,
     p=500, s=10, Erdos-Renyi p=0.5, seed 0): ``decsvm_fit`` on every
     backend (jnp, pallas, megakernel, megakernel_bf16), each against the
     dense jnp fit run at "highest" matmul precision; estimation error and
     F1 against beta*.
  b  Tuned path: ``tuning.select_lambda_path(num=12, mode="warm",
     tol=1e-3)`` on the same data, against the same call at "highest".
  c  Fit server: ``serving.DecsvmFitServer(max_batch=8)`` answers 8
     same-shape requests on one shared grid (one chip holds fewer devices
     than nodes, so they run on the chunked engine); each result against
     a serial ``select_lambda_path`` of the same request at "highest".
  d  Scale: ``decsvm_fit`` at m=16, n=2000, p=10000 (X is 1.28 GB fp32,
     made on the device), jnp and pallas, 50 rounds each.

Four chips (``--chips 4``): ``decsvm_fit_chunked`` and
``decsvm_path_mesh(mode="warm")`` on a 4-device mesh at
m=64, n=500, p=2000, each against the dense single-device fit and path.

Every phase prints one line: name, shape, backend, wall seconds of a
warm run (after ``block_until_ready``), and its error against the
reference.  A phase that names a kernel backend must find a Pallas kernel
(``tpu_custom_call``) in its compiled HLO.  The last line of stdout is the
JSON verdict, printed only when every phase passed.  Exit codes: 0 pass,
1 a phase failed, 2 no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.core import (ADMMConfig, SimConfig, decentral,  # noqa: E402
                        decsvm_fit, generate, losses, metrics, tuning)
from repro.core.graph import erdos_renyi                    # noqa: E402
from repro.core.path import decsvm_path_warm                # noqa: E402
from repro.serving import DecsvmFitServer, FitRequest       # noqa: E402

# Max |B - B_ref| against the fit at "highest" matmul precision, by backend.
# jnp and the two-pass kernel pin their f32 dots at HIGHEST.  The fused
# kernels' X dots run at Mosaic's default, which on v5e is one bf16 pass for
# f32 operands too (2.3e-3 relative error per dot), so the fp32 megakernel
# is as accurate as the bf16 one: 1.1e-4 after 300 rounds in phase a.  The
# bf16 bound is the one tests/test_solver.py holds that mode to.
BOUNDS = {"jnp": 1e-5, "pallas": 1e-5, "megakernel": 1e-3,
          "megakernel_bf16": 1e-2}
BACKENDS = ("jnp", "pallas", "megakernel", "megakernel_bf16")
KERNEL_BACKENDS = ("pallas", "megakernel", "megakernel_bf16")


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def report(phase: str, shape, backend: str, wall_s: float, err: float,
           **extra) -> None:
    fields = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[{phase}] shape={tuple(shape)} backend={backend} "
          f"wall_s={wall_s!r} err={err!r} {fields}".rstrip(), flush=True)


def timed(fn, *args):
    """(result, wall seconds) of one call, after block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def highest():
    return jax.default_matmul_precision("highest")


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def compiled_fit(cfg, X, y, W):
    """``decsvm_fit`` compiled for these operands, and whether its HLO
    holds a Pallas kernel."""
    t0 = time.perf_counter()
    comp = jax.jit(lambda X, y, W: decsvm_fit(X, y, W, cfg)).lower(
        X, y, W).compile()
    return comp, "tpu_custom_call" in comp.as_text(), time.perf_counter() - t0


def fit_backend(phase, cfg, backend, X, y, W, ref, bound, shape, **extra):
    """Fit on one backend; return (B, the reasons it failed)."""
    cfg_b = dataclasses.replace(cfg, backend=backend)
    comp, has_kernel, compile_s = compiled_fit(cfg_b, X, y, W)
    timed(comp, X, y, W)                              # first run
    B, wall = timed(comp, X, y, W)
    err = max_err(B, ref)
    report(phase, shape, backend, wall, err, compile_s=round(compile_s, 3),
           kernel=has_kernel, **{k: f(B) for k, f in extra.items()})
    bad = []
    if backend in KERNEL_BACKENDS and not has_kernel:
        bad.append(f"{phase}/{backend}: no tpu_custom_call in HLO")
    if not all_finite(B):
        bad.append(f"{phase}/{backend}: non-finite B")
    if not err <= bound:
        bad.append(f"{phase}/{backend}: err {err} > bound {bound}")
    return B, bad


def all_finite(B) -> bool:
    return bool(np.all(np.isfinite(np.asarray(B))))


# -- the paper's design ------------------------------------------------------


def paper_problem(m=10, n=200, p=500, s=10, seed=0):
    sim = SimConfig(m=m, n=n, p=p, s=s)
    X, y, bstar = generate(sim, seed=seed)
    W = erdos_renyi(m, sim.p_connect, seed=seed)
    h = losses.default_bandwidth(sim.n_total, p)
    lam = 1.2 * math.sqrt(math.log(p) / sim.n_total)
    cfg = ADMMConfig(lam=lam, h=h, kernel="epanechnikov", max_iter=300)
    return (jnp.asarray(X), jnp.asarray(y), jnp.asarray(W, jnp.float32),
            np.asarray(W), bstar, cfg)


def phase_a():
    X, y, W, _, bstar, cfg = paper_problem()
    with highest():
        ref, wall = timed(lambda: decsvm_fit(
            X, y, W, dataclasses.replace(cfg, backend="jnp")))
    report("a", X.shape, "jnp@highest", wall, 0.0, role="reference",
           est_err=round(metrics.estimation_error(ref, bstar), 5),
           f1=round(metrics.mean_f1(ref, bstar, tol=1e-3), 4))
    bad = []
    for backend in BACKENDS:
        bad += fit_backend(
            "a", cfg, backend, X, y, W, ref, BOUNDS[backend], X.shape,
            est_err=lambda B: round(metrics.estimation_error(B, bstar), 5),
            f1=lambda B: round(metrics.mean_f1(B, bstar, tol=1e-3), 4))[1]
    check(not bad, "; ".join(bad))


def phase_b():
    X, y, W, _, bstar, cfg = paper_problem()
    run = lambda: tuning.select_lambda_path(X, y, W, cfg, num=12,
                                            mode="warm", tol=1e-3)
    with highest():
        ref = run()
    run()                                             # compile + first run
    (best_lam, best_B, _, res), wall = timed(run)
    err = max_err(res.path, ref[3].path)
    iters = np.asarray(res.iters).tolist()
    report("b", X.shape, "jnp", wall, err, lam=best_lam,
           ref_lam=ref[0], iters=iters,
           f1=round(metrics.mean_f1(best_B, bstar, tol=1e-3), 4))
    check(all_finite(res.path), "b: non-finite path")
    check(max(iters) <= cfg.max_iter, "b: iterations over max_iter")
    check(err <= 10 * BOUNDS["jnp"], f"b: path err {err}")


def phase_c(n_req=8):
    X0, _, _, _, _, cfg = paper_problem()
    probs = [paper_problem(seed=100 + i) for i in range(n_req)]
    lams = tuning.shared_lambda_grid(np.stack([np.asarray(p[0])
                                               for p in probs]),
                                     np.stack([np.asarray(p[1])
                                               for p in probs]), num=8)
    cfg = dataclasses.replace(cfg, lam=0.0)

    def serve():
        srv = DecsvmFitServer(max_batch=n_req)
        for i, (X, y, _, Wn, _, _) in enumerate(probs):
            srv.submit(FitRequest(rid=i, X=np.asarray(X), y=np.asarray(y),
                                  W=Wn, cfg=cfg, lams=lams, mode="warm",
                                  tol=1e-3))
        return srv, srv.run()

    serve()                                           # compile + first run
    (srv, done), wall = timed(serve)
    engines = [key[-1] for key, _ in srv.bucket_log]
    check(sorted(done) == list(range(n_req)), "c: missing results")
    check([size for _, size in srv.bucket_log] == [n_req],
          f"c: buckets {list(srv.bucket_log)}")
    errs, lam_ok = [], True
    for i, (X, y, W, _, _, _) in enumerate(probs):
        with highest():
            bl, bB, _, _ = tuning.select_lambda_path(
                X, y, W, cfg, lams=lams, mode="warm", tol=1e-3)
        errs.append(max_err(done[i].B, bB))
        lam_ok &= abs(done[i].best_lam - bl) <= 1e-6 * bl
    report("c", (n_req,) + tuple(X0.shape), "jnp", wall, max(errs),
           engine=engines[0], fits_per_s=round(n_req / wall, 3),
           lams_match=lam_ok)
    check(lam_ok, "c: selected lambda differs from the serial fit")
    check(max(errs) <= 10 * BOUNDS["jnp"], f"c: err {max(errs)}")


def device_design(key, m, n, p, s=10, mu=0.4, rho=0.5, p_flip=0.01):
    """The Section 4.1 design made on the device: the AR(rho) blocks come
    from the AR(1) recursion, whose covariance is exactly rho^|i-j|.
    Returns X (m, n, p+1) with the intercept column, and y (m, n)."""
    N = m * n
    ky, kz, kf = jax.random.split(key, 3)
    y = jnp.where(jax.random.bernoulli(ky, 0.5, (N,)), 1.0, -1.0)
    Z = jax.random.normal(kz, (p, N), jnp.float32)
    c = math.sqrt(1.0 - rho * rho)

    def ar(Zb):
        def step(prev, z):
            x = rho * prev + c * z
            return x, x
        _, rest = jax.lax.scan(step, Zb[0], Zb[1:])
        return jnp.concatenate([Zb[:1], rest])

    Xf = jnp.concatenate([ar(Z[:s]), ar(Z[s:])])               # (p, N)
    shift = jnp.where(jnp.arange(p) < s, mu, 0.0)
    X = Xf.T + y[:, None] * shift[None, :]
    y = jnp.where(jax.random.uniform(kf, (N,)) < p_flip, -y, y)
    X = jnp.concatenate([jnp.ones((N, 1), jnp.float32), X], axis=1)
    return X.reshape(m, n, p + 1), y.reshape(m, n)


def phase_d(m=16, n=2000, p=10000, rounds=50):
    X, y = jax.jit(device_design, static_argnums=(1, 2, 3))(
        jax.random.PRNGKey(0), m, n, p)
    W = jnp.asarray(erdos_renyi(m, 0.5, seed=0), jnp.float32)
    N = m * n
    cfg = ADMMConfig(lam=1.2 * math.sqrt(math.log(p) / N),
                     h=losses.default_bandwidth(N, p), max_iter=rounds)
    with highest():
        ref, wall = timed(lambda: decsvm_fit(
            X, y, W, dataclasses.replace(cfg, backend="jnp")))
    report("d", X.shape, "jnp@highest", wall, 0.0, role="reference",
           x_bytes=X.size * X.dtype.itemsize)
    out, bad = {}, []
    for b in ("jnp", "pallas"):
        out[b], why = fit_backend("d", cfg, b, X, y, W, ref, BOUNDS[b],
                                  X.shape)
        bad += why
    gap = max_err(out["jnp"], out["pallas"])
    print(f"[d] jnp-vs-pallas max|dB|={gap!r}", flush=True)
    check(not bad, "; ".join(bad))
    check(gap <= BOUNDS["jnp"] + BOUNDS["pallas"], f"d: jnp/pallas {gap}")


# -- four chips ----------------------------------------------------------------


def device_peaks():
    return [d.memory_stats().get("peak_bytes_in_use", 0)
            for d in jax.devices()]


def phase_mesh(m=64, n=500, p=2000, fit_rounds=100, tol=1e-4):
    """The chunked fit and the chunked warm path over every device,
    against the dense single-device fit and warm path."""
    X, y, W, Wn, _, cfg = paper_problem(m=m, n=n, p=p)
    ndev = len(jax.devices())
    x_share = X.size * X.dtype.itemsize / ndev
    cfg = dataclasses.replace(cfg, max_iter=fit_rounds)
    lams = tuning.lambda_grid(np.asarray(X), np.asarray(y), num=8)

    fit = lambda: decentral.decsvm_fit_chunked(X, y, Wn, cfg)
    fit()
    B, wall = timed(fit)
    peaks = device_peaks()
    devs = sorted(d.id for d in B.sharding.device_set)
    with highest():
        ref = decsvm_fit(X, y, W, cfg)
    err = max_err(B, ref)
    report("mesh", X.shape, "chunked-fit", wall, err, devices=devs,
           peak_bytes=peaks)
    check(len(devs) == ndev, f"mesh: fit output on devices {devs}")
    check(min(peaks) >= 0.9 * x_share,
          f"mesh: a device never held its X chunk: {peaks}")
    check(err <= BOUNDS["jnp"], f"mesh: chunked fit err {err}")

    pcfg = dataclasses.replace(cfg, lam=0.0, max_iter=300)
    path = lambda: decentral.decsvm_path_mesh(
        X, y, Wn, lams, pcfg, mode="warm", tol=tol)
    path()
    res, wall = timed(path)
    devs = sorted(d.id for d in res.path.sharding.device_set)
    with highest():
        ref_path, _ = decsvm_path_warm(X, y, W, jnp.asarray(lams), pcfg,
                                       tol=tol)
    err = max_err(res.path, ref_path)
    report("mesh", X.shape, "chunked-warm-path", wall, err, devices=devs,
           iters=np.asarray(res.iters).tolist(), peak_bytes=device_peaks())
    check(len(devs) == ndev, f"mesh: path output on devices {devs}")
    check(err <= 10 * tol, f"mesh: warm path err {err}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip engines")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on the host", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    from benchmarks.common import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    phases = ({"mesh": phase_mesh} if args.chips == 4 else
              {"a": phase_a, "b": phase_b, "c": phase_c, "d": phase_d})
    failed = []
    for name, fn in phases.items():
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:                   # recorded as a failure, never a pass
            traceback.print_exc()
            failed.append(name)
        print(f"[{name}] phase_s={time.perf_counter() - t0!r}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
