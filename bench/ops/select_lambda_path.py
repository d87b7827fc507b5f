"""Request: a tuned lambda path with BIC selection on one data set, as a user
calls it: ``tuning.select_lambda_path`` on the dense engine with ``num``
grid points, which it builds itself from the data, warm continuation with
the KKT stop.

Answer: the selected lambda, the solutions along the whole path, and the
rounds run at each grid point.  Counter: rounds summed over the path.
``check`` holds an answer to ``bench/reference.py``; ``control`` puts that
reference, computed one precision lower, in the program's place.
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from bench import design, reference


def build(cfg: dict, traffic: dict, W: np.ndarray):
    import jax.numpy as jnp
    from repro.core import ADMMConfig, tuning

    acfg = ADMMConfig(lam=0.0, tau=cfg["tau"], h=design.bandwidth(cfg),
                      kernel=cfg["kernel"], max_iter=cfg["max_iter"])
    Wd = jnp.asarray(W)

    def request(X, y):
        with TraceAnnotation("bench:select_lambda_path"):
            best_lam, _, _, res = tuning.select_lambda_path(
                X, y, Wd, acfg, num=traffic["num"], mode=traffic["mode"],
                tol=cfg["tol"], criterion=traffic["criterion"],
                stop_rule=cfg["stop_rule"], check_every=cfg["check_every"])
        with TraceAnnotation("bench:fetch"):
            iters = np.asarray(res.iters)
            answer = {"best_lam": float(best_lam),
                      "path": np.asarray(res.path), "iters": iters}
        return answer, {"rounds": int(iters.sum())}

    return request


def control(cfg: dict, traffic: dict, W: np.ndarray, X, y) -> dict:
    """The reference at "bf16x3", making its own stop decisions, in the
    program's place: an answer of the same form."""
    import jax.numpy as jnp

    lams = reference.lambda_grid(np.asarray(X), np.asarray(y),
                                 traffic["num"])
    path, rounds, _, i = reference.warm_path(
        X, y, jnp.asarray(W), jnp.asarray(lams, jnp.float32), cfg["tol"],
        h=design.bandwidth(cfg), kernel=cfg["kernel"], tau=cfg["tau"],
        max_iter=cfg["max_iter"], check_every=cfg["check_every"],
        precision="bf16x3")
    return {"best_lam": float(lams[int(i)]), "path": np.asarray(path),
            "iters": np.asarray(rounds)}


def check(cfg: dict, traffic: dict, W: np.ndarray, X, y,
          answer: dict) -> dict:
    """One answer against the reference run for the rounds it reports.

    ``path_gap``: the largest |B - B_ref| anywhere on the path.
    ``lam_mismatch``: 1 where the selected lambda is another grid point
    than the reference's BIC selection, else 0.
    ``stop_kkt_ratio``: the largest KKT residual of the answer's estimates
    over the tolerance, at the grid points where it stopped before the
    round cap (0 where it stopped at none)."""
    import jax.numpy as jnp

    lams = reference.lambda_grid(np.asarray(X), np.asarray(y),
                                 traffic["num"])
    iters = np.asarray(answer["iters"], np.int32)
    path, i, res = reference.path_following(
        X, y, jnp.asarray(W), jnp.asarray(lams, jnp.float32),
        jnp.asarray(iters), jnp.asarray(answer["path"], jnp.float32),
        h=design.bandwidth(cfg), kernel=cfg["kernel"], tau=cfg["tau"])
    d = np.abs(answer["path"].astype(np.float64) - np.asarray(path))
    early = iters < cfg["max_iter"]
    res = np.asarray(res, np.float64)
    return {"path_gap": float(np.max(d)) if np.all(np.isfinite(d))
            else float("inf"),
            "lam_mismatch": int(abs(answer["best_lam"] - lams[int(i)])
                                > 1e-4 * lams[int(i)]),
            "stop_kkt_ratio": float(np.max(res[early], initial=0.0)
                                    / cfg["tol"])}


def combine(numbers) -> dict:
    """The numbers of a run: answers with another selection are counted,
    the rest is the worst answer's."""
    return {"path_gap": max(n["path_gap"] for n in numbers),
            "lam_mismatch": sum(n["lam_mismatch"] for n in numbers),
            "stop_kkt_ratio": max(n["stop_kkt_ratio"] for n in numbers)}
