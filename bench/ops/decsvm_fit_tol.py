"""Request: one fit at the configuration's lambda on one data set, as a
user calls it: ``decsvm_fit_tol`` with the KKT stop and its round cap.

Answer: the node estimates B and the rounds run.  Counter: the rounds.
``check`` holds an answer to ``bench/reference.py``; ``control`` puts that
reference, computed one precision lower, in the program's place.
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from bench import design, reference


def build(cfg: dict, traffic: dict, W: np.ndarray):
    import jax.numpy as jnp
    from repro.core import ADMMConfig, decsvm_fit_tol

    acfg = ADMMConfig(lam=design.theory_lambda(cfg), tau=cfg["tau"],
                      h=design.bandwidth(cfg), kernel=cfg["kernel"],
                      max_iter=cfg["max_iter"])
    Wd = jnp.asarray(W)

    def request(X, y):
        with TraceAnnotation("bench:decsvm_fit_tol"):
            B, t = decsvm_fit_tol(X, y, Wd, acfg, tol=cfg["tol"],
                                  stop_rule=cfg["stop_rule"],
                                  check_every=cfg["check_every"])
        with TraceAnnotation("bench:fetch"):
            answer = {"B": np.asarray(B), "rounds": int(t)}
        return answer, {"rounds": answer["rounds"]}

    return request


def control(cfg: dict, traffic: dict, W: np.ndarray, X, y) -> dict:
    """The reference at "bf16x3", making its own stop decision, in the
    program's place: an answer of the same form."""
    import jax.numpy as jnp

    B, t = reference.fit(
        X, y, jnp.asarray(W), design.theory_lambda(cfg), cfg["tol"],
        h=design.bandwidth(cfg), kernel=cfg["kernel"], tau=cfg["tau"],
        max_iter=cfg["max_iter"], check_every=cfg["check_every"],
        precision="bf16x3")
    return {"B": np.asarray(B), "rounds": int(t)}


def check(cfg: dict, traffic: dict, W: np.ndarray, X, y,
          answer: dict) -> dict:
    """One answer against the reference run for the rounds it reports.

    ``fit_gap``: the largest |B - B_ref|.  ``stop_kkt_ratio``: the KKT
    residual of the answer's B over the tolerance where it stopped before
    the round cap, else 0."""
    import jax.numpy as jnp

    B, res = reference.fit_following(
        X, y, jnp.asarray(W), design.theory_lambda(cfg),
        jnp.int32(answer["rounds"]), jnp.asarray(answer["B"], jnp.float32),
        h=design.bandwidth(cfg), kernel=cfg["kernel"], tau=cfg["tau"])
    d = np.abs(answer["B"].astype(np.float64) - np.asarray(B))
    early = answer["rounds"] < cfg["max_iter"]
    return {"fit_gap": float(np.max(d)) if np.all(np.isfinite(d))
            else float("inf"),
            "stop_kkt_ratio": float(res) / cfg["tol"] if early else 0.0}


def combine(numbers) -> dict:
    """The numbers of a run: the worst answer's."""
    return {k: max(n[k] for n in numbers) for k in numbers[0]}
