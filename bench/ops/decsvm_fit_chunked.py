"""Request: one fit of a peer network at the configuration's lambda on one
node-sharded data set, as a user calls it: ``decentral.decsvm_fit_chunked``
with the KKT stop and its round cap, the graph as a ``graph.BlockTopology``
made once, and the chips those over which X is sharded (the engine takes
its mesh from ``X.sharding``).

Answer: the node estimates B and the rounds run.  Counter: the rounds.
``check`` holds an answer to ``bench/reference.py``; ``control`` puts that
reference, computed one precision lower, in the program's place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import design, reference

# Nodes of one group in ``control``'s products (see ``_control_fit``).
GROUP_NODES = 32


def build(cfg: dict, traffic: dict, W: np.ndarray):
    from repro.core import ADMMConfig, decentral, graph

    acfg = ADMMConfig(lam=design.theory_lambda(cfg), tau=cfg["tau"],
                      h=design.bandwidth(cfg), kernel=cfg["kernel"],
                      max_iter=cfg["max_iter"])
    top = graph.BlockTopology.from_dense(W)

    def request(X, y):
        with TraceAnnotation("bench:decsvm_fit_chunked"):
            B, t = decentral.decsvm_fit_chunked(
                X, y, top, acfg, tol=cfg["tol"], stop_rule=cfg["stop_rule"],
                check_every=cfg["check_every"])
        with TraceAnnotation("bench:fetch"):
            answer = {"B": np.asarray(B), "rounds": int(t)}
        return answer, {"rounds": answer["rounds"]}

    return request


def _group_nodes(m_local: int) -> int:
    return max(g for g in range(1, min(GROUP_NODES, m_local) + 1)
               if m_local % g == 0)


@functools.partial(jax.jit, static_argnames=(
    "h", "kernel", "tau", "max_iter", "check_every", "chips"))
def _control_fit(X, y, W, lam, tol, *, h, kernel, tau, max_iter,
                 check_every, chips):
    """``reference.fit`` at "bf16x3", its formulas unchanged, with each
    product over X taken a group of nodes at a time on every chip.  For
    ``reference.fit`` itself the compile for four v5e chips keeps six bf16
    parts of the whole X live at this size (12.4 GB of temporaries a chip
    beside the pool's 8.2 GB); here a group's parts are made inside the
    loop over groups.  Returns (B, rounds)."""
    prec = "bf16x3"
    m, n, p = X.shape
    per = _group_nodes(m // chips)
    groups = m // chips // per
    dloss, c_hh = reference.DLOSS[kernel]
    Xg = X.reshape(chips, groups, per, n, p)
    yg = y.reshape(chips, groups, per, n)

    def product(V, weight):
        """X_l' phi(X_l v_l) / n for every node l, as the reference's two
        dots, a group at a time."""
        Vg = V.reshape(chips, groups, per, p)

        def body(i, G):
            take = lambda a: jax.lax.dynamic_index_in_dim(a, i, 1, False)
            Xi, yi = take(Xg), take(yg)
            u = reference.dot("cgnp,cgp->cgn", Xi, take(Vg), prec)
            w = dloss(yi * u, h) * yi if weight == "loss" else u
            gi = reference.dot("cgnp,cgn->cgp", Xi, w, prec) / n
            return jax.lax.dynamic_update_index_in_dim(G, gi, i, 1)

        return jax.lax.fori_loop(0, groups, body,
                                 jnp.zeros_like(Vg)).reshape(m, p)

    # step_sizes: 50 power steps per node from the reference's start vector
    v0 = jax.random.normal(jax.random.PRNGKey(n * 1000003 + p), (p,),
                           jnp.float32)
    V = jnp.broadcast_to(v0 / jnp.linalg.norm(v0), (m, p))

    def power(V, _):
        Wv = product(V, "linear")
        nrm = jnp.linalg.norm(Wv, axis=1, keepdims=True)
        return jnp.where(nrm > 0.0, Wv / jnp.where(nrm > 0, nrm, 1.0),
                         V), None

    V, _ = jax.lax.scan(power, V, None, length=50)
    lmax = jnp.sum(V * product(V, "linear"), axis=1) / jnp.sum(V * V, axis=1)
    rho = 1.05 * (c_hh / h) * lmax
    deg = jnp.sum(W, axis=1)
    omega = 1.0 / (2.0 * tau * deg + rho)

    def admm_round(B, P):
        g = product(B, "loss")
        nb = reference.dot("lk,kp->lp", W, B, prec)
        z = rho[:, None] * B - g - P + tau * (deg[:, None] * B + nb)
        Bn = reference.soft(omega[:, None] * z, lam * omega[:, None])
        Pn = P + tau * (deg[:, None] * Bn
                        - reference.dot("lk,kp->lp", W, Bn, prec))
        return Bn, Pn

    def kkt(B):
        bb = jnp.mean(B, axis=0)
        g = jnp.mean(product(jnp.broadcast_to(bb, (m, p)), "loss"), axis=0)
        stat = jnp.max(jnp.abs(bb - reference.soft(bb - g, lam)))
        return jnp.maximum(stat, jnp.max(jnp.abs(B - bb[None])))

    def cond(c):
        _, _, t, stat = c
        return (t < max_iter) & (stat > tol)

    def body(c):
        B, P, t, _ = c
        for _ in range(check_every):
            Bn, Pn = admm_round(B, P)
            live = t < max_iter
            B = jnp.where(live, Bn, B)
            P = jnp.where(live, Pn, P)
            t = jnp.where(live, t + 1, t)
        return B, P, t, kkt(B)

    B0 = jnp.zeros((m, p), jnp.float32)
    B, _, t, _ = jax.lax.while_loop(
        cond, body, (B0, B0, jnp.int32(0), jnp.float32(jnp.inf)))
    return B, t


def control(cfg: dict, traffic: dict, W: np.ndarray, X, y) -> dict:
    """The reference at "bf16x3", making its own stop decision, in the
    program's place (``_control_fit``): an answer of the same form."""
    B, t = _control_fit(
        X, y, jnp.asarray(W), design.theory_lambda(cfg), cfg["tol"],
        h=design.bandwidth(cfg), kernel=cfg["kernel"], tau=cfg["tau"],
        max_iter=cfg["max_iter"], check_every=cfg["check_every"],
        chips=len(X.sharding.device_set))
    return {"B": np.asarray(B), "rounds": int(t)}


def check(cfg: dict, traffic: dict, W: np.ndarray, X, y,
          answer: dict) -> dict:
    """One answer against the reference run for the rounds it reports, on
    the chips that hold X (GSPMD splits the reference along X's node
    axis).

    ``fit_gap``: the largest |B - B_ref|.  ``stop_kkt_ratio``: the KKT
    residual of the answer's B over the tolerance where it stopped before
    the round cap, else 0."""
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
