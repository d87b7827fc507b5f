"""The one-pass kernel ``decsvm_xpass`` against XLA's pair of fusions, on a
TPU.

Both compute G_l = X_l' phi(X_l v_l) for every node l of X (m, n, p) fp32:
XLA as the two HIGHEST contractions of ``solver.local_update`` (margins,
then X'w), the kernel in one read of X.  Each timing is the device time of
one product inside a jitted loop of ``--iters`` products whose vector
depends on the last product (as the rounds of a fit do), taken as the best
of ``--reps`` loops over the iteration count.

    python benchmarks/bench_xpass.py --out xpass.json
        [--m 10] [--p 2001] [--ns 200,1000,...] [--tiles 512,1024,2048]

Prints one JSON object: the layout JAX gave X, then per n the XLA pair's
and each tile's ms per product, and the kernel's largest gap to the XLA
pair relative to max|G|.  Exit 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import losses, solver  # noqa: E402
from repro.kernels import xpass  # noqa: E402

H, KERNEL = 0.3, "epanechnikov"


def xla_pair(X, y, V, weight):
    """The jnp reference: two HIGHEST contractions per node."""
    def node(Xl, yl, vl):
        u = solver.mm(Xl, vl)
        w = (losses.get_kernel(KERNEL).dloss(yl * u, H) * yl
             if weight == "loss" else u)
        return solver.mm(Xl.T, w) / Xl.shape[0]
    return jax.vmap(node)(X, y, V)


def looped(product, iters):
    """``iters`` dependent products in one program: V <- V/2 + G."""
    @jax.jit
    def run(X, y, V):
        def body(_, V):
            return 0.5 * V + product(X, y, V)
        return jax.lax.fori_loop(0, iters, body, V)
    return run


def device_ms(product, X, y, V, iters, reps):
    run = looped(product, iters)
    jax.block_until_ready(run(X, y, V))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(run(X, y, V))
        best = min(best, time.perf_counter() - t)
    return best / iters * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=10)
    ap.add_argument("--p", type=int, default=2001)
    ap.add_argument("--ns", default="40000")
    ap.add_argument("--tiles", default="1024")
    ap.add_argument("--weights", default="loss")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    rows = []
    out = {"device": jax.devices()[0].device_kind, "m": a.m, "p": a.p,
           "rows": rows}
    for n in (int(s) for s in a.ns.split(",")):
        k = jax.random.split(jax.random.PRNGKey(n), 3)
        X = jax.jit(lambda k: jax.random.normal(k, (a.m, n, a.p)))(k[0])
        y = jnp.sign(jax.random.normal(k[1], (a.m, n)))
        V = 0.05 * jax.random.normal(k[2], (a.m, a.p))
        out.setdefault("x_layout", {})[n] = str(getattr(X, "format", ""))
        for weight in a.weights.split(","):
            row = {"n": n, "weight": weight, "x_bytes_per_node": n * a.p * 4}
            ref = xla_pair(X, y, V, weight)
            row["xla_ms"] = device_ms(
                lambda X, y, V: xla_pair(X, y, V, weight), X, y, V,
                a.iters, a.reps)
            scale = float(jnp.max(jnp.abs(ref)))
            for tile in (int(s) for s in a.tiles.split(",")):
                prod = lambda X, y, V, t=tile: xpass.xpass(
                    X, y, V, weight=weight, h=H, kernel=KERNEL, tile=t)
                g = prod(X, y, V)
                row[f"tile{tile}_ms"] = device_ms(prod, X, y, V, a.iters,
                                                  a.reps)
                row[f"tile{tile}_rel_gap"] = float(
                    jnp.max(jnp.abs(g - ref))) / scale
            rows.append(row)
            print(json.dumps(row), flush=True)
        del X
    text = json.dumps(out, indent=1)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
