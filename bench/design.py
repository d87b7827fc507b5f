"""The data of a deployment, made from the run's seed.

Every configuration uses the same on-device generator: the Section 4.1
simulation design of arXiv:2503.07563 (two Gaussian classes with mean shift
+-mu on the first s covariates, AR(rho) blocks of sizes s and p-s, labels
flipped with probability p_flip, an intercept column first).  The graph is a
connected Erdos-Renyi network drawn on the host.  These are copies kept with
the benchmark, so that a change to the program cannot move the inputs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for stream ``stream`` of a run seed of any size."""
    ss = np.random.SeedSequence([seed % 2**64, stream])
    return ss.generate_state(2, np.uint32)


def device_key(seed: int, stream: int) -> jax.Array:
    return jax.random.wrap_key_data(jnp.asarray(seed_words(seed, stream)),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64,
                                                         stream]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def device_design(key, m, n, p, s, mu, rho, p_flip):
    """The Section 4.1 design made on the device: the AR(rho) blocks come
    from the AR(1) recursion, whose covariance is exactly rho^|i-j|.
    Returns X (m, n, p+1) fp32 with the intercept column, and y (m, n)."""
    N = m * n
    ky, kz, kf = jax.random.split(key, 3)
    y = jnp.where(jax.random.bernoulli(ky, 0.5, (N,)), 1.0, -1.0)
    Z = jax.random.normal(kz, (p, N), jnp.float32)
    c = jnp.sqrt(1.0 - rho * rho)

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


def _connected(W: np.ndarray) -> bool:
    seen = np.zeros(W.shape[0], bool)
    seen[0] = True
    stack = [0]
    while stack:
        for v in np.nonzero(W[stack.pop()])[0]:
            if not seen[v]:
                seen[v] = True
                stack.append(int(v))
    return bool(seen.all())


def erdos_renyi(m: int, p_connect: float, rng: np.random.Generator,
                max_tries: int = 1000) -> np.ndarray:
    """Connected G(m, p_connect), 0/1 float32 adjacency, zero diagonal."""
    for _ in range(max_tries):
        upper = np.triu(rng.random((m, m)) < p_connect, 1)
        W = (upper | upper.T).astype(np.float32)
        if _connected(W):
            return W
    raise RuntimeError(f"no connected G({m}, {p_connect}) in {max_tries}")


def bandwidth(cfg: dict) -> float:
    """The paper's Section 4.1 rule h = max{(log p / N)^(1/4), 0.05}."""
    N = cfg["m"] * cfg["n"]
    return max((math.log(max(cfg["p"], 2)) / max(N, 2)) ** 0.25, 0.05)


def theory_lambda(cfg: dict) -> float:
    """lambda = c * sqrt(log p / N), the rate of the paper's theory."""
    N = cfg["m"] * cfg["n"]
    return cfg["lam_c"] * math.sqrt(math.log(cfg["p"]) / N)


def make_pool(cfg: dict, seed: int, size: int):
    """``size`` data sets of the deployment, and its graph, from the seed.
    Returns ([(X, y), ...] on the device, W (m, m) numpy)."""
    W = erdos_renyi(cfg["m"], cfg["graph_p"], host_rng(seed, 0))
    pool = []
    for k in range(size):
        X, y = device_design(device_key(seed, 1 + k), cfg["m"], cfg["n"],
                             cfg["p"], cfg["s"], cfg["mu"], cfg["ar_rho"],
                             cfg["flip_rate"])
        pool.append((X, y))
    jax.block_until_ready(pool)
    return pool, W
