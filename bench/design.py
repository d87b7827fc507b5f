"""The data of a deployment, made from the run's seed.

Every configuration uses the same on-device design: the Section 4.1
simulation design of arXiv:2503.07563 (two Gaussian classes with mean shift
+-mu on the first s covariates, AR(rho) blocks of sizes s and p-s, labels
flipped with probability p_flip, an intercept column first).  The graph is
named by the configuration's ``graph`` key:

- ``"erdos_renyi"``: a connected G(m, ``graph_p``) drawn on the host;
- ``"k_regular"``: the circulant lattice in which node i links to
  i +- 1..``graph_k``/2 (mod m), ``graph_k`` even.

Where a cell runs on one chip, each data set is made whole by one
``device_design`` call.  Where it runs on several, each data set is made
node by node (node l from ``fold_in(key, l)``) by ``node_design``, directly
as X (m, n, p+1) and y (m, n) sharded along the node axis over a 1-D mesh
of the cell's chips, so that X is never whole on one chip; m has to divide
evenly over them.  These are copies kept with the benchmark, so that a
change to the program cannot move the inputs.
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


def k_regular(m: int, k: int) -> np.ndarray:
    """The circulant lattice: node i links to i +- 1..k/2 (mod m); 0/1
    float32 adjacency, zero diagonal."""
    if k % 2 or not 0 < k < m:
        raise ValueError(f"k_regular needs an even k in (0, m), got k={k}, "
                         f"m={m}")
    W = np.zeros((m, m), np.float32)
    i = np.arange(m)
    for d in range(1, k // 2 + 1):
        W[i, (i + d) % m] = W[(i + d) % m, i] = 1.0
    return W


def make_graph(cfg: dict, seed: int) -> np.ndarray:
    """The deployment's graph by its name (see the module's doc)."""
    name = cfg["graph"]
    if name == "erdos_renyi":
        return erdos_renyi(cfg["m"], cfg["graph_p"], host_rng(seed, 0))
    if name == "k_regular":
        return k_regular(cfg["m"], cfg["graph_k"])
    raise ValueError(f"unknown graph {name!r}; have 'erdos_renyi', "
                     "'k_regular'")


def bandwidth(cfg: dict) -> float:
    """The paper's Section 4.1 rule h = max{(log p / N)^(1/4), 0.05}."""
    N = cfg["m"] * cfg["n"]
    return max((math.log(max(cfg["p"], 2)) / max(N, 2)) ** 0.25, 0.05)


def theory_lambda(cfg: dict) -> float:
    """lambda = c * sqrt(log p / N), the rate of the paper's theory."""
    N = cfg["m"] * cfg["n"]
    return cfg["lam_c"] * math.sqrt(math.log(cfg["p"]) / N)


# Live bytes of one block of columns made at a time by ``node_design``.
BLOCK_BYTES = 128 << 20


def column_block(p: int, column_bytes: int) -> int:
    """Columns made at a time: the largest divisor of p whose block stays
    within ``BLOCK_BYTES`` (one column at the least)."""
    cap = max(1, BLOCK_BYTES // column_bytes)
    return max(c for c in range(1, min(cap, p) + 1) if p % c == 0)


@functools.lru_cache(maxsize=None)
def _node_program(devices, m, n, p, s, mu, rho, p_flip):
    """(zeros, fill): the sharded buffers of X and y, and the program that
    fills them in place (the buffers are donated) from the key's words."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("nodes",))
    m_local = m // len(devices)
    block = column_block(p, 4 * m_local * n)
    rows = NamedSharding(mesh, P("nodes"))
    c = math.sqrt(1.0 - rho * rho)

    def local(words, X, y):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        nodes = jax.lax.axis_index("nodes") * m_local + jnp.arange(m_local)
        keys = jax.vmap(lambda l: jax.random.split(
            jax.random.fold_in(key, l), 3))(nodes)
        ky, kz, kf = keys[:, 0], keys[:, 1], keys[:, 2]
        y0 = jnp.where(jax.vmap(lambda k: jax.random.bernoulli(
            k, 0.5, (n,)))(ky), 1.0, -1.0)
        flip = jax.vmap(lambda k: jax.random.uniform(k, (n,)))(kf) < p_flip
        y = jnp.where(flip, -y0, y0)
        X = jax.lax.dynamic_update_slice(
            X, jnp.ones((m_local, n, 1), jnp.float32), (0, 0, 0))
        normal = jax.vmap(jax.vmap(
            lambda k, j: jax.random.normal(jax.random.fold_in(k, j), (n,)),
            (0, None)), (None, 0))

        def step(prev, zab):
            z, a, b = zab
            x = a * prev + b * z
            return x, x

        def body(i, carry):
            X, prev = carry
            cols = i * block + jnp.arange(block)
            Z = jnp.swapaxes(normal(kz, cols), 1, 2)     # (block, n, m)
            # AR(rho) within each of the blocks [0, s) and [s, p): x_j =
            # rho x_(j-1) + c z_j, and x_j = z_j where a block starts.
            start = (cols == 0) | (cols == s)
            prev, Xf = jax.lax.scan(step, prev, (
                Z, jnp.where(start, 0.0, rho), jnp.where(start, 1.0, c)))
            shift = jnp.where(cols < s, mu, 0.0)
            Xc = jnp.transpose(Xf, (2, 1, 0)) + y0[:, :, None] * shift
            X = jax.lax.dynamic_update_slice(X, Xc, (0, 0, 1 + i * block))
            return X, prev

        X, _ = jax.lax.fori_loop(0, p // block, body, (
            X, jnp.zeros((n, m_local), jnp.float32)))
        return X, y

    zeros = jax.jit(lambda: (jnp.zeros((m, n, p + 1), jnp.float32),
                             jnp.zeros((m, n), jnp.float32)),
                    out_shardings=(rows, rows))
    fill = jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P("nodes"), P("nodes")),
                                 out_specs=(P("nodes"), P("nodes")),
                                 check_vma=False),
                   in_shardings=(NamedSharding(mesh, P()), rows, rows),
                   out_shardings=(rows, rows), donate_argnums=(1, 2))
    return zeros, fill


def node_design(key_words, devices, m, n, p, s, mu, rho, p_flip):
    """The Section 4.1 design keyed by node, so that each chip makes its own
    nodes.  Node l draws from ``fold_in(key, l)`` split three ways: its
    labels y0 (+-1 with probability 1/2), its covariate j from
    ``fold_in(kz, j)`` (n normals), and its label flips; then the AR(rho)
    recursion along the covariates, the mean shift y0 mu on the first s,
    the flips, and the intercept column first.  X (m, n, p+1) and y (m, n)
    come out sharded along the node axis over a 1-D mesh of ``devices``;
    each chip fills its share ``column_block`` columns at a time.  The same
    bits on any number of devices."""
    if m % len(devices):
        raise ValueError(f"m={m} nodes do not divide evenly over "
                         f"{len(devices)} devices")
    zeros, fill = _node_program(tuple(devices), m, n, p, s, mu, rho, p_flip)
    return fill(jnp.asarray(key_words), *zeros())


def make_pool(cfg: dict, seed: int, size: int, devices):
    """``size`` data sets of the deployment on ``devices``, and its graph,
    from the seed.  ``devices`` are the cell's chips, exactly as many as it
    asks for (``harness.check_devices``), so that a cell's data does not
    depend on the machine: on one device each data set is one
    ``device_design`` call; on several it is ``node_design``, sharded along
    the node axis.
    Returns ([(X, y), ...] on the devices, W (m, m) numpy)."""
    W = make_graph(cfg, seed)
    args = (cfg["m"], cfg["n"], cfg["p"], cfg["s"], cfg["mu"], cfg["ar_rho"],
            cfg["flip_rate"])
    pool = []
    for k in range(size):
        if len(devices) == 1:
            with jax.default_device(devices[0]):
                X, y = device_design(device_key(seed, 1 + k), *args)
        else:
            X, y = node_design(seed_words(seed, 1 + k), devices, *args)
        pool.append((X, y))
    jax.block_until_ready(pool)
    return pool, W
