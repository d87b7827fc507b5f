"""The paper's technique as a framework feature: decentralized training of a
sparse elastic-net CSVM head on frozen backbone features, with the network
nodes laid out over JAX devices via shard_map (run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a real 8-device run).

Scenario: 8 'hospitals' (nodes) each hold private sequences; the qwen3
backbone is frozen everywhere; only the (d_model+1)-dim sparse head is
learned, by one-hop ADMM message passing (Algorithm 1).

    PYTHONPATH=src python examples/decentralized_head.py
"""
import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.core import ADMMConfig, metrics
from repro.core.decentral import decsvm_fit_chunked, make_node_chunk_mesh
from repro.core.graph import ring
from repro.models import model
from repro.optim.decsvm_head import extract_features


def main():
    m, n, S = 8, 60, 32
    cfg = configs.get_reduced("qwen3_14b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (m, n, S))

    print("extracting frozen-backbone features ...")
    feats = np.asarray(extract_features(
        params, cfg, jnp.asarray(toks.reshape(-1, S), jnp.int32)))
    feats = feats.reshape(m, n, -1)

    # private labels: sparse hyperplane in feature space + 5% noise
    w_true = np.zeros(feats.shape[-1])
    w_true[:10] = rng.standard_normal(10)
    yl = np.sign(np.einsum("mnd,d->mn", feats - feats.mean((0, 1)), w_true))
    yl = np.where(rng.random(yl.shape) < 0.05, -yl, yl).astype(np.float32)

    mu, sd = feats.mean((0, 1)), feats.std((0, 1)) + 1e-6
    X = np.concatenate([np.ones((m, n, 1), np.float32),
                        ((feats - mu) / sd).astype(np.float32)], axis=-1)

    W = ring(m)   # ring graph: one boundary row each way per round
    acfg = ADMMConfig(lam=0.02, h=0.3, max_iter=400)
    mesh = make_node_chunk_mesh()
    print(f"devices={mesh.shape['node_chunk']} nodes={m}")
    B = np.asarray(decsvm_fit_chunked(
        jnp.asarray(X), jnp.asarray(yl), W, acfg, mesh=mesh))

    margins = np.einsum("mnp,mp->mn", X, B)
    acc = metrics.margin_accuracy(margins, yl)
    print(f"train accuracy      : {acc:.3f}")
    print(f"consensus gap       : {metrics.consensus_gap(B):.2e}")
    print(f"mean support size   : {metrics.mean_support_size(B, 1e-4):.1f} "
          f"of {X.shape[-1]}")
    print("communication/round : one (d_model+1)-vector per neighbour "
          "(never the data)")


if __name__ == "__main__":
    main()
