"""Quickstart: the paper's two sketches in ~40 lines each.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import lsh, sann, swakde


def streaming_ann():
    print("== Streaming (c,r)-ANN (paper §3) ==")
    n, d = 5_000, 16
    rng = np.random.default_rng(0)
    stream = rng.uniform(0, 1, (n, d)).astype(np.float32)  # Poisson-ish cloud

    cfg = sann.SANNConfig(dim=d, n_max=n, eta=0.4, r=0.8, c=2.0, w=1.6,
                          L=10, k=4)
    cfg, params, state = sann.sann_init(cfg, jax.random.PRNGKey(0))
    # batched ingest: one hash matmul + one segment scatter per chunk,
    # bit-identical to the per-point sann_insert_stream under the same key
    state = sann.sann_insert_batch(state, params, jnp.asarray(stream),
                                   jax.random.PRNGKey(1), cfg)
    print(f"  stream={n}  stored={int(state.n_stored)} "
          f"(keep prob n^-eta = {cfg.keep_prob:.3f})")

    queries = jnp.asarray(stream[rng.choice(n, 8)] + 0.01)
    res = sann.sann_query_batch(state, params, queries, cfg)
    for i in range(8):
        print(f"  q{i}: found={bool(res.found[i])} "
              f"dist={float(res.distance[i]):.3f} "
              f"candidates={int(res.n_candidates[i])}")

    # turnstile deletion (§3.4)
    state = sann.sann_delete(state, params, jnp.asarray(stream[0]), cfg)
    print(f"  after delete: stored={int(state.n_stored)}")


def sliding_window_kde():
    print("== Sliding-window A-KDE (paper §4) ==")
    d, window = 8, 100
    cfg = swakde.SWAKDEConfig(L=16, W=64, window=window, eh_eps=0.1)
    params = lsh.init_srp(jax.random.PRNGKey(2), d, L=16, k=2, n_buckets=64)
    state = swakde.swakde_init(cfg)

    rng = np.random.default_rng(3)
    cluster_a = rng.normal(+2, 0.3, (150, d)).astype(np.float32)
    cluster_b = rng.normal(-2, 0.3, (150, d)).astype(np.float32)
    state = swakde.swakde_stream(state, params,
                                 jnp.asarray(np.concatenate([cluster_a,
                                                             cluster_b])), cfg)
    qa = jnp.full((d,), +2.0)
    qb = jnp.full((d,), -2.0)
    da = float(swakde.swakde_query(state, params, qa, cfg))
    db = float(swakde.swakde_query(state, params, qb, cfg))
    print(f"  window holds the last {window} points (cluster B)")
    print(f"  density at A-center: {da:.2f}   density at B-center: {db:.2f}")
    print(f"  -> expired cluster A correctly forgotten: {da < 0.2 * db}")
    print(f"  sketch bytes: {swakde.swakde_bytes(cfg):,} "
          f"(eps={cfg.kde_eps:.2f} guarantee)")


if __name__ == "__main__":
    use_compile_cache()
    streaming_ann()
    sliding_window_kde()
