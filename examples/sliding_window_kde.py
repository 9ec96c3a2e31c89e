"""Sliding-window A-KDE on a drifting stream + batch-update variant
(paper §4 + Corollary 4.2), with the error measured against the exact
collision-kernel density.

Run: PYTHONPATH=src python examples/sliding_window_kde.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import lsh, swakde


def main():
    d, window, L, W = 24, 150, 32, 96
    cfg = swakde.SWAKDEConfig(L=L, W=W, window=window, eh_eps=0.1)
    params = lsh.init_srp(jax.random.PRNGKey(0), d, L=L, k=2, n_buckets=W)

    rng = np.random.default_rng(1)
    phases = [rng.normal(m, 0.5, (200, d)).astype(np.float32)
              for m in (2.0, -2.0, 0.0)]
    stream = np.concatenate(phases)

    state = swakde.swakde_init(cfg)
    # chunked batched ingest — bit-identical to the per-point swakde_stream
    state = swakde.swakde_stream_batched(state, params, jnp.asarray(stream),
                                         cfg, chunk=128)

    q = jnp.asarray(phases[2][:8])   # query near the current phase
    est = np.asarray(swakde.swakde_query_batch(state, params, q, cfg))
    win = jnp.asarray(stream[-window:])
    exact = np.asarray(jax.vmap(
        lambda qq: jax.vmap(lambda x: lsh.srp_collision_prob(x, qq, p=2))(win).sum())(q))
    rel = np.abs(est - exact) / np.maximum(exact, 1e-6)
    print(f"single-update sketch: mean rel err {rel.mean():.3f} "
          f"(theory bound {cfg.kde_eps:.2f})")
    print(f"sketch bytes: {swakde.swakde_bytes(cfg):,}")

    # batch updates (Corollary 4.2): window counts the last N *batches*
    R = 10
    bcfg = swakde.BatchSWAKDEConfig(L=L, W=W, window=window // R, eh_eps=0.1,
                                    batch_size=R)
    bstate = swakde.batch_swakde_init(bcfg)
    for i in range(len(stream) // R):
        bstate = swakde.batch_swakde_update(
            bstate, params, jnp.asarray(stream[i * R:(i + 1) * R]), bcfg)
    best = np.asarray(jax.vmap(
        lambda qq: swakde.batch_swakde_query(bstate, params, qq, bcfg))(q))
    brel = np.abs(best - exact) / np.maximum(exact, 1e-6)
    print(f"batch-update sketch (R={R}): mean rel err {brel.mean():.3f}")


if __name__ == "__main__":
    use_compile_cache()
    main()
