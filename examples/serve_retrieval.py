"""End-to-end serving driver: a small LM decodes with batched requests while
an S-ANN retrieval service indexes the stream of its hidden states — the
paper's sketch as first-class serving infrastructure.

The service ingests through the two-phase pipelined path (`serve.engine
.SketchEngine`: prepare — hash matmul + sort — of chunk k+1 overlaps the
commit of chunk k): the synthetic document corpus is submitted with
``ingest_async`` and the decode loop starts immediately; queries during
the bulk load see a committed prefix of the corpus, and ``flush()`` later
guarantees the full corpus is in — the state is bit-identical to a
synchronous ``ingest``.

``--num-shards N`` demos the sharded service (`repro.parallel
.sketch_sharding`): the L hash tables are split across N devices — on a
CPU-only box the devices are virtual (forced via XLA_FLAGS before jax
initialises), and results are bit-identical to the single-device service.

``--num-workers K`` demos the merge-based cluster (`repro.serve.cluster
.ClusterRetrievalService`): the corpus is hash-partitioned across K worker
engines ingesting concurrently, and queries run against the coordinator's
`sann_merge`-combined sketch.

``--snapshot-dir DIR`` demos durability (`repro.persist`): every ingest
chunk is WAL-logged at enqueue time and the engine snapshots its state in
the background; re-running with the same DIR recovers the previous run's
index bit-identically (snapshot + WAL-tail replay) before ingesting more.

``--rpc`` demos the network cluster (`repro.net`, DESIGN.md §16) — the
workers as separate PROCESSES behind the RPC front door:

  * ``--rpc spawn`` — the coordinator spawns ``--num-workers`` worker
    processes itself (the common one-box case);
  * ``--rpc worker [--port P] [--worker-index W]`` — run THIS process as
    worker W: it builds worker W's engine config (per-worker salt +
    durability subdir, exactly what the coordinator would build) and
    serves RPC until shutdown — e.g. in a second terminal;
  * ``--rpc connect --peers host:port[,host:port...]`` — the coordinator
    connects to externally-started workers.  List peers in worker-index
    order and start every worker with the same flags as the coordinator:
    the cluster's exactness rests on worker W running the config the
    coordinator assumes.

Run: PYTHONPATH=src python examples/serve_retrieval.py [--steps 24]
     PYTHONPATH=src python examples/serve_retrieval.py --num-shards 4
     PYTHONPATH=src python examples/serve_retrieval.py --num-workers 2
     PYTHONPATH=src python examples/serve_retrieval.py \
         --snapshot-dir /tmp/retr_snap   # run twice: 2nd run recovers
     PYTHONPATH=src python examples/serve_retrieval.py --rpc spawn \
         --num-workers 2                 # multi-process cluster
     # two-terminal form:
     PYTHONPATH=src python examples/serve_retrieval.py --rpc worker \
         --port 7461                     # terminal 1: worker 0
     PYTHONPATH=src python examples/serve_retrieval.py --rpc connect \
         --peers 127.0.0.1:7461          # terminal 2: coordinator
"""
import argparse
import os
import time


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--corpus", type=int, default=3000,
                    help="synthetic documents pre-ingested in chunks")
    ap.add_argument("--ingest-chunk", type=int, default=512)
    ap.add_argument("--num-shards", type=int, default=0,
                    help="shard the L hash tables across this many devices "
                         "(0/1 = single-device)")
    ap.add_argument("--num-workers", type=int, default=0,
                    help="hash-partition ingest across this many worker "
                         "engines with a merge-based coordinator "
                         "(0/1 = single engine)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="durability directory (WAL + snapshots); an "
                         "existing one is recovered before ingesting")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="admission control: bound queued-but-uncommitted "
                         "rows (0 = unbounded)")
    ap.add_argument("--rpc", default=None,
                    choices=["spawn", "worker", "connect"],
                    help="network cluster mode: 'spawn' worker processes, "
                         "run as a 'worker', or 'connect' to --peers")
    ap.add_argument("--port", type=int, default=0,
                    help="--rpc worker: listen port (0 = ephemeral, "
                         "announced on stdout)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--rpc worker: listen address")
    ap.add_argument("--worker-index", type=int, default=0,
                    help="--rpc worker: which cluster slot this worker is")
    ap.add_argument("--peers", default=None,
                    help="--rpc connect: comma-separated host:port list, "
                         "in worker-index order")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.num_shards > 1:
        # must be set before jax initialises its backends; append to any
        # pre-existing XLA_FLAGS rather than losing either side
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = (
                existing + " --xla_force_host_platform_device_count="
                f"{args.num_shards}").strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import use_compile_cache
    from repro.configs import registry
    from repro.models import model as model_lib
    from repro.serve import kv_cache, serve_step as serve_lib
    from repro.serve.cluster import ClusterRetrievalService
    from repro.serve.retrieval import RetrievalConfig, RetrievalService

    use_compile_cache()
    cfg = registry.get_smoke_config("qwen3-4b")
    retr_cfg = RetrievalConfig(
        dim=cfg.d_model, n_max=10_000, eta=0.3, r=0.35, c=2.0,
        ingest_chunk=args.ingest_chunk, num_shards=args.num_shards,
        max_pending=args.max_pending or None,
        snapshot_dir=args.snapshot_dir)

    if args.rpc == "worker":
        # This process IS worker --worker-index: build that slot's engine
        # config (per-worker salt + durability subdir — exactly what the
        # coordinator's in-process cluster would build) and serve RPC
        # until a coordinator sends shutdown.
        import dataclasses

        from repro.net import run_worker
        from repro.serve.cluster import _worker_cfg
        wcfg = _worker_cfg(retr_cfg, args.worker_index,
                           ingest_salt=args.worker_index,
                           batch_queries=False)
        run_worker("retrieval", dataclasses.asdict(wcfg), host=args.host,
                   port=args.port)
        return

    params = model_lib.init_model(cfg, jax.random.PRNGKey(0))
    B, S_max = args.batch, args.steps + 8
    cache = kv_cache.init_cache(cfg, B=B, s_max=S_max)
    step = jax.jit(serve_lib.make_serve_step(cfg))

    if args.rpc in ("spawn", "connect"):
        from repro.net import RPCClusterRetrievalService, RPCConfig
        peers = None
        if args.rpc == "connect":
            if not args.peers:
                raise SystemExit("--rpc connect requires --peers")
            peers = tuple(
                (h, int(p)) for h, p in
                (s.rsplit(":", 1) for s in args.peers.split(",")))
            nw = len(peers)
        else:
            nw = max(args.num_workers, 2)
        retr = RPCClusterRetrievalService(retr_cfg, num_workers=nw,
                                          rpc=RPCConfig(peers=peers))
        mode = (f"connected to {args.peers}" if peers
                else f"spawned {nw} worker processes")
        print(f"network cluster retrieval service: {mode} "
              f"(RPC scatter-gather, merge-based coordinator)")
    elif args.num_workers > 1:
        retr = ClusterRetrievalService(retr_cfg,
                                       num_workers=args.num_workers)
        print(f"cluster retrieval service: {args.num_workers} workers "
              f"(hash-partitioned ingest, merge-based coordinator)")
    else:
        retr = RetrievalService(retr_cfg)
        print(f"retrieval service: {retr.num_shards} shard(s), "
              f"ingest_chunk={args.ingest_chunk}")
    if args.snapshot_dir:
        replayed = retr.recover()   # no-op on an empty directory
        print(f"durability at {args.snapshot_dir}: recovered "
              f"{replayed} WAL record(s), "
              f"{getattr(retr, 'stored', 0)} vectors live")

    # Submit the document corpus asynchronously: the engine's prepare
    # thread hashes chunk k+1 while chunk k commits, and the decode loop
    # below starts while the tail of the corpus is still loading.
    rng = np.random.default_rng(7)
    corpus = rng.normal(0, 1, (args.corpus, cfg.d_model)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True) + 1e-6
    t0 = time.time()
    retr.ingest_async(corpus)
    print(f"bulk load submitted: {args.corpus} docs in "
          f"{-(-args.corpus // args.ingest_chunk)} chunks "
          f"(committed so far: {retr.version})")
    retr.flush()                        # wait for every chunk to commit
    dt = time.time() - t0
    print(f"bulk ingest flushed: {args.corpus / dt:.0f} docs/s, "
          f"stored={retr.stored}")

    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab)
    t0 = time.time()
    for t in range(args.steps):
        logits, cache = step(params, cache, tokens)
        tokens = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        # stream the step's hidden summary into the ANN index
        # (here: the logits' top activations as a cheap embedding surrogate)
        emb = np.array(logits[:, 0, : cfg.d_model], np.float32)  # writable copy
        emb /= np.linalg.norm(emb, axis=1, keepdims=True) + 1e-6
        retr.ingest(emb)
    dt = time.time() - t0
    print(f"decoded {args.steps} steps x batch {B} "
          f"({args.steps * B / dt:.1f} tok/s on CPU)")
    print(f"retrieval index: {retr.stored} stored vectors, "
          f"{retr.sketch_bytes:,} sketch bytes")

    # batched queries against the decode-time stream (Corollary 3.2)
    res = retr.query(emb)
    found = np.asarray(res.found)
    mean_d = (np.asarray(res.distance)[found].mean()
              if found.any() else float("nan"))
    print(f"batched query: found={found.mean():.2f} mean_dist={mean_d:.3f}")
    retr.close()     # --rpc: shuts workers down (incl. external --peers)


if __name__ == "__main__":
    main()
