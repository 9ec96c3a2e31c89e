"""Smoke test of the three sketch services on a TPU.

Drives `RetrievalService` (S-ANN), `RACEService` and `KDEService` through
their public entry points — ``ingest`` / ``ingest_async`` + ``flush``, then
``query`` / ``query_topk`` / ``kde`` / ``density`` — at deployment-scale
state, and checks every answer against a plain numpy reference:

  * retrieval — S-ANN at SIFT width (128-d, the paper's ANN dataset shape)
    with n_max = 10M: about 3.5 GB of tables, pointers and points on the
    device.  Every (c, r) answer is a stored point whose host-recomputed
    distance is at most c·r; reported distances match the host's; the
    (c, r) hit rate on near queries and query_topk's recall@10 against
    brute force over the stored set meet floors.
  * race — RACE (L=512, W=4096) on the paper's Gaussian-mixture stream: the
    counters equal a numpy histogram of the chip's own hash codes, and the
    densities equal the host's reading of those counters.
  * kde — SW-AKDE (L=64, W=1024, window 262,144) over two windows of that
    stream: every estimate is within kde_eps (Lemma 4.3) of the exact
    windowed count of the chip's own hash codes.

Usage:

    python chip_smoke.py [--seed N]          # one chip: the three phases
    python chip_smoke.py --chips 4 [--seed N]
        # four chips: retrieval and kde at num_shards=4 against
        # num_shards=1 in the same process; answers must be equal

Every phase prints its sizes, the state's bytes, the device's peak bytes,
set-up (compile) and steady wall times, the implementation (pallas / xla)
each kernel op took, and the share of hash codes that agree with a float64
host recomputation.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import gaussian_mixture_stream, sift_like  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import lsh  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.serve.kde_service import KDEService, KDEServiceConfig  # noqa: E402
from repro.serve.race_service import RACEService, RACEServiceConfig  # noqa: E402
from repro.serve.retrieval import RetrievalConfig, RetrievalService  # noqa: E402

# S-ANN on SIFT-like data (benchmarks.common.sift_like: 64 clusters,
# within-cluster pair distance ≈ 5.6, between clusters ≈ 17).  r covers a
# query's noise around its source point (≈ 0.57); w is a few within-cluster
# distances wide, so a query's in-cluster neighbours share buckets with it.
RETRIEVAL = dict(dim=128, n_max=10_000_000, eta=0.25, r=1.0, c=2.0, w=12.0,
                 L=16, k=8, bucket_cap=16, ingest_chunk=4096,
                 query_block=1024, topk=10)
RACE = dict(dim=200, L=512, W=4096, ingest_chunk=4096)
KDE = dict(dim=200, L=64, W=1024, window=262_144, eh_eps=0.1,
           ingest_chunk=4096)
SIZES = dict(retrieval_points=1_048_576, race_points=1_048_576,
             kde_points=524_288, queries=2048)
QUERY_NOISE = 0.05          # per-coordinate σ of the query perturbation

# Floors for the S-ANN phase, set from a CPU run of the same phase with the
# same stored-point density (n_max=100,000 over 331,776 points: about
# 18,600 stored) — see PERF.md.  The (c, r) floor is the share of queries
# around a stored point that find a point within c·r.
CR_FOUND_FLOOR = 0.9
RECALL_AT_10_FLOOR = 0.3

_MIX = np.uint32(2654435761)


class SmokeFailure(RuntimeError):
    """A phase's answers disagree with the numpy reference."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def tree_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def ingest_timed(svc, data: np.ndarray, chunk: int) -> tuple[float, float]:
    """First chunk through ``ingest`` (set-up: compiles prepare + commit),
    the rest through ``ingest_async`` + ``flush`` (steady)."""
    _, setup = timed(lambda: svc.ingest(data[:chunk]))

    def rest():
        svc.ingest_async(data[chunk:])
        svc.flush()
    _, steady = timed(rest)
    return setup, steady


def query_twice(fn, qs: np.ndarray):
    """First call compiles (set-up), the second is steady; both must give
    the same answers."""
    a, setup = timed(lambda: fn(qs))
    b, steady = timed(lambda: fn(qs))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        check(np.array_equal(np.asarray(x), np.asarray(y)),
              "repeated query over the same snapshot changed its answers")
    return a, setup, steady


def routes(ops) -> str:
    return ",".join(f"{op}:{dispatch.route(op)}" for op in ops)


def check_pallas_served(jitted, *args, ops) -> None:
    """Each op routed to Pallas must appear as a TPU kernel in the program
    the service runs (lowering only; nothing is compiled again)."""
    if any(dispatch.route(op) == "pallas" for op in ops):
        check("tpu_custom_call" in jitted.lower(*args).as_text(),
              f"{ops} are routed to Pallas but the served program holds "
              "no TPU kernel")


# --- host references ---------------------------------------------------------

def fold_np(raw: np.ndarray, mix: np.ndarray, n_buckets: int) -> np.ndarray:
    """numpy form of `core.lsh._fold` (uint32 arithmetic wraps mod 2^32)."""
    acc = (raw.astype(np.uint32) * mix).sum(axis=-1, dtype=np.uint32)
    return ((acc * _MIX) % np.uint32(n_buckets)).astype(np.int32)


def hash_f64(params, x: np.ndarray) -> np.ndarray:
    """Hash codes of ``x`` recomputed on the host in float64."""
    proj = np.asarray(params.proj, np.float64)
    mix = np.asarray(params.mix)
    y = np.asarray(x, np.float64) @ proj
    if isinstance(params, lsh.SRPParams):
        raw = (y >= 0).astype(np.int64)
    else:
        raw = np.floor((y + np.asarray(params.bias, np.float64)) / params.w)
        raw = raw.astype(np.int64)
    raw = raw.reshape(x.shape[0], params.L, params.k)
    return fold_np(raw, mix, params.n_buckets)


def chip_codes(params, x: np.ndarray, block: int) -> np.ndarray:
    """Hash codes of ``x`` computed on the chip in ``block``-row calls —
    the row count the service hashes with — and pulled to the host."""
    fn = jax.jit(lambda b: lsh.hash_points(params, b))
    return np.concatenate([np.asarray(fn(jnp.asarray(x[i:i + block])))
                           for i in range(0, x.shape[0], block)])


def hash_agreement(params, x: np.ndarray, block: int) -> float:
    return float(np.mean(chip_codes(params, x, block) == hash_f64(params, x)))


def histogram(params, x: np.ndarray, block: int) -> np.ndarray:
    """(L, W) counts of the chip's hash codes of ``x``."""
    L, W = params.L, params.n_buckets
    offs = np.arange(L, dtype=np.int64) * W
    hist = np.zeros(L * W, np.int64)
    for i in range(0, x.shape[0], 65_536):
        codes = chip_codes(params, x[i:i + 65_536], block)
        hist += np.bincount((codes + offs).ravel(), minlength=L * W)
    return hist.reshape(L, W)


def cell_means(hist: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """Row-mean of the counters each query hits (float64)."""
    return hist[np.arange(hist.shape[0]), codes].mean(axis=1)


# --- phases ------------------------------------------------------------------

def mixture(n: int, d: int, seed: int) -> np.ndarray:
    """Exactly ``n`` points of the paper's Gaussian-mixture stream (the
    generator emits a multiple of its 10 components)."""
    return gaussian_mixture_stream(-(-n // 10) * 10, d=d, seed=seed)[:n]


def retrieval_queries(data, points, valid, n_q, rng):
    """Half the queries perturb stored points (a true near neighbour within
    r exists), half perturb random ingested points."""
    stored = np.flatnonzero(valid)
    half = n_q // 2
    src = np.concatenate([points[rng.choice(stored, half, replace=False)],
                          data[rng.choice(len(data), n_q - half,
                                          replace=False)]])
    noise = rng.normal(0.0, QUERY_NOISE, src.shape)
    return (src + noise).astype(np.float32), half


def check_retrieval(svc, cfg, qs, n_near, res, ids, dists) -> dict:
    state = svc.snapshot()[0]
    points = np.asarray(state.points)
    valid = np.asarray(state.valid)
    cr = cfg.c * cfg.r

    index = np.asarray(res.index)
    found = np.asarray(res.found)
    dist = np.asarray(res.distance)
    check(np.array_equal(found, index >= 0), "(c, r): found != (index >= 0)")
    check(np.all(np.isinf(dist[~found])), "(c, r): NULL answer has a distance")
    hit = index[found]
    check(np.all(valid[hit]), "(c, r): answer is not a stored point")
    host = np.linalg.norm(qs[found] - points[hit], axis=1)
    check(np.all(host <= cr * (1 + 1e-3)), "(c, r): answer farther than c·r")
    check(np.allclose(dist[found], host, rtol=1e-3, atol=1e-4),
          "(c, r): reported distance != host distance")
    near_found = float(found[:n_near].mean())
    check(near_found >= CR_FOUND_FLOOR,
          f"(c, r): near-query hit rate {near_found} < {CR_FOUND_FLOOR}")

    ids = np.asarray(ids)
    dists = np.asarray(dists)
    live = ids >= 0
    check(np.array_equal(live, np.isfinite(dists)), "topk: id/distance pads")
    check(np.all(valid[ids[live]]), "topk: id is not a stored point")
    host_k = np.linalg.norm(qs[:, None, :] - points[np.maximum(ids, 0)],
                            axis=2)
    check(np.allclose(dists[live], host_k[live], rtol=1e-3, atol=1e-4),
          "topk: reported distance != host distance")
    check(np.all(dists[:, 1:] >= dists[:, :-1]),
          "topk: distances not ascending")

    stored = np.flatnonzero(valid)
    x = points[stored].astype(np.float64)
    xn = (x * x).sum(1)
    k = ids.shape[1]
    hits = 0
    for i in range(0, len(qs), 256):
        q = qs[i:i + 256].astype(np.float64)
        d2 = xn[None, :] - 2.0 * q @ x.T
        true = stored[np.argpartition(d2, k, axis=1)[:, :k]]
        hits += sum(len(set(t) & set(g[g >= 0]))
                    for t, g in zip(true, ids[i:i + 256]))
    recall = hits / (len(qs) * k)
    check(recall >= RECALL_AT_10_FLOOR,
          f"topk: recall@{k} {recall} < {RECALL_AT_10_FLOOR}")
    return dict(stored=int(valid.sum()), cr_found_near=near_found,
                cr_found_far=float(found[n_near:].mean()),
                recall_at_10=recall)


def phase_retrieval(seed: int, n_points: int, n_queries: int,
                    cfg_kw: dict = RETRIEVAL) -> None:
    cfg = RetrievalConfig(**cfg_kw, seed=seed)
    data = sift_like(n_points, seed=seed)
    svc = RetrievalService(cfg)
    try:
        sc = svc.cfg
        log("retrieval", dim=sc.dim, n_max=sc.n_max, eta=sc.eta, r=sc.r,
            c=sc.c, w=sc.w, L=sc.L, k=sc.k, bucket_cap=sc.bucket_cap,
            capacity=sc.capacity, n_buckets=sc.n_buckets,
            points=n_points, queries=n_queries,
            state_bytes=tree_bytes(svc.state))
        setup, steady = ingest_timed(svc, data, cfg.ingest_chunk)
        log("retrieval", ingest_setup_s=setup, ingest_steady_s=steady,
            stored=svc.stored)
        state = svc.snapshot()[0]
        qs, n_near = retrieval_queries(
            data, np.asarray(state.points), np.asarray(state.valid),
            n_queries, np.random.default_rng(seed + 1))
        res, q_setup, q_steady = query_twice(svc.query, qs)
        (ids, dists), k_setup, k_steady = query_twice(svc.query_topk, qs)
        log("retrieval", query_setup_s=q_setup, query_steady_s=q_steady,
            topk_setup_s=k_setup, topk_steady_s=k_steady)
        ops = ["sann_table_scatter", "batch_score_topk"]
        block = jnp.asarray(qs[:cfg.query_block])
        for fn in (svc._query_fn, svc._topk_fn):
            check_pallas_served(fn, state, block, ops=ops)
        log("retrieval", routes=routes(ops), hash="xla(core.lsh)",
            hash_agree_f64=hash_agreement(svc.params, data[:65_536],
                                          cfg.ingest_chunk))
        out = check_retrieval(svc, sc, qs, n_near, res, ids, dists)
        log("retrieval", peak_bytes=peak_bytes(), **out, check="pass")
    finally:
        svc.close()


def phase_race(seed: int, n_points: int, n_queries: int,
               cfg_kw: dict = RACE) -> None:
    cfg = RACEServiceConfig(**cfg_kw, seed=seed)
    data = mixture(n_points, cfg.dim, seed)
    svc = RACEService(cfg)
    try:
        log("race", dim=cfg.dim, L=cfg.L, W=cfg.W, k=cfg.k, points=n_points,
            queries=n_queries, state_bytes=tree_bytes(svc.state))
        setup, steady = ingest_timed(svc, data, cfg.ingest_chunk)
        log("race", ingest_setup_s=setup, ingest_steady_s=steady)
        rng = np.random.default_rng(seed + 2)
        qs = (data[rng.choice(n_points, n_queries, replace=False)]
              + rng.normal(0.0, 0.5, (n_queries, cfg.dim))).astype(np.float32)
        dens, q_setup, q_steady = query_twice(svc.kde, qs)
        log("race", query_setup_s=q_setup, query_steady_s=q_steady)
        ops = ["race_hist"]
        check_pallas_served(svc._prepare_fn,
                            jnp.asarray(data[:cfg.ingest_chunk]), ops=ops)
        log("race", routes=routes(ops), hash="xla(core.lsh)",
            hash_agree_f64=hash_agreement(svc.params, data[:65_536],
                                          cfg.ingest_chunk))

        state = svc.snapshot()[0]
        counts = np.asarray(state.counts)
        hist = histogram(svc.params, data, cfg.ingest_chunk)
        check(int(state.n) == n_points, "race: stream size")
        check(np.array_equal(counts, hist),
              f"race: {int((counts != hist).sum())} counters differ from "
              "the host histogram of the chip's codes")
        qcodes = chip_codes(svc.params, qs, cfg.query_block)
        want = cell_means(hist, qcodes) / n_points
        check(np.allclose(np.asarray(dens), want, rtol=1e-5, atol=0),
              "race: densities != host reading of the counters")
        log("race", peak_bytes=peak_bytes(), counters_equal=True,
            check="pass")
    finally:
        svc.close()


def phase_kde(seed: int, n_points: int, n_queries: int,
              cfg_kw: dict = KDE) -> None:
    cfg = KDEServiceConfig(**cfg_kw, seed=seed)
    data = mixture(n_points, cfg.dim, seed)
    svc = KDEService(cfg)
    try:
        log("kde", dim=cfg.dim, L=cfg.L, W=cfg.W, window=cfg.window,
            eh_eps=cfg.eh_eps, points=n_points, queries=n_queries,
            state_bytes=tree_bytes(svc.state))
        setup, steady = ingest_timed(svc, data, cfg.ingest_chunk)
        log("kde", ingest_setup_s=setup, ingest_steady_s=steady)
        rng = np.random.default_rng(seed + 3)
        # Queries around the newest points, which the window still holds.
        recent = data[-cfg.window:]
        qs = (recent[rng.choice(len(recent), n_queries, replace=False)]
              + rng.normal(0.0, 0.5, (n_queries, cfg.dim))).astype(np.float32)
        dens, q_setup, q_steady = query_twice(svc.density, qs)
        log("kde", query_setup_s=q_setup, query_steady_s=q_steady)
        log("kde", routes=routes(["swakde_segment_pass"]),
            hash="xla(core.lsh)",
            hash_agree_f64=hash_agreement(svc.params, data[:65_536],
                                          cfg.ingest_chunk))

        hist = histogram(svc.params, recent, cfg.ingest_chunk)
        qcodes = chip_codes(svc.params, qs, cfg.query_block)
        exact = cell_means(hist, qcodes) / min(n_points, cfg.window)
        est = np.asarray(dens, np.float64)
        eps = svc.sketch_cfg.kde_eps
        err = np.abs(est - exact)
        check(np.all(err <= eps * exact + 1e-12),
              f"kde: {int((err > eps * exact).sum())} estimates outside "
              f"kde_eps={eps} of the exact window count")
        log("kde", peak_bytes=peak_bytes(), kde_eps=eps,
            max_rel_err=float(np.max(err / np.maximum(exact, 1e-30))),
            check="pass")
    finally:
        svc.close()


def phase_sharded(seed: int, n_points: int, n_queries: int,
                  shards: int = 4) -> None:
    """Retrieval and kde at ``num_shards=shards`` against ``num_shards=1``
    in one process: the answers must be equal."""
    def compare(name, a, b, field):
        a, b = np.asarray(a), np.asarray(b)
        same = np.array_equal(a, b)
        log("sharded", service=name, field=field, equal=same,
            differing=int((a != b).sum()))
        return same

    # retrieval
    rcfg = RetrievalConfig(**RETRIEVAL, seed=seed)
    data = sift_like(n_points, seed=seed)
    one = RetrievalService(rcfg)
    many = RetrievalService(dataclasses.replace(rcfg, num_shards=shards))
    try:
        for name, svc in (("num_shards=1", one),
                          (f"num_shards={shards}", many)):
            setup, steady = ingest_timed(svc, data, rcfg.ingest_chunk)
            log("sharded", service=f"retrieval {name}", ingest_setup_s=setup,
                ingest_steady_s=steady, stored=svc.stored)
        state = one.snapshot()[0]
        qs, _ = retrieval_queries(data, np.asarray(state.points),
                                  np.asarray(state.valid), n_queries,
                                  np.random.default_rng(seed + 1))
        r1, r4 = one.query(qs), many.query(qs)
        (i1, d1), (i4, d4) = one.query_topk(qs), many.query_topk(qs)
        same = [compare("retrieval", getattr(r1, f), getattr(r4, f), f)
                for f in r1._fields]
        same += [compare("retrieval", i1, i4, "topk_ids"),
                 compare("retrieval", d1, d4, "topk_dists")]
        agree = np.mean(chip_codes(one.params, data[:65_536], 4096)
                        == chip_codes(many.params, data[:65_536], 4096))
        log("sharded", service="retrieval", hash_codes_agree=float(agree))
    finally:
        one.close()
        many.close()

    # kde
    kcfg = KDEServiceConfig(**KDE, seed=seed)
    kdata = mixture(n_points // 2, kcfg.dim, seed)
    one = KDEService(kcfg)
    many = KDEService(dataclasses.replace(kcfg, num_shards=shards))
    try:
        for name, svc in (("num_shards=1", one),
                          (f"num_shards={shards}", many)):
            setup, steady = ingest_timed(svc, kdata, kcfg.ingest_chunk)
            log("sharded", service=f"kde {name}", ingest_setup_s=setup,
                ingest_steady_s=steady)
        rng = np.random.default_rng(seed + 3)
        kq = (kdata[rng.choice(len(kdata), n_queries, replace=False)]
              + rng.normal(0.0, 0.5, (n_queries, kcfg.dim))).astype(np.float32)
        same.append(compare("kde", one.density(kq), many.density(kq),
                            "density"))
    finally:
        one.close()
        many.close()
    log("sharded", peak_bytes=peak_bytes())
    check(all(same), f"num_shards={shards} answers differ from num_shards=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 2
    use_compile_cache()
    log("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__)

    n_q = SIZES["queries"]
    if args.chips == 4:
        phase_sharded(args.seed, SIZES["retrieval_points"], n_q)
    else:
        phase_retrieval(args.seed, SIZES["retrieval_points"], n_q)
        phase_race(args.seed, SIZES["race_points"], n_q)
        phase_kde(args.seed, SIZES["kde_points"], n_q)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
