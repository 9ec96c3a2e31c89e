"""Correctness of sann-sift128-1M: what the timed path produced, against
the plain S-ANN reference (`chipbench.refs.sann`).

The reference re-derives the keep decisions and the p-stable hash from the
seed, regenerates the stream, hashes the kept points in float64 and
replays the point-store slots (a slot reused once the store wraps) and
the ring-buffer appends (a ring keeps its newest ``bucket_cap``).  The
fill at the close (points stored, live table entries, ring evictions) is
printed to standard error.  Compared, each with its limit:

* ``state_mismatch`` — exact: the committed counters (write pointer,
  stored and seen counts, Σ table pointers = kept points × L), the valid
  mask, each stored vector bit for bit and each arrival stamp, against the
  reference's sample of the stream.
* ``table_mismatch`` — share of the reference's table entries (row,
  bucket, slot) that the committed ring of that bucket does not hold, at
  any of its ``bucket_cap`` positions.  A TPU's default matmul precision
  takes one bfloat16 pass, so a point's p-stable code differs from the
  float64 one in a few percent of rows; the limit lies between that
  reading and the int8 control's.  The ring position is not compared: one
  append that lands elsewhere shifts every later position of its bucket,
  so over crowded buckets a position count grows with the fill and not
  with the fault.
* ``topk_unstored``, ``topk_repeated``, ``topk_unsorted``, ``topk_pads`` —
  exact, over every answered top-k request: ids that are not stored
  points, ids repeated within an answer, distances out of ascending order
  by more than ``UNSORTED_ULPS`` float32 units in the last place,
  and padding that does not match (id −1 ⇔ distance inf).  The first few
  offending answers are printed to standard error.
* ``topk_dist_err`` — largest relative gap between a reported distance
  and the float64 distance from the query to that id's stream point.
* ``topk_id_miss`` — share of the reference's top-k ids (float64 hash,
  tables and distances) missing from the served answers.

The top-k requests compared are 1,024 through ``query_topk`` after the
window, half around stored points and half around ingested points.
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from chipbench import streams
from chipbench.refs import lsh as ref_lsh
from chipbench.refs import sann as ref_sann

TABLE_MISMATCH_LIMIT = 0.05
DIST_ERR_LIMIT = 1e-4
ID_MISS_LIMIT = 0.1
UNSORTED_ULPS = 4
N_QUERIES = 1024
NEAR_SHARE = 0.5
QUERY_NOISE = 0.05


def _reference(run) -> dict:
    """Keep decisions, stored points, float64 codes and table replay of
    everything ingested so far (cached per ingested length)."""
    cached = getattr(run, "_sann_ref", None)
    if cached is not None and cached["chunks"] == run.chunks_ingested:
        return cached
    s = run.settings
    keep = ref_sann.keep_mask(run.svc_seed, int(s.get("ingest_salt", 0)), 0,
                              run.chunks_ingested, run.chunk,
                              ref_sann.keep_prob(s["n_max"], s["eta"]))
    idx = np.flatnonzero(keep)
    kept = run.rows(idx)
    cap = ref_sann.capacity(s["n_max"], s["eta"])
    owner = ref_sann.owners(len(idx), cap)
    nb = ref_sann.n_buckets(s["n_max"], s["eta"])
    params = ref_lsh.pstable_params(run.svc_seed, s["dim"], s["L"], s["k"],
                                    s["w"], nb)
    ref = {"chunks": run.chunks_ingested, "idx": idx, "kept": kept,
           "cap": cap, "owner": owner, "stored": kept[owner],
           "params": params, "n_buckets": nb,
           "rep": ref_sann.replay(ref_lsh.codes(params, kept), nb,
                                  s["bucket_cap"], cap)}
    run._sann_ref = ref
    return ref


def held_points(run) -> np.ndarray:
    return _reference(run)["stored"]


@functools.partial(jax.jit, static_argnames="cap")
def _ring_holds(tables, rows, codes, slots, cap):
    """Whether ring ``codes`` of row ``rows`` holds ``slots`` anywhere."""
    cols = codes[:, None] * cap + jnp.arange(cap, dtype=codes.dtype)
    return jnp.any(tables[rows[:, None], cols] == slots[:, None], axis=1)


@jax.jit
def _totals(tables, table_ptr):
    return jnp.sum(tables >= 0), jnp.sum(table_ptr)


def observe(run) -> dict:
    svc = run.svc
    ref = _reference(run)
    rep = ref["rep"]
    st = svc.snapshot()[0]
    n = len(ref["owner"])
    live, ptr = _totals(st.tables, st.table_ptr)
    obs = {"write_ptr": int(st.write_ptr), "n_seen": int(st.n_seen),
           "n_stored": int(st.n_stored), "valid": np.asarray(st.valid),
           "points": np.asarray(st.points[:n]),
           "stamps": np.asarray(st.stamps[:n]),
           "ring_holds": np.asarray(_ring_holds(
               st.tables, jnp.asarray(rep.l, jnp.int32),
               jnp.asarray(rep.col // rep.bucket_cap, jnp.int32),
               jnp.asarray(rep.slot, jnp.int32), cap=rep.bucket_cap)),
           "live_entries": int(live), "ptr_total": int(ptr)}
    r = streams.rng(run.seed, 13)
    qs = run.make_queries(N_QUERIES, NEAR_SHARE, QUERY_NOISE, r)
    ids, dists = (np.asarray(a) for a in svc.query_topk(qs))
    obs.update(queries=qs, ids=np.asarray(ids, np.int64),
               dists=np.asarray(dists, np.float64))
    return obs


def _show(what: str, rows, qs, ids, dists) -> None:
    for i in np.flatnonzero(rows)[:3]:
        print(f"topk {what}: request {i}: ids {ids[i].tolist()} "
              f"dists {dists[i].tolist()}", file=sys.stderr)


def _ulps(d):
    """``UNSORTED_ULPS`` float32 units in the last place of finite ``d``
    (0 at the inf pads): the reported distance is rounded after the
    ranking, so two near-equal neighbours can come out one unit apart."""
    fin = np.isfinite(d)
    return np.where(fin, UNSORTED_ULPS * np.spacing(
        np.where(fin, d, 0).astype(np.float32)).astype(np.float64), 0.0)


def _topk_numbers(run, ref, qs, ids, dists):
    """The exact counts, the distance gap and the id miss over every
    answer."""
    n_slots = len(ref["owner"])
    live = ids >= 0
    faults = {
        "unstored": (live & (ids >= n_slots)).any(axis=1),
        "repeated": None,
        "unsorted": (dists[:, 1:] < dists[:, :-1] - _ulps(dists[:, :-1])
                     ).any(axis=1),
        "pads": (live != np.isfinite(dists)).any(axis=1)}
    srt = np.sort(np.where(live, ids, -1 - np.arange(ids.shape[1])), axis=1)
    faults["repeated"] = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    for what, rows in faults.items():
        _show(what, rows, qs, ids, dists)
    q, i_s, d_s = qs, ids, dists
    lv = (i_s >= 0) & (i_s < n_slots)
    pts = ref["stored"][np.where(lv, i_s, 0)].astype(np.float64)
    true = np.sqrt(((pts - q[:, None, :].astype(np.float64)) ** 2).sum(-1))
    gap = np.abs(d_s - true)[lv] / np.maximum(true[lv], 1e-12)
    k = ids.shape[1]
    rids, _ = ref_sann.topk(ref["rep"], ref["stored"],
                            ref_lsh.codes(ref["params"], q), q, k)
    miss = sum(len(set(a[a >= 0]) - set(b[b >= 0]))
               for a, b in zip(rids, i_s))
    total = int(np.sum(rids >= 0))
    return [*((f"topk_{w}", float(r.sum()), 0.0) for w, r in faults.items()),
            ("topk_dist_err", float(gap.max()) if gap.size else 0.0,
             DIST_ERR_LIMIT),
            ("topk_id_miss", miss / max(total, 1), ID_MISS_LIMIT)]


def compare(run, obs) -> list:
    ref = _reference(run)
    s = run.settings
    n_kept = len(ref["idx"])
    cap = ref["cap"]
    n_slots = len(ref["owner"])
    valid = obs["valid"]
    bad = int(obs["write_ptr"] != n_kept % cap)
    bad += int(obs["n_stored"] != n_slots)
    bad += int(obs["n_seen"] != run.chunks_ingested * run.chunk)
    bad += int(obs["ptr_total"] != n_kept * s["L"])
    bad += int(np.sum(~valid[:n_slots])) + int(np.sum(valid[n_slots:]))
    pts = obs["points"][:n_slots]
    bad += int(np.sum(np.any(pts.view(np.uint32)
                             != ref["stored"].view(np.uint32), axis=1)))
    bad += int(np.sum(obs["stamps"][:n_slots] != ref["idx"][ref["owner"]]))
    table = float(np.mean(~obs["ring_holds"])) \
        if ref["rep"].slot.size else 0.0
    rep = ref["rep"]
    entries = s["L"] * rep.n_buckets * rep.bucket_cap
    print(f"state: {n_kept} points kept, {n_slots} stored of {cap} slots; "
          f"{obs['live_entries']} table entries live of {entries} "
          f"({100.0 * obs['live_entries'] / entries:.4f}%); the reference "
          f"evicted {rep.appends - rep.slot.size} of {rep.appends} ring "
          f"appends", file=sys.stderr)
    return [("state_mismatch", float(bad), 0.0),
            ("table_mismatch", table, TABLE_MISMATCH_LIMIT),
            *_topk_numbers(run, ref, obs["queries"], obs["ids"],
                           obs["dists"])]


def control(run, obs) -> list:
    """The reference in the program's place one precision step down: the
    p-stable projection on int8-quantized operands (the program's is one
    bfloat16 pass), and stored points and query distances in bfloat16
    (the program's are float32)."""
    ref = _reference(run)
    s = run.settings
    bf = lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float32)  # noqa
    stored16 = bf(ref["stored"])
    rep8 = ref_sann.replay(ref_lsh.codes(ref["params"], ref["kept"],
                                         "int8"),
                           ref["n_buckets"], s["bucket_cap"], ref["cap"])
    bad = int(np.sum(np.any(stored16 != ref["stored"], axis=1)))
    key = lambda r: (r.l * r.n_buckets + r.col // r.bucket_cap) \
        * (ref["cap"] + 1) + r.slot + 1  # noqa
    table = float(np.mean(~np.isin(key(ref["rep"]), key(rep8)))) \
        if ref["rep"].slot.size else 0.0
    qs = obs["queries"]
    ids, dists = ref_sann.topk(rep8, stored16,
                               ref_lsh.codes(ref["params"], qs, "int8"),
                               bf(qs), obs["ids"].shape[1])
    return [("state_mismatch", float(bad), 0.0),
            ("table_mismatch", table, TABLE_MISMATCH_LIMIT),
            *_topk_numbers(run, ref, qs, ids, dists)]
