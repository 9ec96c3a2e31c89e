"""Correctness of swakde-gmm200-w65k: what the timed ingest produced,
against the plain reference (`chipbench.refs`).

Compared, each with its limit:

* ``hash_mismatch`` — share of the (point, row) SRP codes of the
  window's points, as the timed path's compiled prepare program gives
  them, that differ from the float64 host hash.  A TPU's default matmul
  precision takes one bfloat16 pass, so a few sign bits near zero flip;
  the limit lies between that reading and the int8 control's.
* ``clock_error`` — the committed clock against the points ingested: exact.
* ``grid_l1_err`` — the committed EH grid, every cell read by the
  reference's DGIM decode, against the exact count of the window's points
  that carry its float64 code: Σ|estimate − exact| / Σ exact over all
  cells.  The configuration's guarantee bounds it: the exponential
  histogram keeps each cell within ``1 / (k/2 + 1)`` of the exact count
  of the codes it was given (``k = ⌈1/eh_eps⌉``; 1/6 at ``eh_eps`` = 0.1:
  the DGIM invariants keep at least ``k/2`` buckets of every size below
  the oldest), and each code that differs from the float64 one moves one
  count out of one cell into another, two units of the sum; so the limit
  is ``1 / (k/2 + 1) + 2 × hash_mismatch``'s limit.
* ``density_rel_err`` — 1,024 density answers through the service after
  the window, around points of the window, against the exact windowed KDE
  of the float64 hash: the configuration's guarantee ``kde_eps = 2·eh_eps + eh_eps²``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from chipbench import streams
from chipbench.refs import lsh as ref_lsh
from chipbench.refs import swakde as ref_swakde

HASH_MISMATCH_LIMIT = 0.004
N_QUERIES = 1024
QUERY_NOISE = 0.5


def held_points(run) -> np.ndarray:
    """The window: the last ``window`` ingested points."""
    n_seen = run.chunks_ingested * run.chunk
    return run.rows(np.arange(max(n_seen - int(run.settings["window"]), 0),
                              n_seen))


def _window_chunks(run) -> range:
    n = int(run.settings["window"]) // run.chunk
    return range(run.chunks_ingested - n, run.chunks_ingested)


def _codes_from_prep(prep) -> np.ndarray:
    """Per-point codes ``(C, L)`` out of a prepared chunk's sorted
    segments: sorted position p of row l lies in the segment whose
    ``[seg_first, seg_first + seg_len)`` holds it."""
    order = np.asarray(prep.order)
    seg_code = np.asarray(prep.seg_code)
    seg_len = np.asarray(prep.seg_len)
    seg_first = np.asarray(prep.seg_first)
    L, C = order.shape
    out = np.full((C, L), -1, np.int64)
    for l in range(L):
        live = seg_len[l] > 0
        n = seg_len[l][live]
        pos = (np.repeat(seg_first[l][live], n)
               + np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n))
        out[order[l][pos], l] = np.repeat(seg_code[l][live], n)
    return out


def observe(run) -> dict:
    """The timed path's output: the committed grid and clock, the codes its
    compiled prepare program gives the window's chunks, and 1,024 density
    answers through the service."""
    svc = run.svc
    codes = np.concatenate([_codes_from_prep(svc._prepare(jnp.asarray(
        run.stream.chunk_at(j)))) for j in _window_chunks(run)])
    r = streams.rng(run.seed, 11)
    qs = run.make_queries(N_QUERIES, 1.0, QUERY_NOISE, r)
    dens = np.asarray(svc.density(qs), np.float64)
    state = svc.snapshot()[0]
    return {"codes": codes, "queries": qs, "density": dens,
            "ts": np.asarray(state.ts), "num": np.asarray(state.num),
            "t": int(state.t)}


def _params(run):
    s = run.settings
    return ref_lsh.srp_params(run.svc_seed, s["dim"], s["L"], s["k"],
                              s["W"])


def _numbers(run, queries, t, codes, est, dens) -> list:
    """The four numbers for an output — the window's codes, its per-cell
    window counts and its densities — against the float64 reference."""
    s = run.settings
    N, W = int(s["window"]), int(s["W"])
    eps = float(s["eh_eps"])
    grid_limit = (1.0 / (math.ceil(1.0 / eps) // 2 + 1)
                  + 2 * HASH_MISMATCH_LIMIT)
    p = _params(run)
    ref_codes = ref_lsh.codes(p, held_points(run))
    ref_counts = ref_swakde.window_counts(ref_codes, W)
    t_ref = run.chunks_ingested * run.chunk
    ref_dens = ref_swakde.densities(
        ref_counts, ref_lsh.codes(p, queries), max(min(t_ref, N), 1))
    grid_err = np.abs(est - ref_counts).sum() / max(ref_counts.sum(), 1)
    dens_err = (np.abs(dens - ref_dens) / np.maximum(ref_dens, 1e-30)).max()
    # An output that lost points has no code to compare for them.
    hash_mm = (float(np.mean(codes != ref_codes))
               if codes.shape == ref_codes.shape else 1.0)
    return [("hash_mismatch", hash_mm, HASH_MISMATCH_LIMIT),
            ("clock_error", float(abs(t - t_ref)), 0.0),
            ("grid_l1_err", float(grid_err), grid_limit),
            ("density_rel_err", float(dens_err), 2 * eps + eps * eps)]


def compare(run, obs) -> list:
    est = ref_swakde.decode_cells(obs["ts"], obs["num"], obs["t"],
                                  int(run.settings["window"]))
    return _numbers(run, obs["queries"], obs["t"], obs["codes"], est,
                    obs["density"])


def control(run, obs) -> list:
    """The reference in the program's place one precision step down: the
    SRP projection on int8-quantized operands (the program's is one
    bfloat16 pass), with exact counts."""
    s = run.settings
    p = _params(run)
    codes = ref_lsh.codes(p, held_points(run), "int8")
    counts = ref_swakde.window_counts(codes, int(s["W"]))
    t = run.chunks_ingested * run.chunk
    dens = ref_swakde.densities(
        counts, ref_lsh.codes(p, obs["queries"], "int8"),
        max(min(t, int(s["window"])), 1))
    return _numbers(run, obs["queries"], t, codes,
                    counts.astype(np.float64), dens)
