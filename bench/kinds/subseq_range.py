"""Type I subsequence matching (arXiv:1208.0286 §3.2, §7): a request is a
query sequence, its answer every pair of a database subsequence ``SX``
and a query subsequence ``SQ`` with ``|SX|, |SQ| >= lam``,
``||SX| - |SQ|| <= lambda0`` and ``d(SX, SQ) <= eps`` inside the §7
candidate envelope of a (segment, window) hit, each with its distance.

The contract is ``window_range.py``'s.  Here:

* the data are ``n_seqs`` sequences of ``length`` residues, this file's
  copy of the program's ``protein_sequences``: 20-residue blocks, 70 % a
  motif of ``n_motifs`` families with 10 % of residues replaced, 30 %
  random linkers; the pool is ``pool`` fragments of ``queries.length``
  residues cut at random from the database, ``queries.subst`` of their
  residues substituted.  Everything comes from ``gen.rng(seed, ...)``.
* an answer is an ``(n, 6)`` array of ``(seq_id, x_start, x_len,
  q_start, q_len, distance)`` rows.
* the reference is the paper's §7 in plain numpy: the sequences cut into
  windows of ``l = lam // 2``; every query segment of ``l - lambda0 ..
  l + lambda0`` residues against every window, by brute force (one
  Levenshtein DP per segment start and window gives all its segment
  lengths, the textbook DP of ``bench/distances/levenshtein.py`` read at
  every query prefix); the §7 envelope of each hit; one DP per start
  pair, read at every candidate end.  It runs after the window, one
  query at a time on ``threads`` threads.
* ``gaps``: ``|d - eps|`` of every pair in exactly one of the served and
  the reference answers, or in both with another distance; a served pair
  outside the reference's candidates takes its distance from a direct DP
  (``levenshtein.reference``), a malformed or repeated one the largest
  float.
* ``kernel_work`` counts the real filter rows (segment against window)
  and the real verify rows (one per start pair) dispatched in the window,
  at their real lengths, from ``engine_stats()``; the bytes also count
  the band of end cells each verify row emits, at 4 bytes a cell, in
  place of a row's distance and verdict.  Padding is not counted.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import byname, costs, gen

#: answer columns
COLS = 6


def sequences(n_seqs: int, seed: int, *, length: int = 400,
              alphabet: int = 20, n_motifs: int = 64, block: int = 20,
              motif_share: float = 0.7, mutation: float = 0.1
              ) -> np.ndarray:
    """``(n_seqs, length)`` residues: motif blocks and random linkers."""
    r = gen.rng(seed, 0)
    motifs = r.integers(0, alphabet, size=(n_motifs, block))
    n_blocks = -(-length // block)
    fam = r.integers(0, n_motifs, size=(n_seqs, n_blocks))
    is_motif = r.random((n_seqs, n_blocks)) < motif_share
    blocks = np.where(is_motif[..., None], motifs[fam],
                      r.integers(0, alphabet, (n_seqs, n_blocks, block)))
    mut = is_motif[..., None] & (r.random(blocks.shape) < mutation)
    blocks = np.where(mut, r.integers(0, alphabet, blocks.shape), blocks)
    return blocks.reshape(n_seqs, -1)[:, :length].astype(np.int32)


def fragments(data: np.ndarray, n: int, seed: int, *, length: int,
              subst: float = 0.0, alphabet: int = 20) -> np.ndarray:
    """``n`` fragments of ``length`` residues cut from random sequences at
    random starts, ``subst`` of their residues substituted."""
    r = gen.rng(seed, 1)
    seq = r.integers(0, len(data), n)
    start = r.integers(0, data.shape[1] - length + 1, n)
    q = data[seq[:, None], start[:, None] + np.arange(length)].copy()
    flips = r.random(q.shape) < subst
    q[flips] = r.integers(0, alphabet, int(flips.sum()))
    return q


def make(cell, seed: int):
    d = dict(cell.config["data"])
    data = sequences(d.pop("n_seqs"), seed, **d)
    t = cell.traffic
    pool = fragments(data, int(t["pool"]), seed, **t["queries"])
    return data, pool


def _scope(cell):
    c = cell.config
    lam, l0 = int(c["lam"]), int(c["lambda0"])
    return lam, l0, lam // 2


def warm(cell, data, pool, eps: float) -> int:
    """One dispatch of every batch class the window can reach: filter
    rounds (segments of ``l + lambda0`` against windows of ``l``) from 8
    rows to ``warm_rows``, and verify dispatches (``lam + l`` database
    residues against ``lam + l + lambda0`` query residues, the band mode)
    from 8 rows to ``verify_warm_rows``."""
    from repro.kernels import dispatch
    lam, l0, l = _scope(cell)
    name, t = cell.distance, cell.traffic
    x = np.resize(pool.ravel(), (int(t["warm_rows"]), l + l0))
    y = np.resize(data.ravel(), (int(t["warm_rows"]), l))
    vx = np.resize(data.ravel(), (int(t["verify_warm_rows"]), lam + l))
    vy = np.resize(pool.ravel(), (int(t["verify_warm_rows"]), lam + l + l0))
    n = 0
    for top, call in (
            (len(x), lambda r: dispatch.packed_batch(
                name, x[:r], y[:r], np.full(r, l + l0), np.full(r, l),
                eps=eps, trim=False)),
            (len(vx), lambda r: dispatch.packed_band(
                name, vx[:r], vy[:r], np.full(r, lam + l),
                np.full(r, lam + l + l0), lam=lam, lambda0=l0, eps=eps))):
        rows = 8
        while True:
            call(min(rows, top))
            n += 1
            if rows >= top:
                break
            rows *= 2
    return n


def serve(fleet, eps: float):
    return fleet.serve(eps)


def submit(engine, query, at: float):
    return engine.submit(query, now=at)


def answer(request) -> np.ndarray:
    return np.array([(p.seq_id, p.x_start, p.x_len, p.q_start, p.q_len,
                      p.distance) for p in request.hits],
                    np.float64).reshape(-1, COLS)


def size(answer_) -> int:
    return len(answer_)


# -- the reference --------------------------------------------------------

def _lev_rows(x: np.ndarray, y: np.ndarray):
    """The Levenshtein DP of each row of ``x`` against the same row of
    ``y`` (either broadcast), one row of the table at a time: yields
    ``(i, D[:, i, :])`` for ``i = 1 .. x.shape[1]``.  A row of the table is
    a running minimum (``bench/distances/levenshtein.py``)."""
    n = max(len(x), len(y))
    ly = y.shape[1]
    ar = np.arange(ly + 1, dtype=np.int32)
    prev = np.broadcast_to(ar, (n, ly + 1))
    t = np.empty((n, ly + 1), np.int32)
    for i in range(1, x.shape[1] + 1):
        cost = (y != x[:, i - 1:i]).astype(np.int32)
        t[:, 0] = i
        np.minimum(prev[:, 1:] + 1, prev[:, :-1] + cost, out=t[:, 1:])
        prev = np.minimum.accumulate(t - ar, axis=1) + ar
        yield i, prev


def _codes(keys: np.ndarray, radix: int) -> np.ndarray:
    """One int64 per ``(seq, xs, x_len, qs, q_len)`` row."""
    code = np.zeros(len(keys), np.int64)
    for k in range(5):
        code = code * radix + keys[:, k].astype(np.int64)
    return code


def _one(q: np.ndarray, data: np.ndarray, lam: int, l0: int, eps: float):
    """The §7 answer of one query: every candidate pair with its distance."""
    l = lam // 2
    n_seqs, length = data.shape
    nq = len(q)
    per = length // l
    wins = data[:, :per * l].reshape(-1, l)
    # step 4: (segment start, length) x window hits, by brute force
    hits = []
    for a in range(nq - (l - l0) + 1):
        seg = q[a:a + l + l0][None]
        for i, row in _lev_rows(seg, wins):
            if l - l0 <= i and a + i <= nq:
                w = np.flatnonzero(row[:, l] <= eps)
                hits.append(np.stack([np.full(len(w), a), np.full(
                    len(w), i), w], axis=1))
    hits = np.concatenate(hits) if hits else np.zeros((0, 3), np.int64)
    # step 5: the envelope of every hit, as (seq, xs, x_len, qs, q_len)
    dx, dq = np.arange(l + 1), np.arange(l + l0 + 1)
    xl, dl = np.arange(lam, lam + l + 1), np.arange(-l0, l0 + 1)
    grid = np.stack(np.meshgrid(dx, dq, xl, dl, indexing="ij"),
                    -1).reshape(-1, 4)
    cands = []
    for h in np.array_split(hits, max(1, len(hits) // 8)):
        if not len(h):
            continue
        a, ln, w = (h[:, k:k + 1] for k in range(3))
        s, c = w // per, (w % per) * l
        xs, qs = c - grid[:, 0], a - grid[:, 1]
        xlen, qlen = grid[:, 2], grid[:, 2] + grid[:, 3]
        xe, qe, b = xs + xlen, qs + qlen, a + ln
        ok = ((xs >= 0) & (qs >= 0) & (qlen >= lam)
              & (xe >= c + l) & (xe <= np.minimum(length, c + lam))
              & (qe >= b) & (qe <= np.minimum(nq, b + l + l0)))
        r, g = np.nonzero(ok)
        cands.append(np.stack([s[r, 0], xs[r, g], xlen[g], qs[r, g],
                               qlen[g]], axis=1))
    radix = max(length, nq) + 1
    keys = np.concatenate(cands) if cands else np.zeros((0, 5), np.int64)
    codes, first = np.unique(_codes(keys, radix), return_index=True)
    keys = keys[first]
    # one DP per start pair, read at each of its candidates' ends
    starts, group = np.unique(keys[:, [0, 1, 3]], axis=0,
                              return_inverse=True)
    group = group.ravel()
    dist = np.full(len(keys), np.inf)
    if len(keys):
        xw, yw = lam + l, lam + l + l0
        x = np.pad(data, ((0, 0), (0, xw)), constant_values=-1)[
            starts[:, :1], starts[:, 1:2] + np.arange(xw)]
        y = np.pad(q, (0, yw), constant_values=-2)[
            starts[:, 2:3] + np.arange(yw)]
        for i, row in _lev_rows(x, y):
            at = np.flatnonzero(keys[:, 2] == i)
            dist[at] = row[group[at], keys[at, 4]]
    return {"codes": codes, "keys": keys, "dist": dist, "radix": radix,
            "q": q, "data": data, "lam": lam, "lambda0": l0}


def reference(cell, data, queries, threads: int):
    lam, l0, _ = _scope(cell)
    eps = float(cell.traffic["eps"])
    with ThreadPoolExecutor(max(1, threads)) as pool:
        return list(pool.map(lambda q: _one(q, data, lam, l0, eps),
                             queries))


def control(cell, data, queries, refs, eps: float, threads: int):
    kind = cell.config["control"]
    if kind != "open_ball":
        raise KeyError(f"unknown control {kind!r}")
    return [np.concatenate([r["keys"], r["dist"][:, None]], axis=1)[
        r["dist"] < eps] for r in refs]


def _direct(ref, key) -> float:
    """Distance of a served pair that is no candidate of the reference;
    the largest float where it names no subsequence pair."""
    s, xs, xlen, qs, qlen = (int(v) for v in key)
    data, q = ref["data"], ref["q"]
    if not (0 <= s < len(data) and 0 <= xs and xs + xlen <= data.shape[1]
            and 0 <= qs and qs + qlen <= len(q) and xlen and qlen):
        return np.finfo(float).max
    lev = byname.module("distances", "levenshtein").reference
    return float(lev(data[s, xs:xs + xlen], q[None, qs:qs + qlen])[0])


def gaps(answer_, ref, eps: float) -> np.ndarray:
    served = np.asarray(answer_, np.float64).reshape(-1, COLS)
    keys, d_served = served[:, :5].astype(np.int64), served[:, 5]
    codes = _codes(keys, ref["radix"])
    uniq, first = np.unique(codes, return_index=True)
    out = [np.full(len(codes) - len(uniq), np.finfo(float).max)]
    keys, d_served, codes = keys[first], d_served[first], codes[first]
    at = np.searchsorted(ref["codes"], codes)
    known = at < len(ref["codes"])
    known[known] = ref["codes"][at[known]] == codes[known]
    d_ref = np.full(len(codes), np.nan)
    d_ref[known] = ref["dist"][at[known]]
    for i in np.flatnonzero(~known):
        d_ref[i] = _direct(ref, keys[i])
    wrong = (d_ref > eps) | (d_ref != d_served)
    out.append(np.abs(d_ref[wrong] - eps))
    want = ref["codes"][ref["dist"] <= eps]
    missed = ~np.isin(want, codes)
    out.append(np.abs(ref["dist"][ref["dist"] <= eps][missed] - eps))
    return np.concatenate(out)


def kernel_work(run):
    """Operations and bytes of the filter and verify rows, at their real
    lengths.  A distance's per-row counts are linear in the row's cells
    (operations) and in its elements (bytes), so the engine's sums of
    ``len_x * len_y`` and ``len_x + len_y`` give them exactly."""
    e = run.window.engine
    if not e.get("filter_rows") and not e.get("verify_rows"):
        return None
    dist, d = run.cell.distance, 1
    per_cell = costs.row_ops(dist, 1, 1, d)
    per_row = costs.row_bytes(dist, 0, 0, d)
    per_elem = costs.row_bytes(dist, 1, 0, d) - per_row
    ops = per_cell * (e["filter_cells"] + e["verify_cells"])
    nbytes = (per_elem * (e["filter_elems"] + e["verify_elems"])
              + per_row * e["filter_rows"] + 4 * e["verify_band_cells"])
    return ops, nbytes
