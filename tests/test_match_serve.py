"""Type I subsequence matching on the served fleet path.

A retriever built with ``lam`` on the fleet serves match requests: the
query's segments as one many-row range plan a shard, the §7 candidate
envelope of their hits grouped by start pair, one band-mode wavefront row
per start pair.  Its answer must equal, pair for pair and distance for distance, the
host ``SubsequenceMatcher`` and a brute-force reference written here: every
segment against every window, the §7 envelope of each hit, one DP per
candidate."""

import itertools

import numpy as np
import pytest

from repro.retrieval import RetrievalConfig, Retriever

LAM = 8
SEEDS = (3, 11, 2**31 + 5)


def lev(x, y) -> int:
    """The textbook edit-distance DP."""
    prev = list(range(len(y) + 1))
    for i, a in enumerate(x, 1):
        cur = [i]
        for j, b in enumerate(y, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (a != b)))
        prev = cur
    return prev[-1]


def brute_force(seqs, Q, lam, l0, eps):
    """Steps 1-5 of the paper's §7, one loop at a time."""
    l = lam // 2
    wins = [(s, c) for s, x in enumerate(seqs)
            for c in range(0, len(x) - l + 1, l)]
    segs = [(a, ln) for ln in range(max(1, l - l0), l + l0 + 1)
            for a in range(len(Q) - ln + 1)] if len(Q) >= lam else []
    cands = set()
    for (a, ln), (s, c) in itertools.product(segs, wins):
        if lev(Q[a:a + ln], seqs[s][c:c + l]) > eps:
            continue
        b, nx = a + ln, len(seqs[s])
        for qs, qe, xs, xe in itertools.product(
                range(max(0, a - l - l0), a + 1),
                range(b, min(len(Q), b + l + l0) + 1),
                range(max(0, c - l), c + 1),
                range(c + l, min(nx, c + lam) + 1)):
            if min(qe - qs, xe - xs) >= lam \
                    and abs((xe - xs) - (qe - qs)) <= l0:
                cands.add((s, xs, xe - xs, qs, qe - qs))
    out = []
    for s, xs, xl, qs, ql in sorted(cands):
        d = lev(seqs[s][xs:xs + xl], Q[qs:qs + ql])
        if d <= eps:
            out.append((s, xs, xl, qs, ql, float(d)))
    return out


def rows(pairs):
    return [(*p.key(), p.distance) for p in pairs]


def data(seed):
    """Three short sequences over 4 letters, and a query cut from one of
    them with a few letters changed."""
    r = np.random.default_rng(seed)
    seqs = [r.integers(0, 4, int(n)).astype(np.int32)
            for n in r.integers(20, 36, 3)]
    Q = seqs[1][2:20].copy()
    Q[r.integers(0, len(Q), 2)] = r.integers(0, 4, 2)
    return seqs, Q


_BUILT = {}


def built(seed, l0):
    """The fleet and the host matcher over one seed's data, built once."""
    key = (seed, l0)
    if key not in _BUILT:
        seqs, Q = data(seed)
        kw = dict(lam=LAM, lambda0=l0)
        fleet = Retriever.build(RetrievalConfig(
            "levenshtein", execution="fleet", workers=2,
            kernel_backend="pallas", kernel_exec="scan", tight_bounds=True,
            **kw), seqs)
        host = Retriever.build(RetrievalConfig(
            "levenshtein", execution="host", **kw), seqs)
        _BUILT[key] = seqs, Q, fleet, host
    return _BUILT[key]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("l0", [0, 2])
@pytest.mark.parametrize("eps", [0.0, 1.0, 2.0])
def test_served_matches_equal_host_and_brute_force(seed, l0, eps):
    seqs, Q, fleet, host = built(seed, l0)
    want = brute_force(seqs, Q, LAM, l0, eps)
    assert rows(host.query(Q).range(eps).hits) == want
    assert rows(fleet.query(Q).range(eps).hits) == want
    engine = fleet.serve(eps)
    req = engine.submit(Q, now=0.0)
    while not req.done:
        engine.tick(now=0.0)
    assert rows(req.hits) == want
    st = engine.engine_stats()
    assert st["match_requests"] == 1 and st["match_pairs"] == len(want)
    n_segs = (2 * l0 + 1) * len(Q) - sum(range(-l0, l0 + 1)) \
        - (2 * l0 + 1) * (LAM // 2 - 1)
    assert st["segment_plans"] == 2 * n_segs
    assert st["candidates"] >= len(want)
    assert st["verify_rows"] <= st["candidates"]
    assert req.rounds >= 1 + (st["verify_rows"] > 0)


def test_query_shorter_than_lam_matches_nothing():
    seqs, Q, fleet, _ = built(SEEDS[0], 2)
    assert fleet.query(Q[:LAM - 1]).range(2.0).hits == []
    engine = fleet.serve(2.0)
    req = engine.submit(Q[:LAM - 1], now=0.0)
    engine.tick(now=0.0)
    assert req.done and req.hits == []
    assert engine.engine_stats()["segment_plans"] == 0


def test_window_request_beside_a_match_request():
    """One engine, one tick cadence: a window request (``match=False``)
    answers window ids, the match request its pairs."""
    seqs, Q, fleet, _ = built(SEEDS[1], 2)
    scope = fleet._engine.scope
    win = scope.windows[5]
    engine = fleet.serve(1.0)
    m = engine.submit(Q, now=0.0)
    w = engine.submit(win, now=0.0, match=False)
    ticks = 0
    while not (m.done and w.done):
        engine.tick(now=0.0)
        ticks += 1
    assert rows(m.hits) == brute_force(seqs, Q, LAM, 2, 1.0)
    assert w.hits == [i for i, x in enumerate(scope.windows)
                      if lev(x, win) <= 1.0]
    assert not w.match and m.match and ticks >= 2


def test_batch_of_match_queries():
    seqs, Q, fleet, _ = built(SEEDS[2], 0)
    got = fleet.batch([Q, Q[3:], Q[:4]]).range(1.0).hits
    assert [rows(g) for g in got] == [
        brute_force(seqs, q, LAM, 0, 1.0) for q in (Q, Q[3:], Q[:4])]


def test_fleet_matching_needs_a_wavefront_distance():
    with pytest.raises(ValueError, match="band mode"):
        RetrievalConfig("euclidean", lam=8, execution="fleet", workers=2)
    RetrievalConfig("erp", lam=8, execution="fleet", workers=2)


def test_match_requests_need_a_scope():
    seqs, Q, fleet, _ = built(SEEDS[0], 0)
    windows = Retriever.build(RetrievalConfig(
        "levenshtein", execution="fleet", workers=2,
        kernel_backend="pallas", kernel_exec="scan"),
        fleet._engine.scope.windows)
    with pytest.raises(ValueError, match="MatchScope"):
        windows.serve(1.0).submit(Q, match=True)
    with pytest.raises(ValueError, match="served path"):
        fleet.query(Q).via("host").range(1.0)


def test_erp_matching_with_the_envelope_tier():
    """A 2-D series fleet under ERP screens its segment rows with the
    envelope tier, one query row at a time, and answers the host
    matcher's pairs (distances to float32 rounding)."""
    r = np.random.default_rng(7)
    seqs = [np.cumsum(r.normal(scale=0.3, size=(int(n), 2)), axis=0)
            .astype(np.float32) for n in r.integers(24, 32, 3)]
    Q = seqs[2][3:21] + r.normal(scale=0.05, size=(18, 2)).astype(
        np.float32)
    kw = dict(lam=LAM, lambda0=1)
    fleet = Retriever.build(RetrievalConfig(
        "erp", execution="fleet", workers=2, kernel_backend="pallas",
        kernel_exec="scan", tight_bounds=True, lb_cascade="envelope", **kw),
        seqs)
    host = Retriever.build(RetrievalConfig("erp", execution="host", **kw),
                           seqs)
    engine = fleet.serve(1.5)
    req = engine.submit(Q, now=0.0)
    while not req.done:
        engine.tick(now=0.0)
    want = host.query(Q).range(1.5).hits
    assert [p.key() for p in req.hits] == [p.key() for p in want] != []
    np.testing.assert_allclose([p.distance for p in req.hits],
                               [p.distance for p in want], rtol=1e-5)
    st = engine.engine_stats()
    assert 0 < st["lb_pruned"] < st["lb_rows"]
