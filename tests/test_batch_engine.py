"""Batched frontier engine: bit-identical hit sets and exact-eval counts vs
the host-mode pair-at-a-time reference, across indexes and all four
alignment distances; LB-cascade soundness; backend parity."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.batch_engine import BatchEngine
from repro.core.counter import CountedDistance
from repro.core.covertree import CoverTree
from repro.core.matching import LinearScanIndex, SubsequenceMatcher
from repro.core.refindex import MVReferenceIndex
from repro.core.refnet import ReferenceNet
from repro.distances import get

RNG = np.random.default_rng(7)


def _strings(n, l=10, alphabet=12, rng=RNG):
    motifs = rng.integers(0, alphabet, size=(8, l))
    data = motifs[rng.integers(0, 8, n)]
    m = rng.random((n, l)) < 0.2
    return np.where(m, rng.integers(0, alphabet, size=(n, l)), data)


def _series(n, l=10, rng=RNG):
    steps = rng.normal(scale=0.3, size=(n, l, 2))
    return np.cumsum(steps, axis=1) + rng.normal(scale=1.5, size=(n, 1, 2))


def _build(index, dist_name, data):
    dist = get(dist_name)
    if index == "refnet":
        return ReferenceNet(dist, data, eps_prime=1.0, num_max=4,
                            tight_bounds=True).build()
    if index == "covertree":
        return CoverTree(dist, data, eps_prime=1.0).build()
    if index == "mv":
        return MVReferenceIndex(dist, data, n_refs=4).build()
    return LinearScanIndex(dist, data).build()


# (index, distance): dtw is consistent-but-non-metric, so only linear scan
# may carry it (paper §5); the metric indexes cover the other three.
COMBOS = [
    ("refnet", "levenshtein"), ("refnet", "erp"), ("refnet", "frechet"),
    ("covertree", "levenshtein"), ("covertree", "erp"),
    ("mv", "levenshtein"), ("mv", "frechet"),
    ("linear", "dtw"), ("linear", "levenshtein"), ("linear", "erp"),
    ("linear", "frechet"),
]


@pytest.mark.parametrize("index,dist_name", COMBOS)
def test_engine_matches_host_hits_and_counts(index, dist_name):
    """The acceptance property: identical hit sets AND exact-evaluation
    counts vs sequential host-mode traversal, with fewer dispatches."""
    data = _strings(120) if get(dist_name).string else _series(120)
    idx = _build(index, dist_name, data)
    eps = 2.0 if get(dist_name).string else 1.0
    queries = np.stack([data[i] for i in (3, 17, 40, 77, 101)])

    idx.counter.reset()
    host_hits = [idx.range_query(q, eps) for q in queries]
    host_count, host_disp = idx.counter.count, idx.counter.dispatches

    idx.counter.reset()
    engine = BatchEngine(idx.counter)
    plans = [idx.range_query_plan(eps) for _ in queries]
    eng_hits = engine.run(plans, queries, eps)

    assert eng_hits == host_hits
    assert idx.counter.count == host_count
    # one dispatch per merged round, not one per (query, frontier)
    assert idx.counter.dispatches <= host_disp
    if host_disp > engine.rounds:
        assert idx.counter.dispatches < host_disp
    assert idx.counter.dispatches <= engine.rounds


@pytest.mark.parametrize("dist_name", ["dtw", "erp", "frechet", "levenshtein"])
def test_lower_bounds_never_exceed_exact(dist_name):
    dist = get(dist_name)
    assert dist.lower_bound is not None
    rng = np.random.default_rng(3)
    for lx, ly in [(4, 4), (3, 9), (10, 6)]:
        if dist.string:
            xs = rng.integers(0, 6, size=(32, lx))
            ys = rng.integers(0, 6, size=(32, ly))
        else:
            xs = rng.normal(size=(32, lx, 2)).astype(np.float32)
            ys = rng.normal(size=(32, ly, 2)).astype(np.float32)
        lxv = np.full(32, lx)
        lyv = np.full(32, ly)
        lbs = np.asarray(dist.lower_bound(xs, ys, lxv, lyv))
        from repro.distances import np_backend
        L = max(lx, ly)

        def pad(a):
            w = [(0, 0), (0, L - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
            return np.pad(a, w)

        exact = np.asarray(np_backend.batch_for(dist_name)(
            pad(xs), pad(ys), lxv, lyv))
        assert np.all(lbs <= exact + 1e-4), \
            f"{dist_name}: lb exceeded exact at {np.argmax(lbs - exact)}"


@pytest.mark.parametrize("index,dist_name",
                         [("refnet", "erp"), ("linear", "dtw"),
                          ("mv", "levenshtein")])
def test_lb_cascade_prunes_without_changing_hits(index, dist_name):
    data = _strings(100) if get(dist_name).string else _series(100)
    idx = _build(index, dist_name, data)
    eps = 2.0 if get(dist_name).string else 0.75
    queries = np.stack([data[i] for i in (5, 33, 66)])

    idx.counter.reset()
    plain = BatchEngine(idx.counter).run(
        [idx.range_query_plan(eps) for _ in queries], queries, eps)
    base_count = idx.counter.count

    idx.counter.reset()
    cascaded = BatchEngine(idx.counter, lb_cascade=True).run(
        [idx.range_query_plan(eps) for _ in queries], queries, eps)
    assert cascaded == plain
    assert idx.counter.count <= base_count
    assert idx.counter.lb_count > 0


def test_matcher_batched_step4_matches_legacy_loop():
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, 8, size=(60,)) for _ in range(3)]
    Q = rng.integers(0, 8, size=(24,))
    Q[4:14] = seqs[0][8:18]
    kw = dict(index="refnet", tight_bounds=True)
    batched = SubsequenceMatcher("levenshtein", 8, 1, **kw).build(seqs)
    legacy = SubsequenceMatcher("levenshtein", 8, 1, batched=False,
                                **kw).build(seqs)
    batched.reset_counter()
    legacy.reset_counter()
    hb = {(h.segment, h.window_idx) for h in batched.segment_hits(Q, 1.0)}
    hl = {(h.segment, h.window_idx) for h in legacy.segment_hits(Q, 1.0)}
    assert hb == hl
    assert batched.eval_count == legacy.eval_count
    assert batched.dispatch_count < legacy.dispatch_count
    # end-to-end query type I agrees too
    assert batched.query_range(Q, 1.0) == legacy.query_range(Q, 1.0)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_counter_backend_parity(backend):
    """jax / pallas backends produce the numpy backend's distances."""
    data = _strings(24, l=8)
    dist = get("levenshtein")
    ref = CountedDistance(dist, data, backend="numpy")
    alt = CountedDistance(dist, data, backend=backend)
    q = data[0]
    idxs = np.arange(len(data))
    np.testing.assert_allclose(ref.eval(q, idxs), alt.eval(q, idxs),
                               rtol=1e-4, atol=1e-4)
    # rectangular (q shorter than windows) bucket
    np.testing.assert_allclose(ref.eval(q[:6], idxs), alt.eval(q[:6], idxs),
                               rtol=1e-4, atol=1e-4)
    assert alt.dispatches == 2 and alt.count == 2 * len(data)


@pytest.mark.parametrize("name", ["levenshtein", "erp"])
def test_np_backend_matrix_parity(name):
    """np_backend.matrix_for matches the registry's jitted Distance.matrix."""
    from repro.distances import np_backend
    dist = get(name)
    rng = np.random.default_rng(5)
    if dist.string:
        xs = rng.integers(0, 6, size=(5, 7))
        ys = rng.integers(0, 6, size=(4, 7))
    else:
        xs = rng.normal(size=(5, 7, 2)).astype(np.float32)
        ys = rng.normal(size=(4, 7, 2)).astype(np.float32)
    got = np_backend.matrix_for(name)(xs, ys)
    want = np.asarray(dist.matrix(xs, ys))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # ragged lengths (padded rows) must agree with per-pair evaluation
    lx = np.array([7, 5, 6, 7, 4])
    ly = np.array([3, 7, 6, 5])
    got = np_backend.matrix_for(name)(xs, ys, lx, ly)
    batch = np_backend.batch_for(name)
    for i in range(5):
        for j in range(4):
            want_ij = batch(xs[i:i + 1], ys[j:j + 1],
                            lx[i:i + 1], ly[j:j + 1])[0]
            np.testing.assert_allclose(got[i, j], want_ij,
                                       rtol=1e-4, atol=1e-4)


def test_linear_scan_engine_single_round():
    """A linear-scan bucket is exactly one dispatch for ALL segments."""
    data = _strings(64)
    idx = LinearScanIndex(get("levenshtein"), data).build()
    queries = data[:7]
    idx.counter.reset()
    engine = BatchEngine(idx.counter)
    engine.run([idx.range_query_plan(2.0) for _ in queries], queries, 2.0)
    assert engine.rounds == 1
    assert idx.counter.dispatches == 1
    assert idx.counter.count == 7 * len(data)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1.0, 2.0, 4.0]))
    def test_engine_parity_property(seed, eps):
        rng = np.random.default_rng(seed)
        data = _strings(60, rng=rng)
        net = ReferenceNet(get("levenshtein"), data, eps_prime=1.0).build()
        queries = data[rng.integers(0, len(data), 4)]
        net.counter.reset()
        host = [net.range_query(q, eps) for q in queries]
        hc = net.counter.count
        net.counter.reset()
        eng = BatchEngine(net.counter).run(
            [net.range_query_plan(eps) for _ in queries], queries, eps)
        assert eng == host and net.counter.count == hc
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install .[dev])")
    def test_engine_parity_property():
        pass


# -- FleetBatchEngine: a round's screen and gather stages --------------------


def _reference_fleet_rounds(groups, eps, hook, batch):
    """Drive one batch of fleet plans with the screen and the gather done
    part by part in one loop; returns each round's evaluator operands, the
    LB tallies and the per-group, per-plan results."""
    from repro.core.batch_engine import VERDICT
    state, results = {}, [[None] * len(g.plans) for g in groups]
    for g, grp in enumerate(groups):
        for i, p in enumerate(grp.plans):
            try:
                state[(g, i)] = next(p)
            except StopIteration as stop:
                results[g][i] = stop.value or []
    rounds, lb_rows, lb_pruned = [], 0, 0
    while state:
        keys = sorted(state)
        cols = [[] for _ in range(5)]
        parts = []
        for g, i in keys:
            grp, fr = groups[g], state[(g, i)]
            m = fr.idxs.size
            keep, lbv = np.ones(m, bool), None
            if hook is not None and fr.kind == VERDICT and m:
                lbv = hook(grp.shard, fr.idxs, grp.queries[i],
                           int(grp.q_lens[i]))
                keep = lbv <= eps
                lb_rows += m
                lb_pruned += int(m - keep.sum())
            mk = int(keep.sum())
            for col, v in zip(cols, (
                    np.repeat(grp.queries[i][None], mk, 0),
                    grp.data[fr.idxs[keep]],
                    np.full(mk, int(grp.q_lens[i])),
                    np.full(mk, grp.data.shape[1]),
                    np.full(mk, eps if fr.kind == VERDICT else np.inf,
                            np.float32))):
                col.append(v)
            parts.append((keep, lbv))
        ops = [np.concatenate(c) for c in cols]
        rounds.append(ops)
        ds = np.asarray(batch(*ops[:4]), np.float32) if len(ops[0]) else []
        off = 0
        for (g, i), (keep, lbv) in zip(keys, parts):
            out = np.empty(keep.size, np.float32)
            if lbv is not None:
                out[~keep] = lbv[~keep]
            out[keep] = ds[off:off + keep.sum()]
            off += keep.sum()
            try:
                state[(g, i)] = groups[g].plans[i].send(out)
            except StopIteration as stop:
                del state[(g, i)]
                results[g][i] = stop.value or []
    return rounds, lb_rows, lb_pruned, results


@pytest.mark.parametrize("dist_name", ["erp", "frechet"])
@pytest.mark.parametrize("tier", ["off", "envelope"])
def test_fleet_round_screen_and_gather_stages(tier, dist_name):
    """Screening every part, then gathering every part, sends the
    evaluator the rows, order and ε of the one-loop round, and tallies
    the same LB counts."""
    from repro.core.batch_engine import FleetBatchEngine, ShardPlans
    from repro.distances import bounds, np_backend
    rng = np.random.default_rng(5)
    data = _series(90, rng=rng)
    shards = np.array_split(data, 3)
    qs = data[[4, 33, 71]] + rng.normal(scale=0.05, size=(3, 10, 2))
    eps = 1.0
    envs = [bounds.build_envelopes(s) for s in shards]

    def hook(shard, idxs, q, q_len):
        e = envs[shard].take(idxs)
        return bounds.lb_envelope_rows(
            dist_name, np.repeat(q[None], len(idxs), 0),
            np.full(len(idxs), q_len), e.lo, e.hi, e.mass)

    hook = hook if tier == "envelope" else None
    batch = np_backend.batch_for(dist_name)
    nets = [ReferenceNet(get(dist_name), s, eps_prime=1.0, num_max=4,
                         tight_bounds=True).build() for s in shards]

    def groups():
        return [ShardPlans(shard=si, data=net.data,
                           plans=[net.range_query_plan(eps) for _ in qs],
                           queries=qs, q_lens=np.full(len(qs), 10))
                for si, net in enumerate(nets)]

    seen = []

    def evaluate(xs, ys, lx, ly, eps_rows):
        seen.append([xs, ys, lx, ly, eps_rows])
        return batch(xs, ys, lx, ly), 0

    engine = FleetBatchEngine(evaluate, fused=True, lb=hook)
    got = engine.run(groups(), eps)
    rounds, lb_rows, lb_pruned, want = _reference_fleet_rounds(
        groups(), eps, hook, batch)
    assert got == want
    assert (engine.lb_rows, engine.lb_pruned) == (lb_rows, lb_pruned)
    assert (lb_rows > 0) == (tier == "envelope")
    assert engine.rounds == len(rounds)
    evaluated = [r for r in rounds if len(r[0])]
    assert len(seen) == len(evaluated)
    for a, b in zip(seen, evaluated):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert engine.exact_evals + engine.verdict_evals == sum(
        len(r[0]) for r in rounds)
