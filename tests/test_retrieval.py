"""The `repro.retrieval` facade: config validation, registry round-trips,
deprecation shims, and — the acceptance property — hit-set and
``{query, build}`` eval-count parity between facade calls and the direct
PR-1/2/3 code paths on all four alignment distances."""

import json
import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import _deprecation
from repro.core.counter import CountedDistance
from repro.core.matching import SubsequenceMatcher
from repro.core.refnet import ReferenceNet
from repro.distances import base as dist_base
from repro.distances import get
from repro.launch.elastic import ElasticIndex
from repro.retrieval import (
    RetrievalConfig, Retriever, distance_names, index_names,
    register_distance, register_index, resolve_index, unregister_distance,
    unregister_index)

RNG = np.random.default_rng(7)

#: the paper's four alignment distances; dtw is consistent-but-non-metric,
#: so it rides the linear-scan filter (paper §5)
DISTANCES = [("levenshtein", "refnet"), ("erp", "refnet"),
             ("frechet", "refnet"), ("dtw", "linear")]


def _strings(n, l=10, alphabet=12, rng=RNG):
    motifs = rng.integers(0, alphabet, size=(8, l))
    data = motifs[rng.integers(0, 8, n)]
    m = rng.random((n, l)) < 0.2
    return np.where(m, rng.integers(0, alphabet, size=(n, l)), data)


def _series(n, l=10, rng=RNG):
    steps = rng.normal(scale=0.3, size=(n, l, 2))
    return np.cumsum(steps, axis=1) + rng.normal(scale=1.5, size=(n, 1, 2))


def _seqs_and_query(dist_name, rng):
    """A tiny matching corpus with a planted fragment."""
    if get(dist_name).string:
        seqs = [rng.integers(0, 8, size=(60,)) for _ in range(3)]
        Q = rng.integers(0, 8, size=(24,))
    else:
        seqs = [np.cumsum(rng.normal(scale=0.3, size=(60, 2)), axis=0)
                for _ in range(3)]
        Q = np.cumsum(rng.normal(scale=0.3, size=(24, 2)), axis=0)
    Q[4:14] = seqs[0][8:18]
    return seqs, Q


def _quiet(fn, *a, **kw):
    """Run a legacy constructor without deprecation noise."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*a, **kw)


# -- config validation --------------------------------------------------------

def test_config_rejects_unknown_names():
    with pytest.raises(KeyError, match="unknown distance"):
        RetrievalConfig("nope")
    with pytest.raises(KeyError, match="unknown index kind"):
        RetrievalConfig("levenshtein", index="nope")


@pytest.mark.parametrize("kw,msg", [
    (dict(execution="turbo"), "execution"),
    (dict(backend="cuda"), "backend"),
    (dict(lam=1), "lam"),
    (dict(lam=8, lambda0=4), "lambda0"),
    (dict(lam=8, index="embedding"), "embedding"),
    (dict(execution="fleet"), "workers"),
    (dict(execution="fleet", workers=2, lam=8, index="embedding"),
     "matching pipeline"),
    (dict(execution="fleet", workers=2, index="mv"), "refnet"),
    (dict(execution="fleet", workers=2, lb_cascade=True), "lb_cascade"),
    (dict(workers=("a",)), "fleet"),
])
def test_config_validation_errors(kw, msg):
    with pytest.raises(ValueError, match=msg):
        RetrievalConfig("levenshtein", **kw)


def test_config_rejects_nonmetric_on_indexed_path():
    with pytest.raises(ValueError, match="not a metric"):
        RetrievalConfig("dtw", lam=8, index="refnet")
    # the consistency-only path is fine (paper §5)
    RetrievalConfig("dtw", lam=8, index="linear")


def test_config_worker_count_normalization():
    cfg = RetrievalConfig("levenshtein", execution="fleet", workers=3)
    assert cfg.workers == ("w0", "w1", "w2")


def test_config_json_round_trip():
    cfg = RetrievalConfig("erp", lam=12, lambda0=2, index="covertree",
                          execution="host", tight_bounds=True,
                          eps_prime=0.5)
    again = RetrievalConfig.from_json(cfg.to_json())
    assert again == cfg
    # a Distance *instance* serializes by registry name
    inst = RetrievalConfig(get("frechet"), eps_prime=0.25)
    d = json.loads(inst.to_json())
    assert d["distance"] == "frechet"
    assert RetrievalConfig.from_json(inst.to_json()).dist is get("frechet")
    with pytest.raises(ValueError, match="unknown RetrievalConfig fields"):
        RetrievalConfig.from_dict({"distance": "erp", "warp": 9})


# -- registries ---------------------------------------------------------------

def test_distance_registry_round_trip():
    lev = get("levenshtein")

    @register_distance
    def _make():
        return dist_base.Distance(
            name="test_lev_clone", batch=lev.batch, matrix=lev.matrix,
            metric=True, consistent=True, string=True, variable_length=True)

    try:
        assert "test_lev_clone" in distance_names()
        data = _strings(40)
        r = Retriever.build(RetrievalConfig("test_lev_clone"), data)
        ref = Retriever.build(RetrievalConfig("levenshtein"), data)
        q = data[3]
        assert r.query(q).range(2.0).hits == ref.query(q).range(2.0).hits
    finally:
        unregister_distance("test_lev_clone")
    assert "test_lev_clone" not in distance_names()


def test_index_registry_round_trip():
    @register_index("test_linear_alias", requires_metric=False)
    def _make(dist, data, *, counter=None, **kw):
        from repro.core.matching import LinearScanIndex
        return LinearScanIndex(dist, data, counter=counter)

    try:
        assert "test_linear_alias" in index_names()
        assert resolve_index("test_linear_alias").factory is _make
        data = _strings(40)
        r = Retriever.build(
            RetrievalConfig("levenshtein", index="test_linear_alias"), data)
        ref = Retriever.build(
            RetrievalConfig("levenshtein", index="linear"), data)
        q = data[5]
        a, b = r.query(q).range(2.0), ref.query(q).range(2.0)
        assert a.hits == b.hits
        assert a.stats["query"] == b.stats["query"]
        # the custom kind also resolves inside the matching pipeline
        seqs, Q = _seqs_and_query("levenshtein", np.random.default_rng(0))
        rm = Retriever.build(
            RetrievalConfig("levenshtein", lam=8,
                            index="test_linear_alias"), seqs)
        rl = Retriever.build(
            RetrievalConfig("levenshtein", lam=8, index="linear"), seqs)
        assert rm.query(Q).range(1.0).hits == rl.query(Q).range(1.0).hits
    finally:
        unregister_index("test_linear_alias")
    assert "test_linear_alias" not in index_names()
    with pytest.raises(KeyError):
        resolve_index("test_linear_alias")


def test_register_index_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        register_index("refnet")(lambda *a, **k: None)


# -- deprecation shims --------------------------------------------------------

def test_legacy_constructors_warn():
    seqs, _ = _seqs_and_query("levenshtein", np.random.default_rng(1))
    with pytest.warns(DeprecationWarning, match="SubsequenceMatcher"):
        SubsequenceMatcher("levenshtein", 8)
    with pytest.warns(DeprecationWarning, match="ElasticIndex"):
        ElasticIndex("levenshtein", _strings(20), ["a", "b"])
    from repro.core.embedding_retrieval import EmbeddingRetriever
    with pytest.warns(DeprecationWarning, match="EmbeddingRetriever"):
        EmbeddingRetriever(np.eye(6, dtype=np.float32), meta=[None] * 6)


def test_facade_construction_does_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        seqs, Q = _seqs_and_query("levenshtein", np.random.default_rng(2))
        Retriever.build(RetrievalConfig("levenshtein", lam=8), seqs)
        Retriever.build(
            RetrievalConfig("levenshtein", execution="fleet", workers=2),
            _strings(30))


def test_legacy_shims_still_work():
    """The old constructors stay functional (thin shims over the same
    engines the facade drives)."""
    seqs, Q = _seqs_and_query("levenshtein", np.random.default_rng(3))
    m = _quiet(SubsequenceMatcher, "levenshtein", 8, 1).build(seqs)
    assert m.query_range(Q, 1.0)
    # distance arg accepts an instance OR a name on every path now
    m2 = _quiet(SubsequenceMatcher, get("levenshtein"), 8, 1).build(seqs)
    assert m2.query_range(Q, 1.0) == m.query_range(Q, 1.0)
    data = _strings(30)
    f1 = _quiet(ElasticIndex, "levenshtein", data, ["a", "b"])
    f2 = _quiet(ElasticIndex, get("levenshtein"), data, ["a", "b"])
    assert f1.range_query(data[0], 2.0) == f2.range_query(data[0], 2.0)


# -- facade vs direct paths: the acceptance property -------------------------

@pytest.mark.parametrize("dist_name,index", DISTANCES)
def test_matcher_parity_hits_and_counts(dist_name, index):
    """Facade (matcher mode) == direct SubsequenceMatcher: same hit sets,
    same {query, build} eval counts, same dispatches — on every alignment
    distance, both execution policies."""
    rng = np.random.default_rng(11)
    seqs, Q = _seqs_and_query(dist_name, rng)
    eps = 2.0 if get(dist_name).string else 1.0
    kw = dict(index=index, tight_bounds=(index == "refnet"))

    direct = _quiet(SubsequenceMatcher, dist_name, 8, 1, **kw).build(seqs)
    want = direct.query_range(Q, eps)
    want_counts = (direct.index.counter.count,
                   direct.index.counter.build_count,
                   direct.index.counter.dispatches)

    for execution in ("batched", "host"):
        r = Retriever.build(
            RetrievalConfig(dist_name, lam=8, lambda0=1,
                            execution=execution, **kw), seqs)
        rs = r.query(Q).range(eps)
        assert rs.hits == want, f"{dist_name}/{execution} hit drift"
        assert rs.stats["build"] == want_counts[1], \
            f"{dist_name}/{execution} build-count drift"
        if execution == "batched":
            assert rs.stats["query"] == want_counts[0]
            assert rs.stats["dispatches"] == want_counts[2]
        # types II/III agree end to end
        got_l = r.query(Q).longest(eps).first
        assert got_l == direct.query_longest(Q, eps)
    # and the host path spends exactly the legacy per-segment counts
    legacy = _quiet(SubsequenceMatcher, dist_name, 8, 1, batched=False,
                    **kw).build(seqs)
    legacy.query_range(Q, eps)
    rh = Retriever.build(
        RetrievalConfig(dist_name, lam=8, lambda0=1, execution="host", **kw),
        seqs)
    rsh = rh.query(Q).range(eps)
    assert rsh.stats["query"] == legacy.index.counter.count
    assert rsh.stats["dispatches"] == legacy.index.counter.dispatches


@pytest.mark.parametrize("dist_name", ["levenshtein", "erp", "frechet"])
def test_window_parity_hits_and_counts(dist_name):
    """Facade (window mode) == direct index range queries, host and
    engine execution, including the bulk_build=False legacy structure."""
    data = _strings(80) if get(dist_name).string else _series(80)
    eps = 2.0 if get(dist_name).string else 1.0
    queries = np.stack([data[i] for i in (3, 17, 40)])

    net = ReferenceNet(get(dist_name), data, eps_prime=1.0, num_max=4,
                       tight_bounds=True).build()
    want_build = net.counter.build_count
    net.counter.reset()
    want = [net.range_query(q, eps) for q in queries]
    want_count, want_disp = net.counter.count, net.counter.dispatches

    cfg = RetrievalConfig(dist_name, num_max=4, tight_bounds=True,
                          bulk_build=False)
    r = Retriever.build(cfg, data)
    host = r.batch(queries).via("host").range(eps)
    assert host.hits == want
    assert host.stats["query"] == want_count
    assert host.stats["dispatches"] == want_disp
    eng = r.batch(queries).via("batched").range(eps)
    assert eng.hits == want
    assert eng.stats["query"] == want_count
    assert eng.stats["dispatches"] <= want_disp
    assert r.eval_stats()["build"] == want_build


@pytest.mark.parametrize("dist_name",
                         ["levenshtein", "erp", "frechet", "euclidean"])
def test_fleet_parity_hits_and_counts(dist_name):
    """Facade (fleet mode) == direct ElasticIndex: same hits on the host
    loop AND the stacked device path, same {query, build} buckets."""
    data = _strings(60, l=8) if get(dist_name).string else _series(60, l=8)
    eps = 2.0 if get(dist_name).string else 1.0
    queries = np.stack([data[i] for i in (2, 31, 47)])
    workers = ["a", "b", "c"]

    direct = _quiet(ElasticIndex, dist_name, data, workers,
                    tight_bounds=True)
    want_host = [direct.range_query(q, eps, batched=False) for q in queries]
    want_stacked = direct.range_query_batch(queries, eps)
    want_buckets = direct.eval_count()

    r = Retriever.build(
        RetrievalConfig(dist_name, execution="fleet", workers=workers,
                        tight_bounds=True), data)
    host = r.batch(queries).via("host").range(eps)
    assert host.hits == want_host
    stacked = r.batch(queries).range(eps)
    assert stacked.hits == want_stacked
    assert stacked.hits == host.hits
    got = r.eval_stats()
    assert {k: got[k] for k in ("query", "build")} == want_buckets
    # dead-worker masking flows through the plan API
    masked = r.batch(queries).dead("a").range(eps)
    direct_masked = direct.range_query_batch(queries, eps, dead=("a",))
    assert masked.hits == direct_masked


def test_fleet_dead_mask_cleared_by_resize():
    """A masked worker that survives a resize serves again: resize hands
    every surviving shard a healthy (resharded) state, so a stale mask
    must not silently drop its partition from later answers."""
    data = _strings(60, l=8)
    r = Retriever.build(
        RetrievalConfig("levenshtein", execution="fleet",
                        workers=["a", "b", "c"], tight_bounds=True), data)
    q = data[4]
    full = r.query(q).range(2.0).hits
    h = r.elastic().mark_dead("a")
    assert h.dead == ["a"]
    h.resize(["a", "b", "c"])
    assert h.dead == []
    assert r.query(q).range(2.0).hits == full
    # revive() also lifts the mask without a resize
    h.mark_dead("b").revive("b")
    assert h.dead == []


def test_config_to_json_rejects_unregistered_instance():
    lev = get("levenshtein")
    rogue = dist_base.Distance(
        name="never_registered", batch=lev.batch, matrix=lev.matrix,
        metric=True, consistent=True, string=True, variable_length=True)
    cfg = RetrievalConfig(rogue)
    with pytest.raises(ValueError, match="not in the registry"):
        cfg.to_json()


def test_fleet_elastic_handle_resize_parity():
    data = _strings(60, l=8)
    workers = ["a", "b", "c"]
    direct = _quiet(ElasticIndex, "levenshtein", data, workers,
                    tight_bounds=True)
    r = Retriever.build(
        RetrievalConfig("levenshtein", execution="fleet", workers=workers,
                        tight_bounds=True), data)
    frac_d = direct.resize(["a", "b"])
    frac_f = r.elastic().resize(["a", "b"])
    assert frac_f == frac_d
    got, want = r.eval_stats(), direct.eval_count()
    assert {k: got[k] for k in ("query", "build")} == want
    q = data[4]
    assert r.query(q).range(2.0).hits == \
        direct.range_query_batch([q], 2.0)[0]
    assert r.elastic().workers == ["a", "b"]


# -- facade-only surfaces -----------------------------------------------------

def test_embedding_index_mode():
    rng = np.random.default_rng(5)
    vecs = rng.normal(size=(40, 8)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    r = Retriever.build(
        RetrievalConfig("euclidean", index="embedding", eps_prime=0.05,
                        num_max=5, tight_bounds=True), vecs)
    rs = r.query(vecs[7]).range(1e-4)
    assert 7 in rs.hits
    near = r.query(vecs[7]).nearest(2.0, tol=1e-3)
    assert near.first == 7 and near.distances[0] <= 1e-4
    with pytest.raises(ValueError, match="embedding index expects"):
        Retriever.build(
            RetrievalConfig("euclidean", index="embedding"), _series(10))


def test_window_nearest_and_auto_eps():
    data = _strings(50)
    r = Retriever.build(RetrievalConfig("levenshtein"), data)
    rs = r.query(data[9]).nearest()     # no eps_max: auto-doubling
    assert rs.first == 9 and rs.distances[0] == 0.0
    with pytest.raises(ValueError, match="type II"):
        r.query(data[9]).longest(1.0)


def test_fleet_rejects_nonrange_queries_and_reset():
    data = _strings(40, l=8)
    r = Retriever.build(
        RetrievalConfig("levenshtein", execution="fleet", workers=2), data)
    with pytest.raises(ValueError, match="range queries"):
        r.query(data[0]).nearest(1.0)
    with pytest.raises(ValueError, match="monotone"):
        r.reset_counter()
    with pytest.raises(ValueError, match="lb"):
        r.query(data[0]).lb().range(1.0)


def test_non_fleet_rejects_fleet_controls():
    data = _strings(30)
    r = Retriever.build(RetrievalConfig("levenshtein"), data)
    with pytest.raises(ValueError, match="fleet"):
        r.elastic()
    with pytest.raises(ValueError, match="dead"):
        r.query(data[0]).dead("a")


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1.0, 2.0, 4.0]))
    def test_facade_parity_property(seed, eps):
        """Property form of the acceptance criterion: random corpora keep
        facade and direct paths hit- and count-identical."""
        rng = np.random.default_rng(seed)
        data = _strings(50, rng=rng)
        net = ReferenceNet(get("levenshtein"), data, eps_prime=1.0).build()
        queries = data[rng.integers(0, len(data), 4)]
        net.counter.reset()
        want = [net.range_query(q, eps) for q in queries]
        wc = net.counter.count
        r = Retriever.build(
            RetrievalConfig("levenshtein", bulk_build=False), data)
        rs = r.batch(queries).range(eps)
        assert rs.hits == want and rs.stats["query"] == wc
else:
    @pytest.mark.skip(reason="hypothesis not installed (pip install .[dev])")
    def test_facade_parity_property():
        pass
