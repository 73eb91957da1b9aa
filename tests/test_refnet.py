"""Reference net / cover tree / MV index: invariants, correctness vs linear
scan, deletion, num_max capping, space model."""

import numpy as np
import pytest

from repro.core.counter import CountedDistance
from repro.core.covertree import CoverTree
from repro.core.refindex import MVReferenceIndex
from repro.core.refnet import ReferenceNet
from repro.distances import get

RNG = np.random.default_rng(42)


def _motif_strings(n, l=10, alphabet=20, n_motifs=12, mut=0.15, rng=RNG):
    motifs = rng.integers(0, alphabet, size=(n_motifs, l))
    data = motifs[rng.integers(0, n_motifs, n)]
    m = rng.random((n, l)) < mut
    return np.where(m, rng.integers(0, alphabet, size=(n, l)), data)


def _trajectories(n, l=10, rng=RNG):
    steps = rng.normal(scale=0.3, size=(n, l, 2))
    base = rng.normal(scale=2.0, size=(n, 1, 2))
    return np.cumsum(steps, axis=1) + base


CASES = [
    ("levenshtein", _motif_strings, 1.0),
    ("erp", _trajectories, 0.5),
    ("frechet", _trajectories, 0.25),
]


@pytest.mark.parametrize("dist_name,gen,eps_prime", CASES)
@pytest.mark.parametrize("tight", [False, True])
def test_refnet_range_query_matches_linear_scan(dist_name, gen, eps_prime, tight):
    data = gen(200)
    dist = get(dist_name)
    net = ReferenceNet(dist, data, eps_prime=eps_prime,
                       tight_bounds=tight).build()
    net.check_invariants()
    naive = CountedDistance(dist, data)
    for eps_frac in [0.5, 2.0, 6.0]:
        eps = eps_prime * eps_frac
        for t in range(3):
            q = data[RNG.integers(0, len(data))]
            got = net.range_query(q, eps)
            want = sorted(np.nonzero(
                naive.eval(q, np.arange(len(data))) <= eps)[0].tolist())
            assert got == want


@pytest.mark.parametrize("dist_name,gen,eps_prime", CASES[:2])
def test_covertree_matches_linear_scan(dist_name, gen, eps_prime):
    data = gen(150)
    dist = get(dist_name)
    ct = CoverTree(dist, data, eps_prime=eps_prime).build()
    ct.check_invariants()
    naive = CountedDistance(dist, data)
    q = data[3]
    eps = 3 * eps_prime
    got = ct.range_query(q, eps)
    want = sorted(np.nonzero(
        naive.eval(q, np.arange(len(data))) <= eps)[0].tolist())
    assert got == want


def test_mv_index_matches_linear_scan():
    data = _motif_strings(150)
    dist = get("levenshtein")
    mv = MVReferenceIndex(dist, data, n_refs=5).build()
    naive = CountedDistance(dist, data)
    q = data[7]
    got = mv.range_query(q, 3.0)
    want = sorted(np.nonzero(
        naive.eval(q, np.arange(len(data))) <= 3.0)[0].tolist())
    assert got == want
    assert mv.stats()["table_entries"] == 5 * len(data)


def test_refnet_rejects_non_metric():
    data = _trajectories(10)
    with pytest.raises(ValueError, match="not a metric"):
        ReferenceNet(get("dtw"), data)


def test_num_max_caps_parents():
    data = _motif_strings(300, mut=0.05)  # dense clusters -> many parents
    dist = get("levenshtein")
    un = ReferenceNet(dist, data, eps_prime=1.0).build()
    capped = ReferenceNet(dist, data, eps_prime=1.0, num_max=3).build()
    capped.check_invariants()
    assert capped.stats()["max_parents"] <= 3
    assert capped.stats()["n_list_entries"] <= un.stats()["n_list_entries"]
    # capping must not break correctness
    naive = CountedDistance(dist, data)
    q = data[11]
    want = sorted(np.nonzero(
        naive.eval(q, np.arange(len(data))) <= 2.0)[0].tolist())
    assert capped.range_query(q, 2.0) == want


def test_space_is_linear():
    """Paper fig. 5: node count and list entries grow linearly."""
    dist = get("levenshtein")
    sizes = [100, 200, 400]
    entries = []
    for n in sizes:
        data = _motif_strings(n)
        net = ReferenceNet(dist, data, eps_prime=1.0).build()
        s = net.stats()
        assert s["n_objects"] == n
        entries.append(s["n_list_entries"])
    # list entries per object stay bounded (linear space, paper §6)
    ratios = [e / n for e, n in zip(entries, sizes)]
    assert max(ratios) < 8.0
    assert max(ratios) / min(ratios) < 2.5


def test_deletion_preserves_structure():
    data = _motif_strings(120)
    dist = get("levenshtein")
    net = ReferenceNet(dist, data, eps_prime=1.0).build()
    naive = CountedDistance(dist, data)
    drop = [i for i in [5, 17, 33, 60, 99] if i != net.root]
    for i in drop:
        net.delete(i)
    q = data[2]
    keep = np.array([i for i in range(len(data)) if i not in drop])
    want = sorted(int(i) for i in keep[
        naive.eval(q, keep) <= 2.0])
    assert net.range_query(q, 2.0) == want


@pytest.mark.parametrize("dist_name,gen,eps_prime", CASES)
@pytest.mark.parametrize("num_max", [None, 1])
def test_many_deletions_keep_links_downward(dist_name, gen, eps_prime,
                                            num_max):
    """Deleting a third of the net re-homes references that may land
    below their old level: their lists then dissolve and re-home too, so
    every list still holds lower levels only, the invariants hold, and
    range queries equal a scan of what is left."""
    rng = np.random.default_rng(8)
    data = gen(90, rng=rng)
    dist = get(dist_name)
    net = ReferenceNet(dist, data, eps_prime=eps_prime, num_max=num_max,
                       tight_bounds=True).build_batched()
    drop = [int(x) for x in rng.choice(90, 30, replace=False)
            if x != net.root]
    for x in sorted(drop, key=lambda x: net.nodes[x].level):
        net.delete(x)
    net.check_invariants()
    for n in net.nodes.values():
        assert all(net.nodes[c].level < n.level for c in n.children)
        assert len(set(n.parents)) == len(n.parents)
    keep = np.array(sorted(net.nodes))
    naive = CountedDistance(dist, data)
    for qi in (1, 44, 80):
        d = naive.eval(data[qi], keep)
        for eps in (float(np.quantile(d, 0.1)), float(np.median(d))):
            assert net.range_query(data[qi], eps) == \
                keep[d <= eps].tolist()


def test_pruning_beats_mv_at_equal_space():
    """Paper §8.2 headline: RN prunes better than MV with comparable space."""
    data = _motif_strings(400)
    dist = get("levenshtein")
    net = ReferenceNet(dist, data, eps_prime=1.0, num_max=5,
                       tight_bounds=True).build()
    mv = MVReferenceIndex(dist, data, n_refs=5).build()
    rn_evals, mv_evals = 0, 0
    for t in range(5):
        q = data[RNG.integers(0, len(data))]
        net.counter.reset()
        net.range_query(q, 2.0)
        rn_evals += net.counter.count
        mv.counter.reset()
        mv.range_query(q, 2.0)
        mv_evals += mv.counter.count
    assert rn_evals < mv_evals
