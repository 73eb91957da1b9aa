"""Algorithm 3 over the net's array snapshot against the per-node walk it
replaced: for every query, the same frontiers round by round (ids and
kind), the same hit list and the same exact-evaluation count — across
Levenshtein, ERP and Fréchet, tight and faithful bounds, multi-parent
nets, ``num_max=1`` nets and the cover tree, and nets changed by
``delete``, ``insert`` and ``build_batched`` on extended data.  (DTW
cannot take part: the net refuses a distance that is not a metric.)

Also: the snapshot is built once per change of the net, and a served
fleet builds one per shard."""

from typing import Dict, List, Set

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batch_engine
from repro.core.counter import CountedDistance
from repro.core.covertree import CoverTree
from repro.core.refnet import INF, ReferenceNet
from repro.distances import get


def walk_plan(net: ReferenceNet, eps: float, settles: List[int]):
    """The per-node walk of Algorithm 3 that ``range_query_plan`` replaced
    (dicts and sets, one node at a time), kept as the oracle.  Appends to
    ``settles`` the id of every list-holding node whose whole subtree a
    bound settled."""
    if net.root is None:
        return []
    known: Dict[int, float] = {}   # exact distances (each counted once)
    lo: Dict[int, float] = {}      # accumulated object lower bounds
    hi: Dict[int, float] = {}      # accumulated object upper bounds
    slo: Dict[int, float] = {}     # subtree lower bounds
    shi: Dict[int, float] = {}     # subtree upper bounds
    closed: Set[int] = set()       # whole-subtree verdict settled
    decided: Set[int] = set()      # object verdict settled
    results: List[int] = []

    def request(idxs, kind):
        # de-dup against known, then yield ONE frontier for the batch
        new = sorted(set(i for i in idxs if i not in known))
        if new:
            ds = yield batch_engine.Frontier(np.asarray(new, np.int64),
                                             kind)
            known.update(zip(new, map(float, ds)))

    def settle_subtree(n: int, accept: bool) -> None:
        if n not in closed and net.nodes[n].children:
            settles.append(n)
        stack = [n]
        while stack:
            x = stack.pop()
            if x in closed:
                continue
            closed.add(x)
            if x not in decided:
                decided.add(x)
                if accept:
                    results.append(x)
            stack.extend(net.nodes[x].children)

    def decide(x: int, inside: bool) -> None:
        if x in decided:
            return
        decided.add(x)
        if inside:
            results.append(x)

    yield from request([net.root], batch_engine.EXACT)
    d_root = known[net.root]
    decide(net.root, d_root <= eps)
    alive: Set[int] = {net.root}
    pending_leaf: Set[int] = set()     # objects awaiting final verdict

    for level in range(net.top_level, -1, -1):
        defer = [c for c in alive
                 if c not in known and c not in closed
                 and net.nodes[c].level == level]
        yield from request(defer, batch_engine.EXACT)
        for c in defer:
            d = known[c]
            decide(c, d <= eps)

        for n in sorted(c for c in alive
                        if net.nodes[c].level == level):
            alive.discard(n)
            if n in closed:
                continue
            node = net.nodes[n]
            d = known[n]
            sr = net._subtree_radius(node)
            if d + sr <= eps:
                settle_subtree(n, accept=True)
                continue
            if d - sr > eps:
                # n itself was decided exactly; only descendants settle
                for c in node.children:
                    settle_subtree(c, accept=False)
                closed.add(n)
                continue
            for k, c in enumerate(node.children):
                if c in closed:
                    continue
                cn = net.nodes.get(c)
                if cn is None:
                    continue
                r = net._link_radius(node, k)
                src = net._subtree_radius(cn)
                lo[c] = max(lo.get(c, 0.0), d - r)
                hi[c] = min(hi.get(c, INF), d + r)
                slo[c] = max(slo.get(c, 0.0), d - r - src)
                shi[c] = min(shi.get(c, INF), d + r + src)
                if shi[c] <= eps:
                    settle_subtree(c, accept=True)
                    continue
                if slo[c] > eps:
                    settle_subtree(c, accept=False)
                    continue
                if hi[c] <= eps:
                    decide(c, True)
                elif lo[c] > eps:
                    decide(c, False)
                if cn.children:
                    alive.add(c)       # expandable: deferred to its level
                elif c not in decided:
                    pending_leaf.add(c)
            closed.add(n)

    rem = [c for c in pending_leaf if c not in decided and c not in closed]
    yield from request(rem, batch_engine.VERDICT)
    for c in rem:
        decide(c, known[c] <= eps)
    return sorted(results)


def record(plan, counter: CountedDistance, q):
    """Drive ``plan`` as ``batch_engine.drive`` does; returns its
    frontiers as (ids, kind), its hits and the exact evaluations spent."""
    c0 = counter.count
    frontiers = []
    try:
        fr = next(plan)
        while True:
            frontiers.append((fr.idxs.tolist(), fr.kind))
            fr = plan.send(counter.eval(q, fr.idxs, len(q)))
    except StopIteration as stop:
        hits = stop.value
    return frontiers, hits, counter.count - c0


def _strings(rng, n, l=8, alphabet=12):
    motifs = rng.integers(0, alphabet, size=(6, l))
    data = motifs[rng.integers(0, 6, n)]
    m = rng.random((n, l)) < 0.2
    return np.where(m, rng.integers(0, alphabet, size=(n, l)), data)


def _series(rng, n, l=8):
    steps = rng.normal(scale=0.3, size=(n, l, 2))
    return (np.cumsum(steps, axis=1)
            + rng.normal(scale=1.5, size=(n, 1, 2))).astype(np.float32)


DISTANCES = {"levenshtein": (_strings, 1.0), "erp": (_series, 0.5),
             "frechet": (_series, 0.25)}
INDEXES = {"net": {}, "net_num_max_1": {"num_max": 1}, "cover": None}
N = 60


def _index(kind, dist, data, eps_prime, tight):
    if kind == "cover":
        return CoverTree(dist, data, eps_prime=eps_prime, tight_bounds=tight)
    return ReferenceNet(dist, data, eps_prime=eps_prime, tight_bounds=tight,
                        **INDEXES[kind])


def _changed_net(change, kind, dist_name, tight, rng):
    gen, eps_prime = DISTANCES[dist_name]
    data = gen(rng, N + 20)
    net = _index(kind, get(dist_name), data[:N] if change == "extend"
                 else data, eps_prime, tight).build_batched(order=range(N))
    if change == "extend":
        net.build_batched(order=net.extend_data(data[N:]))
    elif change == "insert":
        for i in range(N, N + 20):
            net.insert(i)
    elif change == "delete":
        drop = [int(x) for x in rng.choice(N, 20, replace=False)
                if x != net.root]
        # plain objects first, then references bottom-up, as the elastic
        # shrink does
        for x in sorted(drop, key=lambda x: net.nodes[x].level):
            net.delete(x)
    return net, data


@pytest.mark.parametrize("change", ["built", "delete", "insert", "extend"])
@pytest.mark.parametrize("kind", sorted(INDEXES))
@pytest.mark.parametrize("tight", [False, True])
@pytest.mark.parametrize("dist_name", sorted(DISTANCES))
@settings(max_examples=2, deadline=None)
@given(seed=st.integers(0, 2 ** 32 - 1))
def test_array_plan_equals_the_node_walk(dist_name, tight, kind, change,
                                         seed):
    rng = np.random.default_rng(seed)
    net, data = _changed_net(change, kind, dist_name, tight, rng)
    counter = net.counter
    queries = [data[int(i)] for i in rng.choice(len(data), 3)]
    queries.append(data[0] + (rng.random(data[0].shape) < 0.3)
                   if dist_name == "levenshtein"
                   else data[0] + rng.normal(scale=0.3, size=data[0].shape))
    for q in queries:
        q = np.asarray(q, data.dtype)
        d = counter.eval(q, np.asarray(sorted(net.nodes)), len(q))
        # no eps, a narrow one, a median one, and one that takes all
        for eps in (-1.0, float(np.quantile(d, 0.05)),
                    float(np.median(d)), 1e9):
            settles: List[int] = []
            want = record(walk_plan(net, eps, settles), counter, q)
            got = record(net.range_query_plan(eps), counter, q)
            assert got == want
            if eps == 1e9:
                assert settles == [net.root]   # the root takes its subtree
    assert net.snapshot_builds == 1


def test_snapshot_is_cached_until_the_net_changes():
    rng = np.random.default_rng(5)
    net, data = _changed_net("built", "net", "levenshtein", True, rng)
    for eps in (1.0, 2.0, 3.0):
        net.range_query(data[3], eps)
    assert (net.snapshot_builds, net.plans) == (1, 3)
    net.delete(next(x for x in net.nodes if x != net.root))
    assert net.snapshot_builds == 1        # dropped, rebuilt on next use
    net.range_query(data[3], 2.0)
    assert (net.snapshot_builds, net.plans) == (2, 4)
    net.range_query(data[4], 2.0)
    assert net.snapshot_builds == 2


# -- the snapshot on the served path ------------------------------------------


def _brute_force(dist_name, data, q, eps):
    d = CountedDistance(get(dist_name), data).eval(q, np.arange(len(data)))
    return np.flatnonzero(d <= eps).tolist()


def test_served_fleet_builds_one_snapshot_per_shard():
    from repro.data.synthetic import proteins
    from repro.launch.elastic import ElasticIndex
    from repro.serve import ServeConfig, ServeEngine

    data = proteins(120, seed=3)
    fleet = ElasticIndex("levenshtein", data, ["a", "b", "c"])
    engine = ServeEngine(fleet, ServeConfig(eps=2.0))
    qs = data[np.arange(20) * 6]
    reqs = engine.run_schedule(qs, np.arange(20) * 0.5)
    assert [r.hits for r in reqs] == [
        _brute_force("levenshtein", data, q, 2.0) for q in qs]
    stats = engine.engine_stats()
    assert stats["plan_snapshot_builds"] == 3
    assert sum(s.net.plans for s in fleet.shards.values()) == 20 * 3


def test_resize_rebuilds_each_changed_shard_snapshot_once():
    from repro.data.synthetic import trajectories
    from repro.launch.elastic import ElasticIndex

    data = trajectories(150, seed=4)
    fleet = ElasticIndex("erp", data, ["a", "b", "c"], eps_prime=0.5)
    qs = data[[5, 60, 110]]
    for workers in (["a", "b", "c", "d"], ["a", "c", "d"], ["a", "c"]):
        before = {w: (s, s.net.snapshot_builds)
                  for w, s in fleet.shards.items()}
        old = {w: list(g) for w, g in fleet.assignment.items()}
        fleet.resize(workers)
        for w, s in fleet.shards.items():
            kept = w in before and before[w][0] is s
            if kept and old[w] == fleet.assignment[w]:
                assert s.net.snapshot_builds == before[w][1], w
            else:   # changed in place, or built anew
                assert s.net.snapshot_builds == (
                    before[w][1] + 1 if kept else 1), w
        builds = {w: s.net.snapshot_builds for w, s in fleet.shards.items()}
        for q in qs:
            assert fleet.range_query_batch([q], 1.0)[0] \
                == _brute_force("erp", data, q, 1.0)
            assert fleet.range_query(q, 1.0, batched=False) \
                == _brute_force("erp", data, q, 1.0)
        assert builds == {w: s.net.snapshot_builds
                          for w, s in fleet.shards.items()}


def test_admit_span_carries_the_snapshot_builds(tmp_path):
    """Under the profiler each ``serve.admit`` span carries the plan
    snapshots its admission built: 0 while every shard's is cached, and 1
    in all once a shard's net has dropped its snapshot."""
    import pathlib
    import sys

    import jax

    from repro.data.synthetic import proteins
    from repro.launch.elastic import ElasticIndex
    from repro.serve import ServeConfig, ServeEngine

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import devtrace, progtrace

    data = proteins(80, seed=6)
    fleet = ElasticIndex("levenshtein", data, ["a", "b"])
    engine = ServeEngine(fleet, ServeConfig(eps=2.0))
    qs = data[[4, 30, 70]]
    admits = []
    for k, drop in enumerate((False, True)):
        if drop:
            fleet.shards["a"].net._changed()
        d = tmp_path / str(k)
        with jax.profiler.trace(str(d)):
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                reqs = engine.run_schedule(qs, [0.0, 0.5, 1.0])
        assert [r.hits for r in reqs] == [
            _brute_force("levenshtein", data, q, 2.0) for q in qs]
        admits.append(progtrace.program_spans(devtrace.load(d))[
            "serve.admit"])
    assert [a["count"] for a in admits] == [3, 3]
    assert [a["stats"]["snapshot_builds"] for a in admits] == [0, 1]
    assert engine.engine_stats()["plan_snapshot_builds"] == 3
