"""Continuous-batching serve subsystem (PR 9): fleet snapshot/restore
round-trips (array + accounting + hit-set parity, restore-then-resize on
both the shrink and the append/grow paths), the request queue, the serve
engine's shared-round admission (continuous vs sequential dispatch
counts, tick vs greedy, the in-flight cap, latency accounting), the
zero-downtime mid-load snapshot-swap resize, wall-clock serving on a
thread with the open-loop Poisson load generator, and the
config/facade wiring (`serve_*` fields, `Retriever.serve()`)."""

import threading

import numpy as np
import pytest

from repro.data.synthetic import proteins, trajectories
from repro.launch.elastic import ElasticIndex
from repro.serve import (FleetSnapshotManager, OpenLoopLoadGen,
                         RequestQueue, ServeConfig, ServeEngine,
                         poisson_schedule)

CASES = [
    ("levenshtein", proteins, 1.0, 2.0),
    ("erp", trajectories, 0.5, 1.0),
]


def _fleet(dist_name="levenshtein", gen=proteins, eps_prime=1.0, n=120,
           workers=("a", "b", "c"), seed=7, **kw):
    data = gen(n, seed=seed)
    return data, ElasticIndex(dist_name, data, list(workers),
                              eps_prime=eps_prime, **kw)


def _oracle(fleet, qs, eps):
    return [fleet.range_query(q, eps, batched=False) for q in qs]


# -- snapshot / restore ------------------------------------------------------


@pytest.mark.parametrize("dist_name,gen,eps_prime,eps", CASES)
def test_snapshot_round_trip_arrays_and_hits(tmp_path, dist_name, gen,
                                             eps_prime, eps):
    """Restore rebuilds every shard bit-for-bit — FlatNet arrays,
    envelopes, gids, pivot ids — spends ZERO distance evaluations, and
    the clone answers exactly like the original."""
    data, fleet = _fleet(dist_name, gen, eps_prime)
    qs = data[[3, 40, 77]]
    want = _oracle(fleet, qs, eps)
    counts = fleet.eval_count()

    snap = FleetSnapshotManager(tmp_path)
    step = snap.save(fleet, block=True)
    clone = snap.restore(step)

    # restore is pure I/O: the {query, build} buckets carry over exactly
    assert clone.eval_count() == counts
    assert fleet.eval_count() == counts
    assert clone.workers == fleet.workers
    for w in fleet.workers:
        s, c = fleet.shards[w], clone.shards[w]
        np.testing.assert_array_equal(s.gids, c.gids)
        np.testing.assert_array_equal(s.flat.pivots, c.flat.pivots)
        np.testing.assert_array_equal(s.flat.pivot_radius,
                                      c.flat.pivot_radius)
        np.testing.assert_array_equal(s.flat.members, c.flat.members)
        np.testing.assert_array_equal(s.flat.member_dist,
                                      c.flat.member_dist)
        np.testing.assert_array_equal(s.flat.pivot_ids, c.flat.pivot_ids)
        if s.flat.envelopes is not None:
            np.testing.assert_array_equal(s.flat.envelopes.lo,
                                          c.flat.envelopes.lo)
            np.testing.assert_array_equal(s.flat.envelopes.hi,
                                          c.flat.envelopes.hi)
            np.testing.assert_array_equal(s.flat.envelopes.mass,
                                          c.flat.envelopes.mass)
        else:
            assert c.flat.envelopes is None
    assert _oracle(clone, qs, eps) == want
    assert clone.range_query_batch(list(qs), eps) == want


def test_snapshot_latest_and_retention(tmp_path):
    _, fleet = _fleet(n=60, workers=("a", "b"))
    snap = FleetSnapshotManager(tmp_path, keep=2)
    s0 = snap.save(fleet, block=True)
    s1 = snap.save(fleet, block=True)
    assert s1 == s0 + 1
    # restore() with no step follows the latest pointer
    clone = snap.restore()
    assert clone.workers == fleet.workers


def test_interpret_follows_the_platform_through_defaults_and_restore(
        tmp_path):
    """No stored default pins interpret mode: the config and the fleet
    default to None (the registry's platform policy: compiled on a TPU),
    the JSON round-trip keeps it, and a snapshot of a fleet pinned to
    interpret mode restores to a clone that follows the platform."""
    import inspect

    from repro.retrieval import RetrievalConfig
    cfg = RetrievalConfig("levenshtein")
    assert cfg.interpret is None
    assert RetrievalConfig.from_json(cfg.to_json()).interpret is None
    assert RetrievalConfig.from_json(
        cfg.replace(interpret=False).to_json()).interpret is False
    assert inspect.signature(ElasticIndex).parameters[
        "interpret"].default is None

    data, fleet = _fleet(n=60, workers=("a", "b"), interpret=True)
    snap = FleetSnapshotManager(tmp_path)
    clone = snap.restore(snap.save(fleet, block=True))
    assert clone.interpret is None
    qs = data[[4, 30]]
    assert clone.range_query_batch(list(qs), 2.0) == _oracle(fleet, qs, 2.0)


def test_restore_then_resize_shrink_and_grow(tmp_path):
    """A restored clone reshards exactly like the original would have:
    the shrink path (Alg.-2 deletes + masking) and the grow/append path
    (extend_data + FlatNet.append) both preserve hit sets, and the
    accounting buckets stay monotone through restore."""
    data, fleet = _fleet(n=150, workers=("a", "b", "c"))
    qs = data[[5, 50, 95]]
    want = _oracle(fleet, qs, 2.0)
    snap = FleetSnapshotManager(tmp_path)
    step = snap.save(fleet, block=True)

    shrunk = snap.restore(step)
    b0 = shrunk.eval_count()["build"]
    shrunk.resize(["a", "b"])
    assert shrunk.eval_count()["build"] >= b0
    assert _oracle(shrunk, qs, 2.0) == want

    grown = snap.restore(step)
    grown.resize(["a", "b", "c", "d"])
    assert len(grown.workers) == 4
    assert _oracle(grown, qs, 2.0) == want
    assert grown.range_query_batch(list(qs), 2.0) == want


# -- request queue -----------------------------------------------------------


def test_request_queue_fifo_and_lifecycle():
    q = RequestQueue()
    r1 = q.submit(np.arange(3), 1.0, now=0.5)
    r2 = q.submit(np.arange(4), 2.0, now=0.7)
    assert (r1.rid, r2.rid) == (0, 1) and q.submitted == 2
    assert len(q) == 2
    assert q.take(1) == [r1]      # FIFO, bounded take
    assert q.take(10) == [r2] and len(q) == 0
    assert not r1.done
    r1.t_admit = 0.6
    r1.finish([4, 9], now=1.5)
    assert r1.done and r1.hits == [4, 9]
    assert r1.latency == pytest.approx(1.0)   # complete - submit
    assert r1.result(timeout=1) == [4, 9]


def test_poisson_schedule_deterministic():
    a = poisson_schedule(8.0, 2.0, seed=3)
    b = poisson_schedule(8.0, 2.0, seed=3)
    np.testing.assert_array_equal(a, b)
    assert (np.diff(a) >= 0).all() and (a < 2.0).all()
    assert len(a) > 0
    assert not np.array_equal(a, poisson_schedule(8.0, 2.0, seed=4))


# -- serve engine: virtual clock ---------------------------------------------


def test_continuous_batching_shares_rounds_and_stays_exact():
    """The tentpole property: overlapping requests ride SHARED merged
    rounds (total dispatches well below the one-query-at-a-time sum)
    while every hit set matches the sequential host-loop oracle."""
    data, fleet = _fleet(n=150)
    qs = [data[i] for i in range(0, 24, 2)]
    want = _oracle(fleet, qs, 2.0)

    r0 = fleet.device_stats["rounds"]
    for q in qs:
        fleet.range_query_batch([q], 2.0)
    seq_rounds = fleet.device_stats["rounds"] - r0

    eng = ServeEngine(fleet, ServeConfig(eps=2.0))
    arrivals = np.arange(len(qs), dtype=np.float64)   # qps 1, depth > 1
    reqs = eng.run_schedule(qs, arrivals)
    assert [r.hits for r in reqs] == want
    assert eng.engine_stats()["rounds"] < seq_rounds
    assert eng.engine_stats()["completed"] == len(qs)
    # every request carries its round count and full timestamp chain
    assert all(r.rounds >= 1 and r.t_admit >= arrivals[i]
               for i, r in enumerate(reqs))

    lat = eng.latency_stats()
    assert lat["n"] == len(qs)
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"]
    assert lat["mean_rounds"] >= 1


def test_greedy_admission_parity_and_extra_rounds():
    data, fleet = _fleet(n=150)
    qs = [data[i] for i in range(0, 16, 2)]
    arrivals = np.arange(len(qs), dtype=np.float64)
    want = _oracle(fleet, qs, 2.0)

    tick = ServeEngine(fleet, ServeConfig(eps=2.0))
    reqs_t = tick.run_schedule(qs, arrivals)
    _, fleet2 = _fleet(n=150)
    greedy = ServeEngine(fleet2, ServeConfig(eps=2.0, admission="greedy"))
    reqs_g = greedy.run_schedule(qs, arrivals)

    assert [r.hits for r in reqs_t] == want
    assert [r.hits for r in reqs_g] == want
    # greedy buys newcomers a dedicated first round; it can never spend
    # FEWER dispatches than pure shared-cadence admission
    assert greedy.engine_stats()["rounds"] >= tick.engine_stats()["rounds"]


def test_max_inflight_caps_admission():
    data, fleet = _fleet(n=120)
    qs = [data[i] for i in range(8)]
    eng = ServeEngine(fleet, ServeConfig(eps=2.0, max_inflight=2))
    for i, q in enumerate(qs):
        eng.submit(q, now=0.0)
    peak = 0
    t = 0.0
    while eng._engine.active or len(eng.queue):
        eng.tick(now=t)
        peak = max(peak, len(eng._inflight))
        t += 1.0
    assert peak <= 2
    assert [r.hits for r in eng.completed] == _oracle(fleet, qs, 2.0)


def test_mid_load_snapshot_swap_resize_zero_downtime(tmp_path):
    """A resize() mid-schedule goes snapshot -> restore clone -> reshard
    off-path -> swap at a round boundary: ZERO failed or mismatched
    requests, in-flight requests finish on the fleet that admitted them,
    post-swap requests serve from the new worker set."""
    data, fleet = _fleet(n=150)
    qs = [data[i] for i in range(0, 24, 2)]
    want = _oracle(fleet, qs, 2.0)
    eng = ServeEngine(fleet, ServeConfig(eps=2.0, snapshot_dir=tmp_path))
    arrivals = np.arange(len(qs), dtype=np.float64)
    reqs = eng.run_schedule(qs, arrivals, resize_at=5.0,
                            resize_to=["a", "b"])
    assert all(r.done for r in reqs)
    assert [r.hits for r in reqs] == want
    assert eng.swaps == 1
    assert eng.fleet.workers == ["a", "b"]
    # the swapped-in fleet keeps serving exactly
    post = eng.run_schedule(qs[:3], [0.0, 0.0, 0.0])
    assert [r.hits for r in post] == want[:3]


# -- serve engine: wall clock ------------------------------------------------


def test_wall_clock_thread_and_loadgen():
    data, fleet = _fleet(n=90)
    qs = [data[i] for i in range(6)]
    want = _oracle(fleet, qs, 2.0)
    eng = ServeEngine(fleet, ServeConfig(eps=2.0)).start()
    try:
        # direct submits resolve through Request.result()
        direct = [eng.submit(q) for q in qs[:2]]
        assert [r.result(timeout=30) for r in direct] == want[:2]
        # open-loop Poisson load drains through the same engine
        load = OpenLoopLoadGen(eng, qs, qps=200.0, seed=0).start()
        reqs = load.join(timeout=30)
    finally:
        eng.close(drain=True)
    assert [r.hits for r in reqs] == want
    assert eng.engine_stats()["completed"] == len(qs) + 2
    assert threading.active_count() >= 1   # thread shut down cleanly
    assert eng._thread is None


# -- config / facade wiring --------------------------------------------------


def test_serve_config_validation():
    with pytest.raises(ValueError, match="max_inflight"):
        ServeConfig(max_inflight=0)
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(admission="eager")


def test_retrieval_config_serve_fields_round_trip_and_validate():
    from repro.retrieval import RetrievalConfig
    cfg = RetrievalConfig("levenshtein", execution="fleet", workers=2,
                          serve_max_inflight=8, serve_admission="greedy",
                          serve_snapshot_dir="/tmp/snaps")
    back = RetrievalConfig.from_json(cfg.to_json())
    assert back.serve_max_inflight == 8
    assert back.serve_admission == "greedy"
    assert back.serve_snapshot_dir == "/tmp/snaps"
    with pytest.raises(ValueError, match="serve_max_inflight"):
        RetrievalConfig("levenshtein", serve_max_inflight=0)
    with pytest.raises(ValueError, match="serve_admission"):
        RetrievalConfig("levenshtein", serve_admission="eager")


def test_facade_serve_builds_engine_fleet_only():
    from repro.retrieval import RetrievalConfig, Retriever
    data = proteins(80, seed=0)
    r = Retriever.build(
        RetrievalConfig("levenshtein", execution="fleet", workers=2,
                        serve_max_inflight=4, serve_admission="greedy"),
        data)
    eng = r.serve(eps=1.5)
    assert isinstance(eng, ServeEngine)
    assert eng.config.eps == 1.5
    assert eng.config.max_inflight == 4
    assert eng.config.admission == "greedy"
    reqs = eng.run_schedule([data[0]], [0.0])
    assert reqs[0].hits == r.batch(data[:1]).via("host").range(1.5).hits[0]

    host = Retriever.build(RetrievalConfig("levenshtein"), data)
    with pytest.raises(ValueError, match="fleet"):
        host.serve()
