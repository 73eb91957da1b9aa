"""The benchmark's own pieces on the CPU: generators, traffic, reference,
operation counts, and the shape of ``BENCHMARK.json``."""

import json
import pathlib
import re
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import costs, gen, harness, load, reference  # noqa: E402

BIG_SEED = 2**31 + 12345


def textbook(x, y, cost, gap_x, gap_y):
    """The DP of the definition, cell by cell in Python."""
    D = np.zeros((len(x) + 1, len(y) + 1))
    for i in range(1, len(x) + 1):
        D[i, 0] = D[i - 1, 0] + gap_x(x[i - 1])
    for j in range(1, len(y) + 1):
        D[0, j] = D[0, j - 1] + gap_y(y[j - 1])
    for i in range(1, len(x) + 1):
        for j in range(1, len(y) + 1):
            D[i, j] = min(D[i - 1, j - 1] + cost(x[i - 1], y[j - 1]),
                          D[i - 1, j] + gap_x(x[i - 1]),
                          D[i, j - 1] + gap_y(y[j - 1]))
    return D[-1, -1]


def test_generators_repeat_per_seed_and_take_large_seeds():
    a = gen.proteins(64, BIG_SEED)
    assert a.shape == (64, 20) and a.dtype == np.int32
    assert np.array_equal(a, gen.proteins(64, BIG_SEED))
    assert not np.array_equal(a, gen.proteins(64, BIG_SEED + 1))
    t = gen.trajectories(64, BIG_SEED)
    assert t.shape == (64, 20, 2) and t.dtype == np.float32
    q = gen.perturb(a, 16, BIG_SEED, subst=0.1)
    assert q.shape == (16, 20) and q.max() < 20


def test_levenshtein_matches_the_definition():
    data = gen.proteins(24, 3)
    q = gen.perturb(data, 1, 3, subst=0.3)[0]
    got = reference.levenshtein(q, data)
    want = [textbook(q, y, lambda a, b: float(a != b), lambda a: 1.0,
                     lambda b: 1.0) for y in data]
    assert np.array_equal(got, want)


def test_erp_matches_the_definition():
    data = gen.trajectories(16, 4)
    q = gen.perturb(data, 1, 4, noise=0.5)[0].astype(np.float64)
    norm = lambda a: float(np.sqrt((np.asarray(a, np.float64) ** 2).sum()))  # noqa
    got = reference.erp(q, data)
    want = [textbook(q, y.astype(np.float64), lambda a, b: norm(a - b),
                     norm, norm) for y in data]
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_bf16_control_departs_from_the_reference():
    data = gen.trajectories(64, 5)
    q = gen.perturb(data, 1, 5, noise=0.1)[0]
    exact, low = reference.erp(q, data), reference.erp_bf16(q, data)
    assert 1e-3 < np.max(np.abs(low - exact) / exact) < 0.1


def test_verdict_gaps():
    d = np.array([0.0, 2.0, 2.5, 7.0])
    assert reference.verdict_gaps([0, 1], d, 2.0).size == 0
    assert np.array_equal(reference.verdict_gaps([0, 3], d, 2.0), [0, 5])
    assert reference.verdict_gaps([0, 1, 9], d, 2.0).tolist() == [
        np.finfo(float).max]


def test_arrivals_same_work_for_every_seed():
    """One schedule for every run; another schedule seed orders the same
    exponential gaps otherwise."""
    mix = {"rate_rps": 20.0, "schedule_seed": 1}
    a = load.arrivals(mix, 30.0)
    b = load.arrivals(dict(mix, schedule_seed=BIG_SEED), 30.0)
    assert np.array_equal(a, load.arrivals(dict(mix), 30.0))
    assert len(a) == len(b) == 600
    assert 0 <= a.min() and a.max() < 30.0 and np.all(np.diff(a) >= 0)
    ga, gb = np.diff(np.r_[0, a]), np.diff(np.r_[0, b])
    assert not np.allclose(ga, gb)
    np.testing.assert_allclose(np.sort(ga)[:-1], np.sort(gb)[:-1],
                               rtol=0.2, atol=1e-3)


def test_arrivals_and_popularity_found_by_name():
    """``poisson`` and ``uniform`` are what a mix without the keys gets."""
    mix = {"rate_rps": 11.2, "schedule_seed": 1, "pool": 32}
    assert np.array_equal(load.arrivals(mix, 50.0), load.arrivals(
        dict(mix, arrivals="poisson"), 50.0))
    a, b = (load.query_stream(m, 9) for m in (mix, dict(
        mix, popularity="uniform")))
    assert [next(a) for _ in range(100)] == [next(b) for _ in range(100)]
    with pytest.raises(FileNotFoundError):
        load.arrivals(dict(mix, arrivals="no_such_pattern"), 1.0)


def test_mmpp_bursts_keep_the_mean_rate():
    """4 x the mean for 1 s of every 10 s; the rate between bursts is
    what keeps the mean, and the schedule is fixed by its seed."""
    mix = {"arrivals": "mmpp", "rate_rps": 20.0, "schedule_seed": 1,
           "mmpp": {"peak_x": 4.0, "burst_s": 1.0, "period_s": 10.0}}
    a = load.arrivals(mix, 200.0)
    assert len(a) == 4000 and np.all(np.diff(a) >= 0)
    assert 0 <= a.min() and a.max() < 200.0
    assert np.array_equal(a, load.arrivals(dict(mix), 200.0))
    assert not np.array_equal(a, load.arrivals(
        dict(mix, schedule_seed=BIG_SEED), 200.0))
    burst = np.mod(a, 10.0) < 1.0
    low = 20.0 * (10.0 - 4.0) / 9.0
    assert burst.sum() / 20.0 == pytest.approx(4 * 20.0, rel=0.05)
    assert (~burst).sum() / 180.0 == pytest.approx(low, rel=0.05)
    with pytest.raises(ValueError):
        load.arrivals(dict(mix, mmpp={"peak_x": 20.0, "burst_s": 1.0,
                                      "period_s": 10.0}), 10.0)


def test_zipf_popularity():
    """Rank k is asked in proportion to k ** -s; the seed permutes which
    pool query holds each rank."""
    mix = {"popularity": "zipf", "zipf": {"s": 1.1}, "pool": 64}

    def counts(seed):
        s = load.query_stream(mix, seed)
        return np.bincount([next(s) for _ in range(40000)], minlength=64)
    c = counts(BIG_SEED)
    assert np.array_equal(c, counts(BIG_SEED))
    ranked = np.sort(c)[::-1] / c.sum()
    p = np.arange(1, 65) ** -1.1
    np.testing.assert_allclose(ranked[:8], (p / p.sum())[:8], rtol=0.05)
    assert c.min() > 0
    top = lambda c: np.argsort(c)[::-1][:5].tolist()  # noqa: E731
    assert top(c) != top(counts(BIG_SEED + 1))


def test_query_stream_passes():
    s = load.query_stream({"pool": 32}, 9)
    one = [next(s) for _ in range(32)]
    two = [next(s) for _ in range(32)]
    assert sorted(one) == sorted(two) == list(range(32)) and one != two


def test_costs_and_peaks():
    assert costs.row_ops("levenshtein", 20, 20, 1) == 2400
    assert costs.row_ops("erp", 20, 20, 2) == 11 * 400 + 4 * 40
    share, bound = costs.roofline("levenshtein", 10**6, 20, 20, 1, 1.0,
                                  "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(100 * 10**6 * 168 / 819e9)
    assert costs.row_bytes("erp", 20, 20, 2) == 4 * 2 * 40 + 8
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_finds_every_file_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in spec["configs"]}
    for c in configs.values():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg)
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert NAME.match(m["name"])
            harness.reader(m["name"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
