"""Request kinds: the window-range cells give the numbers they gave before
the harness found its kind by name, and a kind defined in a file of its
own runs a cell end to end with the harness as it is."""

import hashlib
import json
import pathlib
import sys
import textwrap

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import byname, costs, harness, load, reference  # noqa: E402
from test_bench_runs import SEED, _window, files_cell, shrink  # noqa: E402


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# Computed by the harness before request kinds (window range hard-wired)
# on the same seed, with the same digests.
STREAM = [5, 11, 2, 9, 4, 7, 3, 15, 1, 10, 14, 12, 13, 6, 8, 0, 7, 5, 3,
          1, 8, 11, 0, 9, 2, 10, 4, 14, 15, 12, 6, 13, 8, 13, 1, 5, 14, 10,
          15, 12, 2, 6, 0, 4, 11, 7, 9, 3]
GOLDEN = {
    "protein_lev.steady": {
        "data": "e08630f13c312057", "pool": "f45fd9002a652ddf",
        "schedule": [120, "f7872a7371a1ad27"],
        "schedule_full": [560, "57c448754b07f1e1"],
        "stream": STREAM, "stream_full": "5bcb1a2a9ebe8d10",
        "warm": [8, ["5b818803b7c5da21", "688bc02040765a3c",
                     "543829d48be10242", "8c4d0869ef9cef15",
                     "41ba8c3be2a84164", "d0dcdff9c5f2ecaa",
                     "859db76dcd2d9ac1", "9a53d821a49d67f7"]],
        "reference": "bb3164cd9bdad256",
        "check": [{"unanswered": 0, "wrong_verdicts": 12,
                   "verdict_gap": 2.0}, 12, False],
        "control": [{"unanswered": 0, "wrong_verdicts": 7,
                     "verdict_gap": 0.0}, 7, False],
        "work": [2400 * 1000, 168 * 1000]},
    "traj_erp.steady": {
        "data": "ac658f48234a8418", "pool": "5d84cf498586c9db",
        "schedule": [120, "f7872a7371a1ad27"],
        "schedule_full": [1050, "f9a42b3f331897c6"],
        "stream": STREAM, "stream_full": "5bcb1a2a9ebe8d10",
        "warm": [8, ["6b1e236d355e762d", "846053aca7f306e4",
                     "f32eafbfa2ec2a5b", "f7acf9fb6c8d1b12",
                     "13d781f8672eaadb", "6c73f0633f7a1b41",
                     "95e1de83ec0555ef", "7f39a8b5250d9f4f"]],
        "reference": "576f918db2cbf75b",
        "reference_bf16": "b41c7cd5fc00f491",
        "check": [{"unanswered": 0, "wrong_verdicts": 17,
                   "verdict_gap": 13.822859876383006}, 16, False],
        "control": [{"unanswered": 0, "wrong_verdicts": 0,
                     "verdict_gap": 0.0}, 0, True],
        "work": [4560 * 1000, 328 * 1000]},
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_window_range_gives_the_numbers_it_gave(monkeypatch, name):
    """Data, pool, arrival schedule, query stream, warmed batch classes,
    reference distances, the check's numbers and the roofline's work for
    1000 rows, against values the harness gave before request kinds."""
    want = GOLDEN[name]
    full = files_cell(name)
    cell = shrink(files_cell(name))
    data, pool = harness.make_data(cell, SEED)
    eps = float(cell.traffic["eps"])
    got = {"data": digest(data), "pool": digest(pool)}
    for key, c, seconds in (("schedule", cell, 30.0),
                            ("schedule_full", full, 50.0)):
        due = load.arrivals(c.traffic, seconds)
        got[key] = [len(due), digest(due)]
    s = load.query_stream(cell.traffic, SEED)
    got["stream"] = [next(s) for _ in range(48)]
    s = load.query_stream(full.traffic, SEED)
    got["stream_full"] = digest(np.array([next(s) for _ in range(600)]))

    from repro.kernels import dispatch
    calls = []

    def record(distance, xs, ys, eps=None):
        assert distance == cell.distance and eps == float(
            cell.traffic["eps"])
        calls.append(digest(xs, ys))
    monkeypatch.setattr(dispatch, "packed_batch", record)
    got["warm"] = [cell.kind.warm(cell, data, pool, eps), calls]

    got["reference"] = digest(np.stack(
        reference.distances(cell.distance, pool, data, threads=2)))
    if "reference_bf16" in want:
        got["reference_bf16"] = digest(np.stack(reference.distances(
            cell.distance, pool, data, control=True, threads=2)))
    served = harness.Served(data, pool, None, eps, cell.kind)
    w = _window(served, range(len(pool)))
    for key, control in (("check", False), ("control", True)):
        out = harness.check(cell, served, w, control=control, threads=2)
        got[key] = [out["read"], out["failed"], out["correct"]]
    w.counters["rows"] = 1000
    got["work"] = list(cell.kind.kernel_work(
        harness.Run(cell, 1.0, w, None, "TPU v5 lite", {})))
    assert got == want


TOY_KIND = '''
"""Count query over short DNA windows: the answer is how many windows
lie within eps of the query, not which."""

import numpy as np

from bench import costs, gen
from bench import reference as plain


def make(cell, seed):
    d = cell.config["data"]
    r = gen.rng(seed, 0)
    data = r.integers(0, 4, (d["n_windows"], d["l"])).astype(np.int32)
    pool = data[r.choice(len(data), cell.traffic["pool"], replace=False)]
    return data, pool


def warm(cell, data, pool, eps):
    from repro.kernels import dispatch
    n, rows = 0, 8
    while rows <= cell.traffic["warm_rows"]:
        idx = np.arange(rows)
        dispatch.packed_batch("levenshtein", pool[idx % len(pool)],
                              data[idx % len(data)], eps=eps)
        n, rows = n + 1, 2 * rows
    return n


def serve(fleet, eps):
    return fleet.serve(eps)


def submit(engine, query, at):
    return engine.submit(query, now=at)


def answer(request):
    return len(request.hits)


def size(count):
    return 1


def reference(cell, data, queries, threads):
    return [np.sort(d) for d in plain.distances(
        "levenshtein", queries, data, threads=threads)]


def control(cell, data, queries, refs, eps, threads):
    return [int((d < eps).sum()) for d in refs]


def kernel_work(run):
    """Every row one query of ``l`` letters against a window of ``l``."""
    rows, l = run.window.counters["rows"], run.cell.config["data"]["l"]
    return (rows * costs.row_ops("levenshtein", l, l, 1),
            rows * costs.row_bytes("levenshtein", l, l, 1))


def gaps(count, ref, eps):
    """The verdicts a wrong count would flip: the windows between the
    served count and the true one, in order of distance."""
    true = int((ref <= eps).sum())
    lo, hi = sorted((count, true))
    return np.abs(ref[lo:hi] - eps)
'''


@pytest.fixture
def toy_kind(tmp_path, monkeypatch):
    """``bench/`` as it is, with one more kind file beside it."""
    import jax
    from repro.launch import compile_cache
    for folder in ("metrics", "distances", "arrivals", "popularity"):
        (tmp_path / folder).symlink_to(byname.ROOT / folder)
    (tmp_path / "kinds").mkdir()
    (tmp_path / "kinds" / "dna_count.py").write_text(
        textwrap.dedent(TOY_KIND))
    monkeypatch.setattr(byname, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    protein = json.loads((ROOT / "bench/configs/protein_lev.json")
                         .read_text())
    config = {"kind": "dna_count", "data": {"n_windows": 128, "l": 12},
              "retrieval": dict(protein["retrieval"],
                                serve_max_inflight=4),
              "control": "open_ball",
              "check": {"unanswered": 0, "wrong_verdicts": 0}}
    traffic = {"loop": "open", "arrivals": "mmpp", "rate_rps": 4.0,
               "schedule_seed": 1,
               "mmpp": {"peak_x": 3.0, "burst_s": 0.5, "period_s": 2.0},
               "popularity": "zipf", "zipf": {"s": 1.1}, "pool": 12,
               "eps": 5.0, "warm_rows": 512}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if m["name"] in ("latency_p50_ms", "setup_s")]
    cell = harness.Cell("dna.count", 1, config, traffic, e2e, [])
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    return cell


def test_kind_from_its_own_file_runs_a_cell(toy_kind):
    assert toy_kind.kind.__file__.endswith("kinds/dna_count.py")
    out = harness.run("dna.count", SEED, 2.0, False, harness.clock(),
                      threads=2)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert out["check"]["wrong_verdicts"]["value"] == 0


def test_kind_from_its_own_file_is_checked(toy_kind):
    """The kind's control, and its answers altered, fail its check."""
    cell = toy_kind
    served = harness.build(cell, SEED)
    w = harness.serve_window(served, cell.traffic, 1.5, SEED)
    assert harness.check(cell, served, w, threads=2)["correct"]
    ctrl = harness.check(cell, served, w, control=True, threads=2)
    assert not ctrl["correct"] and ctrl["read"]["wrong_verdicts"] > 0
    for s in w.sent:
        s.req.hits = list(s.req.hits) + [0]
    bad = harness.check(cell, served, w, threads=2)
    assert not bad["correct"] and bad["failed"] == len(w.sent)


def test_kind_counts_the_roofline_work(toy_kind):
    """``wavefront_roofline`` reads the kernel's work through the kind:
    the toy kind's rows are Levenshtein rows of its own length, 12."""
    cell = toy_kind
    served = harness.build(cell, SEED)
    w = harness.serve_window(served, cell.traffic, 1.0, SEED)
    rows = w.counters["rows"]
    assert rows > 0
    run_ = harness.Run(cell, 1.0, w, {"kernel_s": {"wavefront": 0.5}},
                       "TPU v5 lite", {})
    got = harness.reader("wavefront_roofline.lat")(run_)
    want, bound = costs.share(rows * 6 * 12 * 12, rows * (4 * 24 + 8), 0.5,
                              "TPU v5 lite")
    assert got == pytest.approx(want) and 0 < got < 100
    assert run_.notes["wavefront_roofline"]["bound"] == bound
    run_.trace = {"kernel_s": {}}    # no kernel in the trace: no reading
    assert harness.reader("wavefront_roofline.lat")(run_) is None
