"""The ``protein_match.steady`` cell on the CPU at a tiny size: its request
kind (``bench/kinds/subseq_range.py``) end to end through the harness as
it is, the ``open_ball`` control and two planted faults (pairs dropped, a
distance altered) caught by ``correct``, and the kernel work counted at
real lengths.  The wavefront runs as its ``lax.scan`` twin here: the
Pallas interpreter is too slow for 60 x 62 start-pair rows."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import byname, harness  # noqa: E402
from test_bench_runs import SEED, files_cell  # noqa: E402

CELL = "protein_match.steady"


def shrink(cell):
    """4 sequences (80 windows) and 48-residue queries (135 segments, 2 %
    of residues substituted, so that pairs within eps exist): a
    round holds at most 2 requests x 135 segments x 80 windows, under
    the 2 ** 15 filter rows warmed, whatever the timing."""
    cell.config["data"]["n_seqs"] = 4
    cell.config["retrieval"]["serve_max_inflight"] = 2
    cell.traffic.update(pool=4, rate_rps=2.0, warm_rows=1 << 15,
                        verify_warm_rows=1 << 13)
    cell.traffic["queries"] = dict(length=48, subst=0.02)
    return cell


@pytest.fixture
def tiny(monkeypatch):
    import jax
    from repro.kernels import registry
    from repro.launch import compile_cache
    real = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda n: shrink(real(n)))
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(registry, "_DEFAULT_EXEC", "scan")


def run(trace=False):
    return harness.run(CELL, SEED, 2.0, trace, harness.clock(), threads=2)


def test_cell_runs_end_to_end(tiny):
    out = run(trace=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in out["check"].values())
    host = {"verify_cells_per_request", "segment_rows_per_request",
            "candidates_ms", "rounds_per_request", "round_ms.lat"}
    assert host <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] > 0 for m in host)
    json.dumps(out)


def test_config_and_traffic_as_the_benchmark_reads_them():
    cell = harness.load_cell(CELL)
    assert cell.kind is byname.module("kinds", "subseq_range")
    assert cell.chips == 1 and cell.config["retrieval"]["lam"] == 40
    assert {m["name"] for m in cell.end_to_end} == {"latency_p50_ms",
                                                    "setup_s"}
    t = cell.traffic
    assert t["rate_rps"] * 50 >= 40     # 40 requests a 50 s window
    for k in ("warm_rows", "verify_warm_rows"):
        assert t[k] & (t[k] - 1) == 0


def test_data_and_pool_repeat_per_seed():
    cell = shrink(files_cell(CELL))
    data, pool = harness.make_data(cell, SEED)
    assert data.shape == (4, 400) and data.dtype == np.int32
    assert pool.shape == (4, 48) and pool.max() < 20
    again = harness.make_data(cell, SEED)
    assert np.array_equal(data, again[0]) and np.array_equal(pool, again[1])
    assert not np.array_equal(data, harness.make_data(cell, SEED + 1)[0])


def _serve(cell, served):
    """The fleet's answers to every pool query, one request each."""
    engine = cell.kind.serve(served.fleet, served.eps)
    reqs = [cell.kind.submit(engine, q, 0.0) for q in served.pool]
    while not all(r.done for r in reqs):
        engine.tick(now=0.0)
    sent = [harness.Sent(i, 0.0, 0.0, r, done=1.0)
            for i, r in enumerate(reqs)]
    return harness.Window(0.0, 1.0, sent, [], {}, engine.engine_stats())


def test_control_and_planted_faults_are_caught(tiny):
    """The open ball in the program's place, and the program's answers
    with half of each request's pairs dropped, or one distance altered:
    each turns ``correct`` false; the answers as served do not."""
    cell = harness.load_cell(CELL)
    served = harness.build(cell, SEED)
    w = _serve(cell, served)
    assert sum(len(s.req.hits) for s in w.sent) > 0
    assert harness.check(cell, served, w, threads=2)["correct"]
    ctrl = harness.check(cell, served, w, control=True, threads=2)
    assert not ctrl["correct"] and ctrl["read"]["wrong_verdicts"] > 0
    for fault in ("drop", "alter"):
        for s in w.sent:
            hits = list(s.req.hits)
            if fault == "drop":
                hits = hits[::2]
            elif hits:
                p = hits[0]
                hits[0] = type(p)(*p.key(), p.distance + 1.0)
            s.req.hits = hits
        out = harness.check(cell, served, w, threads=2)
        assert not out["correct"] and out["failed"] > 0, fault


def test_kernel_work_counts_real_cells(tiny, monkeypatch):
    """Operations are 6 per real DP cell of every filter and verify row
    the engine dispatched, fewer than the padded cells."""
    from repro.kernels import dispatch
    cell = harness.load_cell(CELL)
    calls = []
    for fn in ("packed_batch", "packed_band"):
        real = getattr(dispatch, fn)

        def spy(name, xs, ys, lx=None, ly=None, _real=real, **kw):
            calls.append((np.dot(np.asarray(lx, np.int64), ly),
                          len(xs) * xs.shape[1] * ys.shape[1]))
            return _real(name, xs, ys, lx, ly, **kw)
        monkeypatch.setattr(dispatch, fn, spy)
    served = harness.build(cell, SEED)
    calls.clear()
    w = _serve(cell, served)
    run_ = harness.Run(cell, 1.0, w, None, "cpu", {})
    ops, nbytes = cell.kind.kernel_work(run_)
    e = w.engine
    assert e["verify_cells"] > 0 and e["filter_cells"] > 0
    assert ops == 6 * (e["filter_cells"] + e["verify_cells"])
    assert ops == 6 * sum(c[0] for c in calls)
    assert ops < 6 * sum(c[1] for c in calls)
    assert nbytes > 4 * e["verify_band_cells"] > 0


@pytest.mark.parametrize("script, args", [
    ("calibrate", ["--seeds", str(SEED), "--seconds", "1"]),
    ("sweep", ["--seed", str(SEED), "--rates", "1", "2", "--seconds",
               "1"]),
    ("split", ["--seed", str(SEED), "--seconds", "1"])])
def test_chip_scripts_drive_the_cell(tiny, monkeypatch, script, args):
    """``bench/calibrate.py``, ``sweep.py`` and ``split.py`` on the match
    cell, with the chip steered to the CPU: the control is caught."""
    import importlib
    lines = []
    monkeypatch.setattr(harness, "emit", lambda line: lines.append(
        json.loads(json.dumps(line))))
    main = importlib.import_module(f"bench.{script}").main
    assert main(["--workload", CELL] + args) == 0
    if script == "calibrate":
        assert lines[0]["correct"] and not lines[0]["control_correct"]
    elif script == "sweep":
        assert [x["rate_rps"] for x in lines[1:]] == [1.0, 2.0]
    else:
        assert lines[0]["workload"] == CELL and lines[0]["ticks"] > 0
