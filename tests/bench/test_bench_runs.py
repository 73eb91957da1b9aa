"""Each cell's harness path end to end on the CPU, at a tiny size.

The TPU check is steered from here (``harness.require_chips`` returns the
CPU devices), the Pallas kernels run in interpret mode as the platform
chooses, and the compile cache is left off.  Also: the reference against
the fleet on 512 windows, the controls and the planted faults that
``correct`` has to catch, and the refusal to run without a chip."""

import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import byname, harness, reference  # noqa: E402

CELLS = ("protein_lev.steady", "traj_erp.steady", "traj_erp.wide")
SEED = 2**31 + 77


def files_cell(name):
    """A cell made from its configuration and traffic files alone."""
    config, _ = name.split(".")
    read = lambda p: json.loads((ROOT / "bench" / p).read_text())  # noqa
    return harness.Cell(name, 1, read(f"configs/{config}.json"),
                        read(f"traffic/{name}.json"), [], [])


def shrink(cell, windows=256):
    cell.config["data"]["n_windows"] = windows
    cell.config["retrieval"]["serve_max_inflight"] = 4
    cell.traffic["pool"] = 16
    cell.traffic["warm_rows"] = 4 * windows
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_rps"] = 4.0
    else:
        cell.traffic["outstanding"] = 8
    return cell


@pytest.fixture
def tiny(monkeypatch):
    """Run cells on the CPU at a tiny size."""
    import jax
    from repro.launch import compile_cache
    real = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda n: shrink(real(n)))
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(compile_cache, "enable", lambda: None)


def run(cell, trace=False, seconds=2.0):
    return harness.run(cell, SEED, seconds, trace, harness.clock(),
                       threads=2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(tiny, cell):
    out = run(cell)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["count"] >= 1
    assert list(out)[-1] == "check"
    assert all(c["value"] <= c["limit"] for c in out["check"].values())
    json.dumps(out)


def test_closed_loop_keeps_its_requests_outstanding(tiny):
    """The closed loop: the protein mix with 8 requests outstanding."""
    cell = files_cell("protein_lev.steady")
    cell.traffic = dict(cell.traffic, loop="closed", outstanding=8)
    cell = shrink(cell)
    served = harness.build(cell, SEED)
    w = harness.serve_window(served, cell.traffic, 1.5, SEED)
    assert len(w.sent) > 8 and all(s.done >= s.due for s in w.sent)
    assert sum(s.due == w.t0 for s in w.sent) == 8
    out = harness.check(cell, served, w, threads=2)
    assert out["correct"] and out["failed"] == 0
    run_ = harness.Run(cell, 1.0, w, None, "cpu", {})
    assert harness.reader("round_ms.lat")(run_) > 0


def test_traced_run_reports_per_layer_metrics(tiny):
    cell = "traj_erp.steady"
    out = run(cell, trace=True)
    assert out["correct"]
    host = {m["name"] for m in harness.load_cell(cell).per_layer} - {
        "device_idle.lat", "wavefront_roofline.lat"}
    assert host and host <= set(out["metrics"])
    # no device plane on the CPU: the device readers find nothing
    assert "device_idle.lat" not in out["metrics"]
    assert out["device"]["window_s"] == pytest.approx(2.0, rel=0.05)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", ["protein_lev", "traj_erp"])
def test_reference_agrees_with_the_fleet_on_512_windows(tiny, name):
    cell = shrink(files_cell(f"{name}.steady"), windows=512)
    served = harness.build(cell, SEED)
    got = served.fleet.batch(served.pool).range(served.eps).hits
    dists = reference.distances(cell.distance, served.pool, served.data,
                                threads=2)
    for hits, d in zip(got, dists):
        gaps = reference.verdict_gaps(hits, d, served.eps)
        assert gaps.size == 0 or gaps.max() < 1e-3
    assert sum(map(len, got)) > len(got) // 2


def _window(served, queries):
    """Every pool query answered once by a stand-in for the program."""
    class Req:
        hits = []
    sent = [harness.Sent(q, 0.0, 0.0, Req(), done=1.0) for q in queries]
    return harness.Window(0.0, 1.0, sent, [], {}, {})


@pytest.mark.parametrize("cell, windows, pool", [
    ("protein_lev.steady", 2048, 64), ("traj_erp.steady", 4096, 256),
    ("traj_erp.wide", 4096, 256)])
def test_control_is_caught(cell, windows, pool):
    """The control in the program's place: ``correct`` comes out false.
    (Protein: the open ball ``d < eps``; ERP: the reference in
    bfloat16.)"""
    c = files_cell(cell)
    c.config["data"]["n_windows"] = windows
    c.traffic["pool"] = pool
    data, qs = harness.make_data(c, 5)
    served = harness.Served(data, qs, None, float(c.traffic["eps"]), c.kind)
    w = _window(served, range(pool))
    out = harness.check(c, served, w, control=True, threads=4)
    assert not out["correct"] and out["failed"] > 0
    read = out["read"]
    if c.config["control"] == "open_ball":
        assert read["wrong_verdicts"] > 0
    else:
        assert read["verdict_gap"] > c.config["check"]["verdict_gap"]


def _drop_half(real):
    """Packed dispatch that leaves half of every batch out: those rows
    come back as misses."""
    def packed_batch(name, xs, ys, lx=None, ly=None, **kw):
        out = real(name, xs, ys, lx, ly, **kw)
        dist, hit, pruned = (np.array(a) for a in out)
        dist[1::2], hit[1::2], pruned[1::2] = 3.4e37, False, True
        return type(out)(dist, hit, pruned)
    return packed_batch


def _alter_answer(real):
    """Every answer altered where it is produced: one hit dropped, or a
    hit on window 0 made up."""
    def finish(self, hits, now):
        return real(self, hits[1:] if hits else [0], now)
    return finish


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
def test_planted_fault_is_caught(tiny, monkeypatch, fault, cell):
    from repro.kernels import dispatch
    from repro.serve import queue
    if fault == "half_batch":
        monkeypatch.setattr(dispatch, "packed_batch",
                            _drop_half(dispatch.packed_batch))
    else:
        monkeypatch.setattr(queue.Request, "finish",
                            _alter_answer(queue.Request.finish))
    out = run(cell, seconds=3.0)
    assert not out["correct"] and out["failed"] > 0


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "protein_lev.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_chip_no_result():
    p = _run_cli(ROOT, {})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unanswered_request_fails_the_run(tiny):
    cell = shrink(files_cell("protein_lev.steady"))
    served = harness.build(cell, SEED)
    w = harness.serve_window(served, cell.traffic, 1.5, SEED)
    assert all(s.done >= s.due for s in w.sent)
    w.sent[0].done = math.nan
    out = harness.check(cell, served, w, threads=2)
    assert out["read"]["unanswered"] == 1 and out["failed"] == 1
    assert not out["correct"]


def test_compile_inside_the_window_fails_the_run(tiny, monkeypatch):
    """Shapes left cold until the window compile there: no result.  (The
    fleet's build compiles some classes itself, so the planted warm-up
    drops every compiled kernel.)"""
    from repro.kernels import registry
    monkeypatch.setattr(byname.module("kinds", "window_range"), "warm",
                        lambda *a, **k: registry.clear_cache())
    with pytest.raises(harness.CompiledInWindow, match="kernel_traces"):
        run("protein_lev.steady")


def test_served_path_runs_no_envelope_kernel(tiny, monkeypatch):
    """The ERP cell's envelope tier screens on the host: the window needs
    no device envelope class warmed."""
    from repro.kernels import dispatch

    def refuse(*a, **k):
        raise AssertionError("the served path ran the envelope kernel")
    monkeypatch.setattr(dispatch, "packed_envelope", refuse)
    out = run("traj_erp.steady")
    assert out["correct"] and out["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("script, args", [
    ("calibrate", ["--seeds", str(SEED), "--seconds", "1"]),
    ("sweep", ["--seed", str(SEED), "--rates", "2", "4", "--seconds",
               "1"]),
    ("split", ["--seed", str(SEED), "--seconds", "1"])])
def test_chip_scripts_drive_each_cell(tiny, monkeypatch, cell, script,
                                     args):
    """``bench/calibrate.py``, ``sweep.py`` and ``split.py`` through the
    harness, with the chip steered to the CPU."""
    import importlib
    lines = []
    monkeypatch.setattr(harness, "emit", lambda line: lines.append(
        json.loads(json.dumps(line))))
    main = importlib.import_module(f"bench.{script}").main
    assert main(["--workload", cell] + args) == 0
    if script == "calibrate":
        assert lines[0]["correct"] and lines[0]["requests"] > 0
        assert set(lines[0]["control"]) == set(lines[0]["program"])
    elif script == "sweep":
        assert [x["rate_rps"] for x in lines[1:]] == [2.0, 4.0]
        assert all(x["largest_round_rows"] > 0 for x in lines[1:])
    else:
        assert lines[0]["workload"] == cell and lines[0]["ticks"] > 0
