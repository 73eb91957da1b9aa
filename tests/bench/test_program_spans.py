"""The program's own spans: their reduction on a small synthetic trace
(``program_trace.pbtxt`` says what it holds), and the spans a served
fleet writes on the CPU under the profiler."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, progtrace  # noqa: E402

HERE = pathlib.Path(__file__).parent
US = 1e-6

#: each program span and the span it nests in
PARENT = {"serve.admit": "serve.tick", "serve.finalize": "serve.tick",
          "fleet.round": "serve.tick", "fleet.lb": "fleet.round",
          "fleet.gather": "fleet.round", "fleet.evaluate": "fleet.round",
          "fleet.resume": "fleet.round", "dispatch.pack": "fleet.evaluate",
          "dispatch.pad": "fleet.evaluate",
          "dispatch.launch": "fleet.evaluate",
          "dispatch.fetch": "fleet.evaluate",
          "dispatch.unpack": "fleet.evaluate"}


def profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto((HERE / name).read_text())


@pytest.fixture(scope="module")
def synthetic():
    return profile("program_trace.pbtxt")


@pytest.fixture(scope="module")
def spans(synthetic):
    return progtrace.program_spans(synthetic)


def test_counts_and_clipping(spans):
    # tick A opens before the window: clipped, still counted; its
    # serve.admit lies wholly before the window and is not
    assert "serve.admit" not in spans
    assert {k: v["count"] for k, v in spans.items()} == {
        k: 2 for k in progtrace.PROGRAM if k != "serve.admit"}
    assert spans["serve.tick"]["incl_s"] == pytest.approx(630 * US)
    assert spans["fleet.round"]["incl_s"] == pytest.approx(560 * US)


def test_self_time_against_nested_program_spans(spans):
    self_us = {k: round(v["self_s"] / US, 6) for k, v in spans.items()}
    assert self_us["serve.tick"] == 20      # less rounds and finalizes
    assert self_us["fleet.round"] == 10     # less its four stages
    assert self_us["fleet.evaluate"] == 5   # less the five dispatch spans


def test_jax_events_count_inside_their_program_span(spans):
    # DevicePut and PjitFunction nest in dispatch.launch, np.asarray in
    # dispatch.fetch: none of them is a program span
    for name in ("dispatch.launch", "dispatch.fetch"):
        assert spans[name]["self_s"] == pytest.approx(spans[name]["incl_s"])
    assert spans["dispatch.fetch"]["incl_s"] == pytest.approx(330 * US)


def test_stat_sums(spans):
    assert spans["dispatch.pad"]["stats"] == {
        "rows": 16, "padded_rows": 24, "cells": 6400, "padded_cells": 9600}
    assert spans["fleet.lb"]["stats"] == {"lb_rows": 18, "lb_pruned": 2}
    assert spans["dispatch.launch"]["stats"] == {"h2d_bytes": 1600}
    assert spans["serve.finalize"]["stats"]["hits"] == 3


def test_stages_per_tick_add_up(spans):
    st = progtrace.stages(spans)
    want_ms = {"serve_tick_ms": 315, "engine_self_ms": 35,
               "plan_resume_ms": 15, "gather_ms": 15, "lb_screen_ms": 10,
               "pack_ms": 27.5, "launch_ms": 40, "fetch_ms": 165}
    for k, us in want_ms.items():
        assert st[k] == pytest.approx(us * 1e-3), k
    assert st["padded_cell_share"] == pytest.approx(100 / 3)
    assert st["h2d_bytes_per_row"] == pytest.approx(100.0)
    # what the stages leave of the tick is fleet.round's and
    # fleet.evaluate's own time, per tick
    rest = st["serve_tick_ms"] - sum(st[k] for k in want_ms
                                     if k != "serve_tick_ms")
    assert rest == pytest.approx(1e3 * (spans["fleet.round"]["self_s"]
                                        + spans["fleet.evaluate"]["self_s"])
                                 / 2)
    assert progtrace.spans_per_tick(spans) == pytest.approx(12)


def test_kernel_time_inside_launch_and_fetch(synthetic):
    # 60 + 180 us inside fetches, 15 us in an unpack; 500-600 is outside
    assert progtrace.kernel_inside(
        synthetic, ("dispatch.launch", "dispatch.fetch")) \
        == pytest.approx(240 / 255)


def test_old_trace_has_no_program_spans():
    old = profile("window_trace.pbtxt")
    spans = progtrace.program_spans(old)
    assert spans == {}
    assert set(progtrace.stages(spans).values()) == {None}
    assert progtrace.spans_per_tick(spans) is None
    assert progtrace.kernel_inside(old, ("dispatch.fetch",)) == 0.0
    assert set(devtrace.reduce(old)) == {
        "window_s", "busy_s", "devices", "kernel_s", "kernel_events",
        "device_ops", "idle_gaps"}


def test_names_are_the_programs():
    from repro import spans
    assert progtrace.PROGRAM == spans.NAMES


# -- a served fleet on the CPU -------------------------------------------------


def _events(prof):
    events, _ = progtrace._window_line(prof)
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
             dict(e.stats)) for e in events
            if e.name in progtrace.PROGRAM]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A small ERP fleet with the envelope tier served under the profiler
    until two requests are answered."""
    import jax

    from repro.data.synthetic import trajectories
    from repro.kernels import dispatch, registry
    from repro.retrieval import RetrievalConfig, Retriever
    data = trajectories(96, l=8, seed=3)
    fleet = Retriever.build(RetrievalConfig(
        "erp", execution="fleet", workers=2, kernel_backend="pallas",
        tight_bounds=True, lb_cascade="envelope"), data)
    engine = fleet.serve(eps=1.0)
    reqs = [engine.submit(data[i] + 0.05, now=0.0) for i in (5, 40)]
    d = tmp_path_factory.mktemp("trace")
    rows0, ticks = dispatch.STATS.rows, 0
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            while not all(r.done for r in reqs):
                engine.tick(now=0.0)
                ticks += 1
    prof = devtrace.load(d)
    return {"prof": prof, "events": _events(prof), "reqs": reqs,
            "ticks": ticks, "rows": dispatch.STATS.rows - rows0,
            "stats": engine.engine_stats(),
            "P": {k.batch for k in registry.cache_keys()}}


def test_served_fleet_writes_every_span_nested(served):
    ev = served["events"]
    assert {e[0] for e in ev} == set(progtrace.PROGRAM)
    for name, a, b, _ in ev:
        if name in PARENT:
            assert any(p == PARENT[name] and pa <= a and b <= pb
                       for p, pa, pb, _ in ev), name
    ticks = [e for e in ev if e[0] == "serve.tick"]
    assert len(ticks) == served["ticks"]
    assert sum(e[3]["admitted"] for e in ticks) == 2


def test_served_fleet_counts(served):
    from repro.kernels.registry import _pad_pow2
    ev, reqs = served["events"], served["reqs"]
    pads = [e[3] for e in ev if e[0] == "dispatch.pad"]
    assert sum(p["rows"] for p in pads) == served["rows"] > 0
    for p in pads:
        assert p["padded_rows"] == _pad_pow2(max(p["rows"], 8))
        assert p["padded_rows"] in served["P"]
        assert p["cells"] <= p["padded_cells"]
    rids = {r.rid for r in reqs}
    assert {e[3]["rid"] for e in ev if e[0] == "serve.admit"} == rids
    fin = {e[3]["rid"]: e[3]["hits"] for e in ev
           if e[0] == "serve.finalize"}
    assert fin == {r.rid: len(r.hits) for r in reqs}
    lb = [e[3] for e in ev if e[0] == "fleet.lb"]
    assert sum(s["lb_rows"] for s in lb) == served["stats"]["lb_rows"]
    assert sum(s["lb_pruned"] for s in lb) == served["stats"]["lb_pruned"]
    rounds = [e[3] for e in ev if e[0] == "fleet.round"]
    assert len(rounds) == served["stats"]["rounds"]
    assert sum(s["rows"] for s in rounds) == served["rows"]
    st = progtrace.stages(progtrace.program_spans(served["prof"]))
    assert all(v is not None for v in st.values()), st
    assert np.isfinite(list(st.values())).all()
