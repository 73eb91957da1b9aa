"""One run of one cell: set-up, the measured window, the check.

Everything a cell is made of is found by name:

* the cell's entry in ``BENCHMARK.json`` names its configuration and its
  traffic mix, and the metrics it reports;
* ``bench/configs/<config>.json``: the request kind (``"kind"``,
  ``window_range`` where absent), the data and its sizes, the
  ``RetrievalConfig`` the program is built with, and what ``correct``
  compares (``check``: each number's limit; ``control``: the control's
  kind);
* ``bench/kinds/<kind>.py``: everything that depends on what a request
  is: data and pool, warm-up, submitting and reading answers, the
  reference, the control and the comparison (``window_range.py`` lists
  what a kind provides);
* ``bench/traffic/<cell>.json``: the mix, read by :mod:`bench.load`;
* ``bench/metrics/<metric>.py`` (or ``<metric up to its first dot>.py``):
  ``read(run)`` returns the metric's value, or ``None`` where it finds
  nothing to read.

The program is driven through its public path only: ``Retriever.build``,
the engine the kind opens on the fleet and its ``tick``, from this one
thread, with every request stamped with the time it was due.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import pathlib
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import byname, devtrace, load

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
clock = time.perf_counter
#: threads of the reference scan, which runs after the window
THREADS = min(12, os.cpu_count() or 1)


def program() -> None:
    """Put this checkout's program first on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class NoChip(RuntimeError):
    pass


class CompiledInWindow(RuntimeError):
    """Something was traced or compiled inside the measured window."""


def require_chips(n: int) -> list:
    """The TPU devices, at least ``n`` of them; anything else is refused."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devices)}")
    return devices


# -- the cell, from its files -------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def distance(self) -> str:
        return self.config["retrieval"]["distance"]

    @property
    def kind(self):
        """The module of the configuration's request kind."""
        return byname.module("kinds", self.config.get("kind",
                                                      "window_range"))


def load_cell(name: str, spec_path: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    spec = json.loads(spec_path.read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    config = json.loads((ROOT / files[cell["config"]]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads",
                                                           [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in e2e_names else [])]
    return Cell(name, int(cell["chips"]), config, traffic, e2e, per_layer)


def reader(metric: str):
    """``read`` of ``bench/metrics/<metric>.py``, else of the file named by
    the metric's name up to its first dot."""
    for stem in (metric, metric.split(".")[0]):
        try:
            return byname.module("metrics", stem).read
        except FileNotFoundError:
            continue
    raise FileNotFoundError(f"no reader for metric {metric!r}")


# -- set-up --------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What set-up hands the window: data, query pool, the built fleet,
    and the request kind that submits to it."""
    data: np.ndarray
    pool: np.ndarray
    fleet: object
    eps: float
    kind: object


def make_data(cell: Cell, seed: int):
    """The database and the query pool, from the seed."""
    return cell.kind.make(cell, seed)


def build(cell: Cell, seed: int) -> Served:
    """Data from the seed, the fleet through ``Retriever.build``, and
    every kernel shape the window can reach compiled and run once."""
    program()
    from repro.retrieval import RetrievalConfig, Retriever
    data, pool = make_data(cell, seed)
    rc = RetrievalConfig.from_dict(cell.config["retrieval"])
    fleet = Retriever.build(rc, data)
    eps = float(cell.traffic["eps"])
    cell.kind.warm(cell, data, pool, eps)
    gc.collect()   # set-up's garbage goes in set-up, not in the window
    return Served(data, pool, fleet, eps, cell.kind)


# -- the window ----------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    query: int          # pool index
    due: float
    sent: float
    req: object         # the engine's Request
    done: float = math.nan


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    sent: List[Sent]
    ticks: List[tuple]           # (start, end) of each engine tick
    counters: Dict[str, float]   # program counters over the window
    engine: Dict[str, int]       # engine_stats() at the window's close

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def answered(self) -> List[Sent]:
        return [s for s in self.sent if not math.isnan(s.done)]


def _counters() -> Dict[str, float]:
    from repro.kernels import dispatch, registry
    st = dispatch.STATS
    return {"dispatches": st.dispatches, "rows": st.rows,
            "kernel_traces": registry.STATS["traces"],
            "jax_traces": _COMPILES[_TRACE_EVENT],
            "jax_compiles": _COMPILES[_COMPILE_EVENT]}


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = {_TRACE_EVENT: 0, _COMPILE_EVENT: 0}
#: the counters that have to stay still inside the window
IN_WINDOW_ZERO = ("kernel_traces", "jax_traces", "jax_compiles")


def _count_traces() -> None:
    """Count every JAX trace and XLA compile in the process."""
    if "listener" in _COMPILES:
        return
    import jax

    def listener(event, duration, **_):
        if event in (_TRACE_EVENT, _COMPILE_EVENT):
            _COMPILES[event] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    _COMPILES["listener"] = listener


class _GcPauses:
    """Python's garbage collections while it is registered: how many of
    each generation, and the longest pause."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest = 0.0
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = clock()
        else:
            self.count[info["generation"]] += 1
            self.longest = max(self.longest, clock() - self._t)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def serve_window(served: Served, traffic: dict, seconds: float, seed: int,
                 *, drain_s: float = 60.0, on_close=None) -> Window:
    """Drive ``Retriever.serve`` from this thread for ``seconds``.

    Open loop: each request is submitted once its due time has passed and
    stamped with that due time.  Closed loop: ``outstanding`` requests,
    each answered one replaced at once.  After the close, requests still
    open are served for at most ``drain_s`` more; ``on_close`` runs at the
    close (the tracer stops there)."""
    kind = served.kind
    engine = kind.serve(served.fleet, served.eps)
    pool, stream = served.pool, load.query_stream(traffic, seed)
    closed = traffic["loop"] == "closed"
    due = (np.zeros(int(traffic["outstanding"])) if closed
           else load.arrivals(traffic, seconds))
    sent: List[Sent] = []
    by_rid: Dict[int, Sent] = {}
    ticks: List[tuple] = []
    from repro.kernels.dispatch import STATS as stats
    largest = [0]   # rows of the largest round
    longest = [0.0, 0, 0.0]   # the longest tick: seconds, rows, start
    objects = len(gc.get_objects())
    pauses = _GcPauses()
    gc.callbacks.append(pauses)
    c0 = _counters()

    def submit(q: int, at: float) -> None:
        req = kind.submit(engine, pool[q], at)
        s = Sent(q, at, clock(), req)
        sent.append(s)
        by_rid[req.rid] = s

    def tick() -> List[Sent]:
        n = stats.dispatches
        with _annotate("bench.round"):
            ts = clock()
            done = engine.tick(now=ts)
            te = clock()
        ticks.append((ts, te))
        rows = 0
        if stats.dispatches != n:
            rows = sum(b[2] for b in stats.last_meta.buckets)
            largest[0] = max(largest[0], rows)
        if te - ts > longest[0] and te <= end:
            longest[:] = [te - ts, rows, ts - t0]
        out = [by_rid[r.rid] for r in done]
        for s in out:
            s.done = te
        return out

    t0 = clock()
    end = t0 + seconds
    i = 0
    open_ = 0
    with _annotate(devtrace.WINDOW):
        if closed:
            for _ in due:
                submit(next(stream), t0)
            open_ = len(due)
        while True:
            now = clock()
            if now >= end:
                break
            if not closed:
                while i < len(due) and t0 + due[i] <= now:
                    submit(next(stream), t0 + due[i])
                    i += 1
                    open_ += 1
            if open_:
                finished = tick()
                open_ -= len(finished)
                if closed:
                    for s in finished:
                        if s.done < end:
                            submit(next(stream), s.done)
                            open_ += 1
            else:
                nxt = t0 + due[i] if i < len(due) else end
                with _annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - clock()))
    c1 = _counters()
    gc.callbacks.remove(pauses)
    engine_stats = engine.engine_stats()
    if on_close is not None:
        on_close()
    while i < len(due) and not closed:   # due before the close, not sent
        submit(next(stream), t0 + due[i])
        i += 1
        open_ += 1
    limit = end + drain_s
    while open_ and clock() < limit:
        open_ -= len(tick())
    counters = {k: c1[k] - c0[k] for k in c0}
    counters["largest_round_rows"] = largest[0]
    counters["longest_tick"] = {"ms": 1e3 * longest[0], "rows": longest[1],
                                "at_s": longest[2]}
    counters["gc"] = {"objects": objects, "collections": pauses.count,
                      "longest_ms": 1e3 * pauses.longest}
    return Window(t0, seconds, sent, ticks, counters, engine_stats)


# -- the check -----------------------------------------------------------------

def _numbers(window: Window, answer_of, refs, gaps, eps: float) -> dict:
    """Unanswered requests, wrong verdicts, and the widest gap by which a
    wrong verdict's reference distance lies from ``eps``."""
    n_unanswered = n_wrong = 0
    gap = 0.0
    per_request = []
    for s in window.sent:
        if math.isnan(s.done):
            n_unanswered += 1
            per_request.append(math.inf)
            continue
        g = gaps(answer_of(s), refs[s.query], eps)
        n_wrong += len(g)
        worst = float(g.max()) if len(g) else -1.0
        gap = max(gap, worst)
        per_request.append(worst)
    return {"unanswered": n_unanswered, "wrong_verdicts": n_wrong,
            "verdict_gap": gap, "per_request": per_request}


def _failed(per_request: List[float], limits: Dict[str, float]) -> int:
    """Requests unanswered (read as inf), or with a wrong verdict that the
    compared numbers forbid: any, under ``wrong_verdicts``; one further
    than the limit from ``eps``, under ``verdict_gap``."""
    allowed = limits.get("verdict_gap",
                         -1.0 if "wrong_verdicts" in limits else math.inf)
    return sum(g > allowed for g in per_request)


def check(cell: Cell, served: Served, window: Window, *,
          control: bool = False, threads: int = THREADS) -> dict:
    """The served answers (or, with ``control``, the control's) against
    the plain reference of the cell's request kind.  Returns the compared
    numbers with their limits, the failed requests, and the other numbers
    read."""
    kind = cell.kind
    queries = sorted({s.query for s in window.sent})
    ref = kind.reference(cell, served.data, served.pool[queries], threads)
    refs = dict(zip(queries, ref))
    eps = served.eps
    if not control:
        answer_of = lambda s: kind.answer(s.req)  # noqa: E731
    else:
        answers = dict(zip(queries, kind.control(
            cell, served.data, served.pool[queries], ref, eps, threads)))
        answer_of = lambda s: answers[s.query]  # noqa: E731
    nums = _numbers(window, answer_of, refs, kind.gaps, eps)
    limits = cell.config["check"]
    compared = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
    return {"compared": compared,
            "correct": all(c["value"] <= c["limit"]
                           for c in compared.values()),
            "failed": _failed(nums["per_request"], limits),
            "read": {k: nums[k] for k in ("unanswered", "wrong_verdicts",
                                          "verdict_gap")}}


# -- one run -------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    setup_s: float
    window: Window
    trace: Optional[dict]
    device_kind: str
    notes: Dict[str, dict]


def percentile_ms(values, q: float) -> Optional[float]:
    return 1e3 * float(np.percentile(values, q)) if len(values) else None


def latencies(window: Window) -> List[float]:
    return [s.done - s.due for s in window.answered()]


def device_info(devices, n: int) -> dict:
    used = devices[:n]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(line: dict, stream=sys.stdout) -> None:
    print(json.dumps(line), file=stream, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, threads: int = THREADS) -> dict:
    """Set-up, window and check of one cell; returns the result line."""
    cell = load_cell(workload)
    devices = require_chips(cell.chips)
    program()
    from repro.launch import compile_cache
    compile_cache.enable()
    _count_traces()
    served = build(cell, seed)
    traced = _tracer() if trace else contextlib.nullcontext()
    with traced as tracer:
        setup_s = clock() - t_start
        window = serve_window(served, cell.traffic, seconds, seed,
                              on_close=getattr(tracer, "stop", None))
    traced_in_window = {k: window.counters[k] for k in IN_WINDOW_ZERO}
    if any(traced_in_window.values()):
        raise CompiledInWindow(f"traces inside the window: "
                               f"{traced_in_window}")
    device = device_info(devices, cell.chips)
    reduced = tracer.result if trace else None
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    served.fleet = None                       # the program's state goes
    verdict = check(cell, served, window, threads=threads)
    run_ = Run(cell, setup_s, window, reduced, device["kind"], {})
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lat = latencies(window)
    late = [s.sent - s.due for s in window.sent]
    worst = max(window.sent, key=lambda s: s.sent - s.due, default=None)
    emit({"window": {
        "requests": len(window.sent), "answered": len(lat),
        "lateness_ms": {"p50": percentile_ms(late, 50),
                        "p95": percentile_ms(late, 95),
                        "max": percentile_ms(late, 100),
                        "max_at_s": worst and worst.due - window.t0},
        "hits_per_request": (float(np.mean([
            cell.kind.size(cell.kind.answer(s.req))
            for s in window.answered()])) if lat else None),
        "ticks": len(window.ticks), "counters": window.counters,
        "engine": window.engine, "notes": run_.notes,
        "check_read": verdict["read"]}})
    result = {"correct": bool(verdict["correct"]),
              "attempted": len(window.sent), "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = verdict["compared"]
    return result


class _tracer:
    """The profiler over the window, stopped at its close and reduced."""

    def __enter__(self):
        import tempfile

        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self.result = None
        return self

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def __exit__(self, *exc):
        import shutil
        self.stop()
        try:
            if exc[0] is None:
                self.result = devtrace.reduce(devtrace.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False
