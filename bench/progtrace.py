"""Reduce the program's own spans in a profiler trace of the window.

The served path marks each layer boundary of a round with a host span
(``repro.spans``; :data:`PROGRAM` lists their names), on the same thread
and clock as the harness's ``bench.window``.  :func:`program_spans` gives,
for each program span name on that thread, clipped to the window:

* ``count``: the spans that overlap the window;
* ``incl_s``: their summed duration;
* ``self_s``: that less the part covered by program spans nested inside
  them, so JAX's own events (``DevicePut``, ``PjitFunction``, the
  blocking ``np.asarray``) count toward the program span around them;
* ``stats``: the sum of each numeric count the spans carry.

:func:`stages` turns that into the round's split, per ``serve.tick``;
:func:`kernel_inside` says how much of a kernel's device time lies inside
given program spans, which shows the two clocks agree.  A trace of a
program without these spans gives ``{}`` and ``None`` throughout.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, Optional

from bench import devtrace

PROGRAM = ("serve.tick", "serve.admit", "serve.finalize", "fleet.round",
           "fleet.lb", "fleet.gather", "fleet.evaluate", "fleet.resume",
           "dispatch.pack", "dispatch.pad", "dispatch.launch",
           "dispatch.fetch", "dispatch.unpack")


def _window_line(profile):
    """The host line that holds the window span, and the span's ends."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            for e in events:
                if e.name == devtrace.WINDOW:
                    a = int(e.start_ns)
                    return events, (a, a + int(e.duration_ns))
    raise RuntimeError(f"no {devtrace.WINDOW!r} span in the trace")


def program_spans(profile) -> Dict[str, dict]:
    events, (w0, w1) = _window_line(profile)
    spans = []
    for e in events:
        if e.name not in PROGRAM:
            continue
        a = int(e.start_ns)
        b = a + int(e.duration_ns)
        if b > w0 and a < w1:
            spans.append((e.name, max(a, w0), min(b, w1), e.stats))
    out: Dict[str, dict] = {}
    covered = [0] * len(spans)   # time of each span under nested spans
    stack = []                   # indices of the open spans
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    for i in order:
        name, a, b, stats = spans[i]
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            covered[stack[-1]] += b - a
        stack.append(i)
        rec = out.setdefault(name, {"count": 0, "incl_s": 0.0,
                                    "self_s": 0.0, "stats": {}})
        rec["count"] += 1
        for k, v in stats:
            if isinstance(v, (int, float)):
                rec["stats"][k] = rec["stats"].get(k, 0) + v
    for (name, a, b, _), c in zip(spans, covered):
        out[name]["incl_s"] += (b - a) / 1e9
        out[name]["self_s"] += (b - a - c) / 1e9
    return out


def stages(spans: Dict[str, dict]) -> Dict[str, Optional[float]]:
    """The round's split: milliseconds per ``serve.tick`` in each stage,
    the padded share of the cells dispatched, and the bytes sent to the
    device per requested row.  ``None`` where a span is missing."""
    ticks = spans.get("serve.tick", {}).get("count", 0)

    def ms(names: Iterable[str], key: str) -> Optional[float]:
        recs = [spans[n] for n in names if n in spans]
        if not ticks or not recs:
            return None
        return 1e3 * sum(r[key] for r in recs) / ticks

    def stat(name: str, key: str) -> float:
        return spans.get(name, {}).get("stats", {}).get(key, 0)

    engine_self = None
    if ticks and "fleet.round" in spans:
        engine_self = 1e3 * (spans["serve.tick"]["incl_s"]
                             - spans["fleet.round"]["incl_s"]) / ticks
    cells = stat("dispatch.pad", "cells")
    padded_cells = stat("dispatch.pad", "padded_cells")
    rows = stat("dispatch.pad", "rows")
    h2d = stat("dispatch.launch", "h2d_bytes")
    return {
        "serve_tick_ms": ms(["serve.tick"], "incl_s"),
        "engine_self_ms": engine_self,
        "plan_resume_ms": ms(["fleet.resume"], "self_s"),
        "gather_ms": ms(["fleet.gather"], "self_s"),
        "lb_screen_ms": ms(["fleet.lb"], "self_s"),
        "pack_ms": ms(["dispatch.pack", "dispatch.pad", "dispatch.unpack"],
                      "self_s"),
        "launch_ms": ms(["dispatch.launch"], "incl_s"),
        "fetch_ms": ms(["dispatch.fetch"], "incl_s"),
        "padded_cell_share": (100.0 * (1.0 - cells / padded_cells)
                              if padded_cells else None),
        "h2d_bytes_per_row": h2d / rows if rows and h2d else None,
    }


def kernel_inside(profile, names, kernel: str = "wavefront"
                  ) -> Optional[float]:
    """Share of ``kernel``'s device time in the window that lies inside
    the program spans named ``names`` (on the window's thread); ``None``
    where the kernel ran no event there."""
    events, (w0, w1) = _window_line(profile)
    inside = devtrace.union(
        devtrace._clip([(int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in events if e.name in names], w0, w1))
    ends = [y for _, y in inside]
    marks = devtrace.KERNELS[kernel]
    total = covered = 0
    for plane in profile.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in devtrace.OPS_LINES:
                continue
            for e in line.events:
                if not any(m in t for m in marks
                           for t in devtrace._texts(e)):
                    continue
                a = max(int(e.start_ns), w0)
                b = min(int(e.start_ns + e.duration_ns), w1)
                if b <= a:
                    continue
                total += b - a
                j = bisect.bisect_right(ends, a)
                while j < len(inside) and inside[j][0] < b:
                    covered += min(b, inside[j][1]) - max(a, inside[j][0])
                    j += 1
    return covered / total if total else None


def spans_per_tick(spans: Dict[str, dict]) -> Optional[float]:
    ticks = spans.get("serve.tick", {}).get("count", 0)
    return (sum(r["count"] for r in spans.values()) / ticks
            if ticks else None)
