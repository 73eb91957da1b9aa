"""Reduce a profiler trace of the measured window to device numbers.

The run wraps its window in a ``bench.window`` host span and each engine
round in ``bench.round`` (``bench.wait`` while no request is due), with
``jax.profiler.TraceAnnotation``, so the spans and the device's operations
share the trace's clock.  From the trace this module takes:

* ``window_s``: the length of the ``bench.window`` span;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` and ``Async XLA Ops`` lines of each
  ``/device:TPU:<n>`` plane), inside the window, averaged over the devices
  that ran anything;
* ``kernel_s``: per kernel, the summed device time of its events, found by
  the names in :data:`KERNELS` in the event's name or its metadata;
* ``device_ops``: the ten operations with the most device time, by HLO
  name (and custom-call target);
* ``idle_gaps``: device idle time inside the window, split by the host
  span that was innermost on the harness's thread at each instant of it,
  summed by span name, the ten largest.
"""

from __future__ import annotations

import bisect
import collections
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

#: kernel -> substrings that mark its device events.  On a TPU v5e an op
#: event's name is its HLO instruction, and the wavefront's ``pallas_call``
#: (the one Pallas kernel on the served path, in the kernel registry's
#: jitted ``traced``) shows as ``%traced.1 = (...) custom-call(...),
#: custom_call_target="tpu_custom_call", ...``.
KERNELS = {"wavefront": ("wavefront", 'custom_call_target="tpu_custom_call"')}

Span = Tuple[str, int, int]  # name, start ns, end ns


def load(log_dir) -> "object":
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(found)}")
    return ProfileData.from_file(str(found[0]))


def _events(line) -> List[Span]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _texts(event) -> Iterable[str]:
    yield event.name
    for _, v in event.stats:
        if isinstance(v, str):
            yield v


def op_name(name: str) -> str:
    """``%traced.1 tpu_custom_call`` for ``%traced.1 = (...) custom-call(
    ...), custom_call_target="tpu_custom_call", ...``."""
    short = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} {target.group(1)}" if target else short


def host_spans(profile) -> Tuple[List[Span], Span]:
    """Events of the host thread that holds the window span, and that
    span."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = _events(line)
            win = [s for s in spans if s[0] == WINDOW]
            if win:
                return spans, win[0]
    raise RuntimeError(f"no {WINDOW!r} span in the trace")


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(spans, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in spans
            if b > lo and a < hi]


def innermost(spans: List[Span]) -> Tuple[List[int], List[str]]:
    """Segment starts and labels of the innermost span at each instant
    (the spans of one thread nest)."""
    starts: List[int] = []
    labels: List[str] = []
    stack: List[Span] = []
    idle = "outside any span"

    def mark(t, label):
        if starts and starts[-1] == t:
            labels[-1] = label
        else:
            starts.append(t)
            labels.append(label)

    for s in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= s[1]:
            end = stack.pop()[2]
            mark(end, stack[-1][0] if stack else idle)
        stack.append(s)
        mark(s[1], s[0])
    while stack:
        end = stack.pop()[2]
        mark(end, stack[-1][0] if stack else idle)
    return starts, labels


def reduce(profile, kernels: Optional[Dict[str, tuple]] = None) -> dict:
    kernels = KERNELS if kernels is None else kernels
    spans, (_, w0, w1) = host_spans(profile)
    busy: List[List[Tuple[int, int]]] = []
    op_time: Dict[str, int] = collections.Counter()
    kernel_ns: Dict[str, int] = collections.Counter()
    kernel_n: Dict[str, int] = collections.Counter()
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name not in OPS_LINES:
                continue
            for e in line.events:
                a = int(e.start_ns)
                b = a + int(e.duration_ns)
                if b <= w0 or a >= w1:
                    continue
                ops.append((a, b))
                op_time[op_name(e.name)] += b - a
                texts = list(_texts(e))
                for k, marks in kernels.items():
                    if any(m in t for m in marks for t in texts):
                        kernel_ns[k] += b - a
                        kernel_n[k] += 1
        if ops:
            busy.append(union(_clip(ops, w0, w1)))
    window_ns = w1 - w0
    busy_ns = (sum(b - a for u in busy for a, b in u) / len(busy)
               if busy else 0.0)
    # idle time of the first device, split by the host's innermost span
    gaps: Dict[str, int] = collections.Counter()
    if busy:
        starts, labels = innermost(spans)
        edges = [w0] + [t for a, b in busy[0] for t in (a, b)] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            i = bisect.bisect_right(starts, a) - 1
            while a < b:
                nxt = starts[i + 1] if i + 1 < len(starts) else b
                cut = min(b, nxt)
                gaps["host: " + (labels[i] if i >= 0 else "?")] += cut - a
                a, i = cut, i + 1
    top = lambda c: [[k, v / 1e9] for k, v in c.most_common(10)]  # noqa
    return {"window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9,
            "devices": len(busy),
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "kernel_events": dict(kernel_n),
            "device_ops": top(op_time), "idle_gaps": top(gaps)}
