"""Continuous-batching retrieval serve engine.

(Until PR 9 this module held a dormant LLM prefill/decode engine — an
artifact of the training stack with no retrieval surface, including the
never-implemented ``Engine.hidden_states`` stub.  It is replaced
wholesale: serving *retrieval* is the subsystem the substrate was built
for.)

The engine turns the round-based fleet substrate into a front end for
asynchronous traffic.  Requests submitted at any time land in a
:class:`~repro.serve.queue.RequestQueue`; each :meth:`ServeEngine.tick`

1. applies a pending fleet swap (zero-downtime resize) at the round
   boundary,
2. admits queued requests up to ``max_inflight`` — each request becomes
   one :class:`~repro.core.batch_engine.ShardPlans` group per alive
   shard, joined to the shared cadence via
   :meth:`FleetBatchEngine.admit`, and
3. advances EVERY in-flight request's frontier by ONE merged round —
   one packed ``kernels/dispatch.packed_batch`` call across all
   requests, shards, and length buckets — retiring finished requests'
   rows immediately.

Subsequence matching (type I, paper §7): an engine opened with a
:class:`~repro.core.matching.MatchScope` serves match requests, a whole
query sequence in and every similar (database subsequence, query
subsequence) pair out.  Such a request goes through three stages:

* **admission** runs Algorithm 3 for every query segment (step 3) on
  every shard: one range plan per shard with one row per segment
  (``range_query_rows_plan``; a window request's plan has one row), the
  segments' ragged rows in its ``ShardPlans``;
* the shared rounds run those plans like any other (step 4);
* **verify**: once its plans are done, its (segment, window) hits give
  the §7 candidate envelope, grouped by start pair (``match.candidates``);
  each start pair is one row of the tick's one band-mode wavefront
  dispatch (``match.verify``, ``dispatch.packed_band``), in the tick its
  plans finished; **collect** keeps each candidate end cell within ε, and
  the request finishes with those pairs, sorted.

Window requests take the path above unchanged.

Admission policy: ``"tick"`` (default) merges newcomers straight into
the next shared round — strictly fewest dispatches; ``"greedy"`` gives
newcomers one dedicated round first when older requests are already
mid-flight, trading an extra dispatch for not making deep-frontier
stragglers gate a newcomer's first rows.

Zero-downtime resize: :meth:`ServeEngine.resize` snapshots the live
fleet (:class:`~repro.serve.snapshot.FleetSnapshotManager` — atomic
write, latest pointer), restores a clone, reshards the CLONE while the
original keeps serving, then swaps at the next round boundary.
In-flight requests captured their shard groups (plans + gids) at admit
time and finish against the old arrays — hit sets are
shard-layout-invariant over the same windows, so exactness holds across
the swap; new admissions serve from the resharded fleet.

Latency accounting rides the request records themselves
(submit/admit/first-dispatch/complete timestamps, rounds carried);
:meth:`ServeEngine.latency_stats` reduces them to p50/p95/p99.  Two
clocks drive the same machinery: :meth:`start`/:meth:`submit` serve
wall-clock traffic on a background thread, :meth:`run_schedule` replays
a deterministic arrival schedule on a virtual clock — the count-strict
benchmark gate (``benchmarks/bench_serve.py``) uses the latter.
"""

from __future__ import annotations

import dataclasses
import math
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro import spans
from repro.core.batch_engine import FleetBatchEngine, ShardPlans
from repro.core.matching import MatchScope, StartPairs
from repro.kernels import dispatch
from repro.serve.queue import Request, RequestQueue
from repro.serve.snapshot import FleetSnapshotManager

#: admission policies (see the module docstring)
ADMISSION_POLICIES = ("tick", "greedy")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serve-engine knobs (mirrored by ``RetrievalConfig.serve_*``)."""
    eps: float = 1.0                    # default query radius
    max_inflight: int = 32              # in-flight request cap
    admission: str = "tick"             # "tick" | "greedy"
    snapshot_dir: Optional[str] = None  # default: a fresh temp dir
    snapshot_keep: int = 3

    def __post_init__(self):
        if self.max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1; got {self.max_inflight}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"admission must be one of {ADMISSION_POLICIES}; "
                f"got {self.admission!r}")


#: ``engine_stats()`` counters of the match path's verify stage, and of
#: the largest rounds (``largest_filter_rows``: the merged round;
#: ``largest_verify_rows``: the verify dispatch).  ``candidates``:
#: candidate pairs, ``verify_rows``: their start pairs, ``candidates_ns``:
#: host time spent building them; ``verify_cells``: the verify rows' real
#: ``len_x * len_y`` summed, ``verify_elems`` their ``len_x + len_y``,
#: ``verify_band_cells`` the end cells they emit.  (The merged rounds'
#: rows are ``filter_rows``/``filter_cells``/``filter_elems``.)
COUNTERS = ("match_requests", "segment_plans", "segment_hits",
            "candidates", "verify_rows", "verify_cells", "verify_elems",
            "verify_band_cells", "match_pairs", "candidates_ns",
            "largest_filter_rows", "largest_verify_rows")


@dataclasses.dataclass
class _Verify:
    """A match request whose plans are done, and its start pairs."""
    req: Request
    pairs: StartPairs


def _snapshot_builds(fleet) -> int:
    """Plan snapshots built by the fleet's shard nets, summed."""
    return sum(s.net.snapshot_builds for s in fleet.shards.values()
               if s is not None)


class ServeEngine:
    """Continuous-batching front end over an ElasticIndex fleet."""

    def __init__(self, fleet, config: Optional[ServeConfig] = None, *,
                 clock=time.monotonic, scope: Optional[MatchScope] = None):
        self.fleet = fleet
        self.config = config or ServeConfig()
        #: the sequences and windows behind the fleet (match requests)
        self.scope = scope
        self.counts = dict.fromkeys(COUNTERS, 0)
        self._verifying: List[_Verify] = []
        self.clock = clock
        self.queue = RequestQueue()
        evaluate, fused = fleet._round_evaluator()
        # ONE long-lived engine: the evaluator closes over the distance
        # name + kernel policy only (shape-generic), so it keeps serving
        # across fleet swaps
        self._engine = FleetBatchEngine(evaluate, fused=fused)
        #: bid -> (request, per-group gids captured at admit time, and a
        #: match request's segment starts and lengths)
        self._inflight: Dict[int, Tuple[Request, List[np.ndarray],
                                        Optional[tuple]]] = {}
        self.completed: List[Request] = []
        self.swaps = 0
        self._pending_swap = None
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._snap: Optional[FleetSnapshotManager] = None

    # -- submission ---------------------------------------------------------

    def submit(self, query: np.ndarray, eps: Optional[float] = None, *,
               tag: Optional[object] = None,
               now: Optional[float] = None,
               match: Optional[bool] = None) -> Request:
        """Enqueue a range query; returns its handle (``req.result()``
        blocks until served when the engine runs on a thread).  ``match``
        (default: whether the engine has a match scope) makes it a
        subsequence-match request, ``query`` a whole query sequence."""
        match = self.scope is not None if match is None else bool(match)
        if match and self.scope is None:
            raise ValueError("match requests need an engine opened with a "
                             "MatchScope (a retriever built with lam)")
        return self.queue.submit(
            query, self.config.eps if eps is None else eps, tag=tag,
            now=self.clock() if now is None else now, match=match)

    # -- admission + rounds -------------------------------------------------

    def _lb_hook(self, fleet):
        """Envelope-cascade hook over THIS fleet's precomputed per-window
        envelopes (same tier as ``ElasticIndex._round_query``); bound to
        each admitted group so pre- and post-swap requests screen against
        the fleet that admitted them."""
        if fleet.lb_cascade != "envelope":
            return None
        from repro.distances import bounds as dist_bounds
        envs = {}
        for si, w in enumerate(fleet.workers):
            s = fleet.shards.get(w)
            if s is not None and s.flat.envelopes is not None:
                envs[si] = s.flat.envelopes
        if not envs:
            return None
        name = fleet.dist.name

        def hook(shard, idxs, q, q_len):
            e = envs[shard].take(idxs)
            xs = np.repeat(q[None], len(idxs), 0)
            return dist_bounds.lb_envelope_rows(
                name, xs, np.full(len(idxs), q_len, np.int64),
                e.lo, e.hi, e.mass)

        return hook

    def _admit_one(self, req: Request, now: float) -> Optional[int]:
        with spans.span(spans.SERVE_ADMIT, rid=req.rid) as sp:
            fleet = self.fleet
            builds = _snapshot_builds(fleet)
            q = np.asarray(req.query)
            segs = None
            if req.match:
                with spans.span(spans.MATCH_SEGMENTS, rid=req.rid) as ms:
                    qpad, q_lens, starts = self.scope.segments(q)
                    segs = (starts, q_lens)
                    ms.set_metadata(segments=len(q_lens))
            else:
                qpad, q_lens = q[None], np.asarray([len(q)], np.int64)
            hook = self._lb_hook(fleet)
            groups: List[ShardPlans] = []
            gids: List[np.ndarray] = []
            for si, w in enumerate(fleet.workers):
                s = fleet.shards.get(w)
                if s is None:
                    continue
                # one plan per shard over the request's query rows: the
                # query, or a match request's segments
                groups.append(ShardPlans(
                    shard=si, data=s.net.data,
                    plans=[s.net.range_query_rows_plan(req.eps,
                                                       len(q_lens))],
                    queries=qpad, q_lens=q_lens, lb=hook))
                gids.append(s.gids)
            if req.match:
                self.counts["match_requests"] += 1
                self.counts["segment_plans"] += len(q_lens) * len(groups)
            req.t_admit = now
            bid = self._engine.admit(groups, req.eps)
            self._inflight[bid] = (req, gids, segs)
            # plan snapshots this admission built (0 once every shard's
            # is cached)
            sp.set_metadata(snapshot_builds=_snapshot_builds(fleet) - builds)
        if self._engine.is_finished(bid):  # e.g. an empty fleet
            self._finalize(bid, now)
            return None
        return bid

    def _finalize(self, bid: int, now: float) -> Optional[Request]:
        """A batch whose plans are done: a window request finishes, a
        match request goes on to verify (None, unless it has no
        candidate)."""
        req, gids, segs = self._inflight.pop(bid)
        with spans.span(spans.SERVE_FINALIZE, rid=req.rid) as sp:
            per_group = self._engine.results(bid)
            if segs is not None:
                pairs = self._candidates(req, gids, segs, per_group)
                sp.set_metadata(hits=len(pairs))
                if len(pairs):
                    self._verifying.append(_Verify(req, pairs))
                    return None
                hits = []
            else:
                hits = set()
                for g, res in zip(gids, per_group):
                    hits.update(int(g[x]) for x in res[0][0])
                hits = sorted(hits)
                sp.set_metadata(hits=len(hits))
            req.finish(hits, now)
        self.completed.append(req)
        return req

    def _candidates(self, req: Request, gids, segs, per_group
                    ) -> StartPairs:
        """Steps 4 -> 5: the request's (segment, window) hits as global
        windows, and their candidate envelope by start pair."""
        with spans.span(spans.MATCH_CANDIDATES, rid=req.rid) as sp:
            t0 = time.perf_counter_ns()
            plan, win = [], []
            for g, res in zip(gids, per_group):
                for i, local in enumerate(res[0]):   # one list per segment
                    if local:
                        plan.append(np.full(len(local), i, np.int64))
                        win.append(g[np.asarray(local, np.int64)])
            plan = np.concatenate(plan) if plan else np.zeros(0, np.int64)
            win = np.concatenate(win) if win else np.zeros(0, np.int64)
            starts, lens = segs
            pairs = self.scope.candidates(len(req.query), starts[plan],
                                          lens[plan], win)
            n_cand = int(pairs.mask.sum())
            sp.set_metadata(hits=len(win), candidates=n_cand,
                            rows=len(pairs))
            c = self.counts
            c["segment_hits"] += len(win)
            c["candidates"] += n_cand
            c["candidates_ns"] += time.perf_counter_ns() - t0
        return pairs

    def _verify_round(self, now: float) -> List[Request]:
        """The tick's one verify dispatch over every request in verify:
        one band-mode row per start pair, then each request's pairs."""
        jobs, self._verifying = self._verifying, []
        scope, fleet, c = self.scope, self.fleet, self.counts
        with spans.span(spans.MATCH_VERIFY, requests=len(jobs)) as sp:
            rows = [scope.verify_rows(j.req.query, j.pairs) for j in jobs]
            lx = np.concatenate([j.pairs.lx for j in jobs])
            ly = np.concatenate([j.pairs.ly for j in jobs])
            eps = np.concatenate([np.full(len(j.pairs), j.req.eps,
                                          np.float32) for j in jobs])
            band = dispatch.packed_band(
                fleet.dist.name, np.concatenate([r[0] for r in rows]),
                np.concatenate([r[1] for r in rows]), lx, ly,
                lam=scope.lam, lambda0=scope.lambda0, eps=eps,
                interpret=fleet.interpret, exec=fleet.kernel_exec,
                tile=fleet.kernel_tile)
            sp.set_metadata(rows=len(lx))
        c["verify_rows"] += len(lx)
        c["verify_cells"] += int(np.dot(lx, ly))
        c["verify_elems"] += int(lx.sum() + ly.sum())
        c["verify_band_cells"] += scope.band_cells(lx, ly)
        c["largest_verify_rows"] = max(c["largest_verify_rows"], len(lx))
        done, off = [], 0
        for j in jobs:
            n, req = len(j.pairs), j.req
            req.rounds += 1
            with spans.span(spans.MATCH_COLLECT, rid=req.rid) as cs:
                pairs = scope.collect(j.pairs, band[off:off + n], req.eps)
                cs.set_metadata(pairs=len(pairs))
            c["match_pairs"] += len(pairs)
            req.finish(pairs, now)
            self.completed.append(req)
            done.append(req)
            off += n
        return done

    def _round(self, now: float,
               only: Optional[Set[int]] = None) -> List[Request]:
        """One merged round over the in-flight set (or the ``only``
        subset); stamps first-dispatch times, retires finished rows."""
        parts = self._engine.batches_in_flight()
        if only is not None:
            parts &= only
        for bid in parts:
            req = self._inflight[bid][0]
            req.rounds += 1
            if math.isnan(req.t_first_dispatch):
                req.t_first_dispatch = now
        finished = self._engine.step(only=only)
        c = self.counts
        c["largest_filter_rows"] = max(c["largest_filter_rows"],
                                       self._engine.last_rows)
        done = (self._finalize(bid, now) for bid in finished)
        return [r for r in done if r is not None]

    def tick(self, now: Optional[float] = None) -> List[Request]:
        """One scheduler beat: swap -> admit -> (greedy round) -> shared
        round.  Returns the requests completed this tick."""
        with self._lock, spans.span(spans.SERVE_TICK) as sp:
            now = self.clock() if now is None else now
            if self._pending_swap is not None:  # round boundary: safe swap
                self.fleet = self._pending_swap
                self._pending_swap = None
                self.swaps += 1
            had_inflight = bool(self._inflight)
            budget = self.config.max_inflight - len(self._inflight)
            newly: Set[int] = set()
            taken = self.queue.take(max(budget, 0))
            for req in taken:
                bid = self._admit_one(req, now)
                if bid is not None:
                    newly.add(bid)
            sp.set_metadata(admitted=len(taken),
                            inflight=len(self._inflight))
            done: List[Request] = []
            if self.config.admission == "greedy" and had_inflight and newly:
                # dedicated first round: newcomers dispatch immediately
                # instead of waiting on the shared cadence
                done.extend(self._round(now, only=newly))
            if self._engine.active:
                done.extend(self._round(now))
            if self._verifying:
                done.extend(self._verify_round(now))
            return done

    # -- zero-downtime resize ----------------------------------------------

    def _snapshot_manager(self) -> FleetSnapshotManager:
        if self._snap is None:
            d = self.config.snapshot_dir or tempfile.mkdtemp(
                prefix="repro-serve-snap-")
            self._snap = FleetSnapshotManager(
                d, keep=self.config.snapshot_keep)
        return self._snap

    def snapshot(self, block: bool = True) -> int:
        """Snapshot the live fleet; returns the snapshot step."""
        with self._lock:
            return self._snapshot_manager().save(self.fleet, block=block)

    def resize(self, workers: Sequence[str], *, block: bool = True) -> None:
        """Reshard with zero downtime: snapshot -> restore a clone ->
        resize the CLONE (the live fleet keeps serving) -> stage the swap
        for the next round boundary.  ``block=False`` runs the rebuild on
        a background thread (the wall-clock serving mode)."""
        workers = list(workers)

        def work():
            snap = self._snapshot_manager()
            with self._lock:
                step = snap.save(self.fleet, block=True)
            clone = snap.restore(step)
            clone.resize(workers)           # off the serving path
            with self._lock:
                self._pending_swap = clone

        if block:
            work()
        else:
            threading.Thread(target=work, daemon=True).start()

    # -- deterministic virtual-clock serving --------------------------------

    def run_schedule(self, queries: Sequence[np.ndarray],
                     arrivals: Sequence[float], *,
                     eps: Optional[float] = None, round_cost: float = 1.0,
                     resize_at: Optional[float] = None,
                     resize_to: Optional[Sequence[str]] = None
                     ) -> List[Request]:
        """Replay an arrival schedule on a virtual clock (deterministic:
        fixed arrivals + fixed ``round_cost`` per merged round -> identical
        admission pattern, dispatch counts, and latency numbers every run).
        Optionally triggers a zero-downtime ``resize(resize_to)`` at
        virtual time ``resize_at``.  Returns requests in submit order."""
        arrivals = np.asarray(arrivals, np.float64)
        assert len(queries) == len(arrivals)
        reqs: List[Request] = []
        i, n = 0, len(queries)
        t = 0.0
        resized = resize_at is None
        while True:
            if not resized and t >= resize_at:
                self.resize(resize_to)
                resized = True
            while i < n and arrivals[i] <= t:
                reqs.append(self.submit(queries[i], eps=eps,
                                        now=float(arrivals[i])))
                i += 1
            before = self._engine.rounds
            self.tick(now=t)
            t += round_cost * max(1, self._engine.rounds - before)
            if self._engine.active or len(self.queue):
                continue
            if i >= n and resized:
                break
            # idle: jump the clock to the next event (arrival or resize)
            pending = [float(arrivals[i])] if i < n else []
            if not resized:
                pending.append(float(resize_at))
            t = max(t, min(pending))
        return reqs

    # -- wall-clock serving -------------------------------------------------

    def start(self) -> "ServeEngine":
        """Serve on a background thread until :meth:`close`."""
        assert self._thread is None, "already started"
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                with self._lock:
                    idle = not (self._engine.active or len(self.queue)
                                or self._pending_swap is not None)
                if idle:
                    time.sleep(1e-3)
                else:
                    self.tick()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def close(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the serving thread; ``drain`` serves everything queued or
        in flight first."""
        if self._thread is None:
            return
        if drain:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    busy = (self._engine.active or len(self.queue)
                            or self._pending_swap is not None)
                if not busy:
                    break
                time.sleep(1e-3)
        self._stop.set()
        self._thread.join(timeout)
        self._thread = None

    # -- accounting ---------------------------------------------------------

    def engine_stats(self) -> Dict[str, int]:
        """Shared-cadence totals (merged rounds, eval split, swaps), the
        plan snapshots the fleet's shard nets have built, the merged
        rounds' rows (``filter_rows``) with their real cells and elements,
        and the match path's :data:`COUNTERS`."""
        e = self._engine
        return {"rounds": e.rounds, "exact_evals": e.exact_evals,
                "verdict_evals": e.verdict_evals,
                "fused_pruned": e.fused_pruned,
                "lb_rows": e.lb_rows, "lb_pruned": e.lb_pruned,
                "submitted": self.queue.submitted,
                "completed": len(self.completed), "swaps": self.swaps,
                "plan_snapshot_builds": _snapshot_builds(self.fleet),
                "filter_rows": e.exact_evals + e.verdict_evals,
                "filter_cells": e.cells, "filter_elems": e.elems,
                **self.counts}

    def latency_stats(self) -> Dict[str, float]:
        """Per-request latency percentiles over the completed set (clock
        units: seconds in wall-clock mode, virtual time under
        :meth:`run_schedule`)."""
        done = [r for r in self.completed if r.done]
        if not done:
            return {"n": 0}
        lat = np.array([r.latency for r in done], np.float64)
        out = {"n": len(done),
               "p50": float(np.percentile(lat, 50)),
               "p95": float(np.percentile(lat, 95)),
               "p99": float(np.percentile(lat, 99)),
               "mean": float(lat.mean()),
               "mean_rounds": float(np.mean([r.rounds for r in done]))}
        qd = np.array([r.queue_delay for r in done
                       if not math.isnan(r.t_first_dispatch)], np.float64)
        if len(qd):
            out["queue_p50"] = float(np.percentile(qd, 50))
            out["queue_p99"] = float(np.percentile(qd, 99))
        return out
