"""The Reference Net (paper §6 + Appendix A) — host-mode implementation.

A hierarchical metric index with levels ``i = 0 .. r-1``:

* level radius ``eps_i = eps' * 2**i``;
* *inclusive*: every reference at level i-1 is within ``eps_i`` of at least
  one level-i reference (it has >= 1 parent);
* *exclusive*: two references at the same level i are > ``eps_i`` apart;
* a node may have **multiple parents** (the net/tree distinction of Fig. 2),
  capped at ``num_max`` to keep space linear;
* the bottom layer holds *all* database objects: an object within ``eps_0``
  of some level-0 reference is stored as a plain member of that reference's
  list, otherwise it becomes a level-0 (or higher) reference itself;
* each reference is stored once, at its highest level (paper §6), and each
  list link records the (conceptual) level at which it was formed — in the
  paper a reference has a separate list per level it appears at; recording
  the attach level preserves those per-level radii in flattened storage.

Range queries implement Algorithm 3 / Lemma 4 as *bound propagation*: every
processed reference R with known d = delta(Q, R) contributes, through each
of its list links, an interval for the child and for the child's whole
derived subtree:

    d(Q, c)        in  [d - r_link,        d + r_link]
    d(Q, subtree)  in  [d - r_link - sr_c, d + r_link + sr_c]

where, in **faithful** mode (the paper's Lemma 4), ``r_link = eps_i`` of the
attach level and ``sr_c = eps_{level(c)+1}``; in **tight** mode (a
beyond-paper refinement, cf. M-tree) ``r_link`` is the exact stored link
distance and ``sr_c`` the exact maintained subtree radius.  With multiple
parents the intervals *intersect* — this is precisely the Fig. 2 advantage:
every additional parent is another chance to decide a child for free.
Children are resolved lazily (objects at the very end, expandable references
just before their own level), so every parent that gets processed
contributes its bound before any distance evaluation is spent.

The query plan runs on arrays, not on the node dicts: a
:class:`PlanSnapshot` (levels, subtree radii, and per level a CSR of the
lists with their link radii) is built on first use and cached until a
method that changes the net (``insert``, ``build_batched``, ``delete``,
``extend_data``) drops it, so one build serves every query until the next
change.  The plan keeps its per-query state in arrays over the database
rows and propagates bounds one level at a time with numpy; a subtree a
bound settles is marked at its top only, and the mark is passed down each
level's links just before the level below is visited, so no settled
subtree is walked node by node.  That needs every list to hold nodes of
lower levels only, which ``delete``'s re-homing keeps.

All distance evaluations go through :class:`CountedDistance`, so pruning
ratios reported by the benchmarks are exact evaluation counts.

Construction mirrors querying: Alg. 1's widened descent is a frontier
*plan* (:meth:`ReferenceNet.insert_plan`) that yields per-level candidate
batches and returns a pure :class:`InsertOutcome`; ``insert`` drives one
plan sequentially (classic counts), while :meth:`ReferenceNet.build_batched`
drives whole cohorts of plans through the batch engine and commits them
after order-rank conflict arbitration — same invariants and hit sets, far
fewer backend dispatches.  Build-time evaluations are charged to the
counter's ``build`` bucket, never to the paper's query currency.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core import batch_engine
from repro.core.counter import CountedDistance
from repro.distances import base as dist_base

OBJ = -1  # pseudo-level of plain (non-reference) objects
INF = float("inf")


@dataclasses.dataclass
class InsertOutcome:
    """Result of an :meth:`ReferenceNet.insert_plan` descent.

    A pure description of *where* object ``idx`` lands — the plan never
    mutates the net, so many plans can run concurrently against one
    snapshot and be committed (or re-planned) afterwards by the bulk
    loader's arbitration."""
    idx: int
    new_top: int                 # required root level (>= top at plan time)
    target_level: int            # stored level of the new node (OBJ = member)
    attach_level: int            # conceptual level of the new links
    owners: Dict[int, float]     # candidate parents -> exact distance


@dataclasses.dataclass
class Node:
    idx: int                   # row in the data array
    level: int                 # highest level at which this node is a reference
    children: List[int]        # node idxs appearing in my list
    child_dist: List[float]    # exact delta(me, child) per link
    child_level: List[int]     # conceptual level the link was formed at
    parents: List[int]         # up-links (multi-parent; len <= num_max)
    sub_radius: float = 0.0    # exact derived-subtree radius (maintained)


@dataclasses.dataclass(frozen=True)
class PlanSnapshot:
    """The net's structure as arrays, for
    :meth:`ReferenceNet.range_query_plan`.

    Per row of the net's database: the subtree radius as
    ``_subtree_radius`` gives it and whether the row's node has a list
    (rows that hold no node read 0 and False).  The lists form one CSR
    per level: ``levels[l]`` holds the ids of the level's list holders in
    ascending order, the offsets of each holder's links, and the links'
    child ids and radii (``_link_radius``) in list order.  Links to
    deleted nodes are left out.  Plain objects hold no list, and every
    list holds nodes of lower levels only."""
    rows: int
    sub_radius: np.ndarray         # (rows,) float64
    has_children: np.ndarray       # (rows,) bool
    #: level (top_level .. 0) -> (holder ids, link offsets, child ids,
    #: link radii)
    levels: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]


def _links(ptr: np.ndarray, pos: np.ndarray):
    """The link positions of the list holders at ``pos`` in a level's CSR
    (``ptr``), in list order, and each holder's link count."""
    start = ptr[pos]
    cnt = ptr[pos + 1] - start
    ends = np.cumsum(cnt)
    return (np.arange(ends[-1] if ends.size else 0)
            + np.repeat(start - ends + cnt, cnt)), cnt


class ReferenceNet:
    """Host-mode reference net over a fixed-length window database.

    Args:
      tight_bounds: False = paper-faithful Lemma-4 radii (eps powers);
        True = exact link distances / subtree radii (beyond-paper, strictly
        tighter, same O(n) space).
    """

    def __init__(self, dist, data: np.ndarray, *,
                 eps_prime: float = 1.0, num_max: Optional[int] = None,
                 tight_bounds: bool = False,
                 counter: Optional[CountedDistance] = None):
        # registry name or Distance instance, interchangeably
        self.dist = dist_base.require_metric(dist)
        self.eps_prime = float(eps_prime)
        self.num_max = num_max
        self.tight_bounds = tight_bounds
        self.counter = counter or CountedDistance(self.dist, data)
        self.data = self.counter.data
        self.nodes: Dict[int, Node] = {}
        self.root: Optional[int] = None
        self.top_level: int = 0
        self._snapshot: Optional[PlanSnapshot] = None
        self.snapshot_builds = 0   # PlanSnapshot builds (one per change)
        self.plans = 0             # range-query plans started

    # -- radii ------------------------------------------------------------

    def eps(self, i: int) -> float:
        """Level radius eps_i = eps' * 2**i  (eps_{OBJ} treated as 0)."""
        if i < 0:
            return 0.0
        return self.eps_prime * (2.0 ** i)

    def _link_radius(self, node: Node, k: int) -> float:
        if self.tight_bounds:
            return node.child_dist[k]
        return self.eps(node.child_level[k])

    def _subtree_radius(self, node: Node) -> float:
        if not node.children:
            return 0.0
        if self.tight_bounds:
            return node.sub_radius
        return self.eps(node.level + 1)

    # -- array snapshot for range queries ------------------------------------

    def plan_snapshot(self) -> PlanSnapshot:
        """The cached :class:`PlanSnapshot` of the net, built on first use
        after a change; every method that changes the structure, a radius
        or the database drops it."""
        if self._snapshot is None:
            self._snapshot = self._build_snapshot()
            self.snapshot_builds += 1
        return self._snapshot

    def _changed(self) -> None:
        self._snapshot = None

    def _build_snapshot(self) -> PlanSnapshot:
        rows = len(self.data)
        level = np.full(rows, OBJ, np.int64)
        sub_radius = np.zeros(rows)
        has_children = np.zeros(rows, bool)
        holders: Dict[int, List[int]] = {lv: [] for lv in
                                         range(self.top_level + 1)}
        for x, node in self.nodes.items():
            level[x] = node.level
            sub_radius[x] = self._subtree_radius(node)
            if node.children:
                assert node.level >= 0, f"plain object {x} holds a list"
                has_children[x] = True
                holders[node.level].append(x)
        levels = {}
        for lv, ids in holders.items():
            ids.sort()
            ptr = [0]
            child: List[int] = []
            link_r: List[float] = []
            for x in ids:
                node = self.nodes[x]
                for k, c in enumerate(node.children):
                    if c in self.nodes:
                        child.append(c)
                        link_r.append(self._link_radius(node, k))
                ptr.append(len(child))
            levels[lv] = (np.asarray(ids, np.int64),
                          np.asarray(ptr, np.int64),
                          np.asarray(child, np.int64),
                          np.asarray(link_r, np.float64))
            # Algorithm 3 visits levels top-down: a list may only hold
            # nodes of lower levels
            assert (level[levels[lv][2]] < lv).all(), \
                f"a level-{lv} list holds a node of its level or above"
        return PlanSnapshot(rows, sub_radius, has_children, levels)

    # -- construction -------------------------------------------------------

    def build(self, order: Optional[Sequence[int]] = None) -> "ReferenceNet":
        """Sequential loader (one insert-plan descent per object); see
        :meth:`build_batched` for the cohort bulk loader."""
        idxs = range(len(self.data)) if order is None else order
        for i in idxs:
            self.insert(i)
        return self

    def extend_data(self, rows: np.ndarray) -> List[int]:
        """Append fresh windows to the net's database without touching the
        built structure; returns their new row indices.

        The rows are *not* inserted — feed the returned indices to
        :meth:`build_batched` (``order=new_ids``) to bulk-load them through
        the cohort pipeline against the existing net.  This is the elastic
        layer's reshard-in path: a shard that gains windows extends and
        bulk-loads instead of rebuilding from scratch."""
        rows = np.asarray(rows)
        self._changed()
        base = len(self.counter.data)
        self.counter.extend(rows)
        self.data = self.counter.data
        return list(range(base, base + len(rows)))

    def insert(self, idx: int) -> None:
        """Insert object ``idx``: the sequential ``drive()`` of
        :meth:`insert_plan` — evaluation counts and the resulting structure
        are bit-identical to the historical pair-at-a-time descent."""
        if self.root is None:
            self._changed()
            self.root = idx
            self.top_level = 0
            self.nodes[idx] = Node(idx, 0, [], [], [], [])
            return
        out = batch_engine.drive(self.insert_plan(idx), self.counter,
                                 self.data[idx])
        self._apply_insert(out)

    def insert_plan(self, idx: int) -> batch_engine.Plan:
        """Alg. 1's widened descent as a frontier plan (same Frontier/send
        protocol as :meth:`range_query_plan`, build-bucket accounting).

        Yields per-level EXACT frontiers of reference idxs, receives their
        distances to ``data[idx]``, and returns an :class:`InsertOutcome`
        describing where the object lands — without mutating the net, so
        ``build_batched`` can run whole cohorts of these concurrently
        against one snapshot and arbitrate conflicts before committing.
        """
        assert self.root is not None, "seed the net with one insert() first"
        ds = yield batch_engine.Frontier(
            np.asarray([self.root], np.int64), batch_engine.EXACT,
            bucket=batch_engine.BUILD)
        d_root = float(ds[0])
        # the root's level must grow until it covers the new point; recorded
        # in the outcome and applied at commit time
        top = self.top_level
        while d_root > self.eps(top):
            top += 1

        # descend, keeping the *wide* frontier: refs with d <= 2*eps_i; any
        # same-level conflict below is reachable through such ancestors
        # (chain bound: eps_l + sum_{t=l+1..i} eps_t <= 2*eps_i).
        frontier: Dict[int, float] = {self.root: d_root}
        parents_at: Dict[int, Dict[int, float]] = {}
        level = top
        parents_at[level] = {
            n: d for n, d in frontier.items() if d <= self.eps(level)}
        while level > 0:
            cand: Set[int] = set()
            for n in frontier:
                for c in self.nodes[n].children:
                    if c in self.nodes and self.nodes[c].level == level - 1:
                        cand.add(c)
                # a reference conceptually appears at every level below its
                # top; keep it in the running frontier
                cand.add(n)
            cand_new = [c for c in cand if c not in frontier]
            dists: Dict[int, float] = {}
            if cand_new:
                ds = yield batch_engine.Frontier(
                    np.asarray(cand_new, np.int64), batch_engine.EXACT,
                    bucket=batch_engine.BUILD)
                dists.update(zip(cand_new, map(float, ds)))
            dists.update({c: frontier[c] for c in cand if c in frontier})
            level -= 1
            frontier = {c: d for c, d in dists.items()
                        if d <= 2.0 * self.eps(level)}
            parents_at[level] = {
                c: d for c, d in dists.items() if d <= self.eps(level)}
            if not frontier:
                break

        # Alg. 1 "jumps to the lowest possible level": X becomes a reference
        # one level below the lowest covered level m.  Exclusivity at m-1 is
        # guaranteed: any level-(m-1) conflict would have been discovered
        # through the wide frontier.
        m = None
        for l in range(0, top + 1):
            if parents_at.get(l):
                m = l
                break
        assert m is not None, "root must cover the new point after growth"
        if m == 0:
            # within eps_0 of a level-0 reference -> plain object (bottom)
            return InsertOutcome(idx, top, OBJ, 0, parents_at[0])
        return InsertOutcome(idx, top, m - 1, m, parents_at[m])

    def _apply_insert(self, out: InsertOutcome) -> None:
        """Commit a planned insert: grow the root, then attach."""
        self._changed()
        while self.top_level < out.new_top:
            self.top_level += 1
            self.nodes[self.root].level = self.top_level
        self._attach(out.idx, out.target_level, out.owners,
                     attach_level=out.attach_level)

    def build_batched(self, order: Optional[Sequence[int]] = None, *,
                      max_cohort: int = 256,
                      engine: Optional["batch_engine.BatchEngine"] = None
                      ) -> "ReferenceNet":
        """Level-synchronous bulk loader: cohorts of concurrent insert plans.

        Each round takes a cohort of not-yet-inserted objects, runs all
        their :meth:`insert_plan` descents against the *current* net through
        the :class:`~repro.core.batch_engine.BatchEngine` (pairwise mode —
        one merged dispatch per descent level instead of one per object per
        level), then commits the outcomes.  Two cohort members that would
        both become references at the same level may violate the exclusive
        property; :meth:`_commit_cohort` detects those pairs with one
        batched dispatch and resolves them by deterministic order-rank
        arbitration — the earlier object in ``order`` wins, the loser is
        re-planned in the next cohort against the updated net (where it
        typically lands *under* the winner).  The result passes
        ``check_invariants()`` and returns identical range-query hit sets
        to a sequentially built net, with far fewer backend dispatches
        (``counter.build_dispatches``; see ``benchmarks/bench_build.py``).

        Cohort sizes double from 4 up to ``max_cohort`` — the early net is
        coarse and conflict-prone, the late net absorbs large cohorts with
        almost no arbitration.
        """
        idxs = list(range(len(self.data))) if order is None else \
            [int(i) for i in order]
        rank = {x: r for r, x in enumerate(idxs)}
        pending = [i for i in idxs if i not in self.nodes]
        if self.root is None and pending:
            self.insert(pending.pop(0))
        eng = engine or batch_engine.BatchEngine(self.counter)
        cohort = 4
        while pending:
            take, pending = pending[:cohort], pending[cohort:]
            plans = [self.insert_plan(i) for i in take]
            outs = eng.run(plans, np.asarray(take, np.int64), eps=0.0)
            deferred = self._commit_cohort(outs, rank)
            pending = deferred + pending
            cohort = min(2 * cohort, max_cohort)
        return self

    def _commit_cohort(self, outs: Sequence[InsertOutcome],
                       rank: Dict[int, int]) -> List[int]:
        """Commit one cohort's outcomes; return the re-plan (loser) idxs.

        Conflicts only arise between two *new* references at the same
        stored level (each plan's wide frontier already rules out conflicts
        with snapshot references), so it suffices to evaluate intra-cohort
        same-level pairs — one batched dispatch — and accept greedily in
        order-rank."""
        outs = sorted(outs, key=lambda o: rank[o.idx])
        groups: Dict[int, List[int]] = {}
        for o in outs:
            if o.target_level >= 0:
                groups.setdefault(o.target_level, []).append(o.idx)
        pairs = [(a, b) for grp in groups.values()
                 for i, a in enumerate(grp) for b in grp[i + 1:]]
        pair_d: Dict[Tuple[int, int], float] = {}
        if pairs:
            ds = self.counter.eval_pairs([a for a, _ in pairs],
                                         [b for _, b in pairs])
            pair_d = {p: float(d) for p, d in zip(pairs, ds)}
        accepted: List[InsertOutcome] = []
        deferred: List[int] = []
        winners_at: Dict[int, List[int]] = {}
        for o in outs:
            if o.target_level >= 0:
                eps_l = self.eps(o.target_level)
                if any(pair_d[(w, o.idx)] <= eps_l
                       for w in winners_at.get(o.target_level, ())):
                    deferred.append(o.idx)
                    continue
                winners_at.setdefault(o.target_level, []).append(o.idx)
            accepted.append(o)
        for o in accepted:
            self._apply_insert(o)
        return deferred

    def _attach(self, idx: int, level: int, owners: Dict[int, float],
                attach_level: int) -> None:
        assert owners, "inclusive property would be violated"
        self._changed()
        ranked = sorted(owners.items(), key=lambda kv: kv[1])
        if self.num_max is not None:
            ranked = ranked[: self.num_max]
        node = Node(idx, level, [], [], [], [p for p, _ in ranked])
        self.nodes[idx] = node
        for p, d in ranked:
            pn = self.nodes[p]
            pn.children.append(idx)
            pn.child_dist.append(d)
            pn.child_level.append(attach_level)
            self._grow_radius(p, d)  # node.sub_radius starts at 0

    def _grow_radius(self, p: int, new_r: float) -> None:
        """Propagate an enlarged subtree radius up the parent DAG.

        Iterative (explicit stack): multi-parent DAGs built from large n can
        be deep enough that the recursive form hits Python's recursion
        limit; the <=-check still cuts every already-covered branch."""
        self._changed()
        stack = [(p, new_r)]
        while stack:
            x, r = stack.pop()
            xn = self.nodes[x]
            if r <= xn.sub_radius:
                continue
            xn.sub_radius = r
            for gp in xn.parents:
                gpn = self.nodes.get(gp)
                if gpn is None:
                    continue
                k = gpn.children.index(x)
                stack.append((gp, gpn.child_dist[k] + r))

    # -- deletion (Alg. 2) --------------------------------------------------

    def delete(self, idx: int) -> None:
        """Alg. 2: remove object ``idx``; a member of its list that appears
        in no other list is re-homed.

        A re-homed reference keeps its own list only where it lands at its
        old level or above, so links still point to lower levels and the
        Lemma-4 radius still covers the list.  Where it lands lower, its
        list dissolves instead, and each member left in no list is
        re-homed in turn (such members hang only below the re-homed
        references, so none is re-homed twice)."""
        self._changed()
        node = self.nodes.pop(idx)
        if idx == self.root:
            raise NotImplementedError("root deletion requires re-rooting")
        for p in node.parents:
            pn = self.nodes.get(p)
            if pn is not None:
                k = pn.children.index(idx)
                del pn.children[k], pn.child_dist[k], pn.child_level[k]
        orphans = self._unlink(idx, node.children)
        while orphans:
            c = orphans.pop(0)
            cn = self.nodes.pop(c)
            sub = [(g, cn.child_dist[k], cn.child_level[k])
                   for k, g in enumerate(cn.children) if g in self.nodes]
            self.insert(c)
            new_cn = self.nodes[c]
            if new_cn.level < cn.level:
                orphans.extend(self._unlink(c, [g for g, _, _ in sub]))
                continue
            for g, gd, gl in sub:
                new_cn.children.append(g)
                new_cn.child_dist.append(gd)
                new_cn.child_level.append(gl)
                self._grow_radius(c, gd + self.nodes[g].sub_radius)

    def _unlink(self, p: int, children: Sequence[int]) -> List[int]:
        """Drop ``p`` from the parents of ``children``; returns those left
        with no parent."""
        orphans = []
        for c in children:
            cn = self.nodes.get(c)
            if cn is None:
                continue
            cn.parents = [x for x in cn.parents if x != p]
            if not cn.parents:
                orphans.append(c)
        return orphans

    # -- range query (Alg. 3 as bound propagation) ---------------------------

    def range_query(self, q: np.ndarray, eps: float,
                    q_len: Optional[int] = None, *,
                    lb_cascade=False) -> List[int]:
        """All object idxs X with delta(q, X) <= eps (host-mode driver)."""
        return batch_engine.drive(self.range_query_plan(eps), self.counter,
                                  q, q_len, eps=eps, lb_cascade=lb_cascade)

    def range_query_plan(self, eps: float) -> batch_engine.Plan:
        """Algorithm 3 as a frontier generator (see ``core/batch_engine.py``).

        Yields batches of undecided candidates, receives their distances,
        returns the sorted hit list.  The frontier sequence — and therefore
        the exact-evaluation count — is identical to the classic host path;
        only *who* evaluates a frontier (sequential driver vs the batched
        engine merging many plans per round) changes.

        The plan walks :meth:`plan_snapshot`'s levels from the top, one
        level per step, with its state in arrays over the net's rows:
        ``dist``/``known`` (each distance counted once), the Lemma-4
        intervals ``lo``/``hi`` (object) and ``slo``/``shi`` (subtree),
        ``decided``/``inside`` (object verdicts), ``reached`` (a parent
        expanded onto the node, so it awaits its level or the final
        verdict round), and ``settled``/``accept`` (a whole-subtree
        verdict).  Settled and processed are separate flags: a parent
        that expands its list is done with, but its children are not, so
        only ``settled`` is passed down the edges.  It is passed lazily —
        down each level's edges just before the level below is visited —
        which marks every descendant of a settled node before that
        descendant could be requested or expanded, without walking the
        subtree when it is settled.  Within a level every expanding
        parent's links fold into the children's intervals at once
        (``np.maximum.at`` / ``np.minimum.at``); as the bounds are valid
        intervals of a metric, no verdict depends on the order the
        parents were visited in.
        """
        self.plans += 1
        if self.root is None:
            return []
        snap = self.plan_snapshot()
        n = snap.rows
        dist = np.zeros(n)
        known = np.zeros(n, bool)
        lo = np.zeros(n)                 # object lower bounds
        hi = np.full(n, INF)             # object upper bounds
        slo = np.zeros(n)                # subtree lower bounds
        shi = np.full(n, INF)            # subtree upper bounds
        decided = np.zeros(n, bool)      # object verdict settled
        inside = np.zeros(n, bool)       # ... and its value
        reached = np.zeros(n, bool)      # expanded onto by a parent
        settled = np.zeros(n, bool)      # whole-subtree verdict settled
        accept = np.zeros(n, bool)       # ... and its value

        def request(idxs: np.ndarray, kind: str):
            # callers pass sorted ids that are not yet known: ONE frontier
            ds = yield batch_engine.Frontier(idxs, kind)
            dist[idxs] = np.asarray(ds, np.float64)
            known[idxs] = True
            fresh = idxs[~decided[idxs]]
            inside[fresh] = dist[fresh] <= eps
            decided[fresh] = True

        def pass_down(level: int) -> None:
            # settled flags of this level's list holders onto their members
            ids, ptr, child, _ = snap.levels[level]
            pos = np.flatnonzero(settled[ids])
            if not pos.size:
                return
            e, cnt = _links(ptr, pos)
            c = child[e]
            new = ~settled[c]
            settled[c[new]] = True
            accept[c[new]] = np.repeat(accept[ids[pos]], cnt)[new]

        root = np.asarray([self.root], np.int64)
        yield from request(root, batch_engine.EXACT)
        reached[root] = True
        for level in range(self.top_level, -1, -1):
            if level < self.top_level:
                pass_down(level + 1)
            ids, ptr, child, link_r = snap.levels[level]
            pos = np.flatnonzero(reached[ids] & ~settled[ids])
            if not pos.size:
                continue
            # evaluate the expandable nodes whose level is reached; exact
            # values feed Lemma-4 bound propagation below
            par = ids[pos]
            defer = par[~known[par]]
            if defer.size:
                yield from request(defer, batch_engine.EXACT)
            d = dist[par]
            sr = snap.sub_radius[par]
            whole_in = d + sr <= eps
            whole_out = ~whole_in & (d - sr > eps)
            # a parent's own verdict is exact already; whole_in settles its
            # subtree through it, whole_out settles each member's subtree
            settled[par[whole_in]] = True
            accept[par[whole_in]] = True
            if whole_out.any():
                c = child[_links(ptr, pos[whole_out])[0]]
                settled[c] = True
            expand = ~(whole_in | whole_out)
            if not expand.any():
                continue
            e, cnt = _links(ptr, pos[expand])
            c = child[e]
            keep = ~settled[c]
            c = c[keep]
            if not c.size:
                continue
            dp = np.repeat(d[expand], cnt)[keep]
            r = link_r[e[keep]]
            lo_e = dp - r
            hi_e = dp + r
            src = snap.sub_radius[c]
            np.maximum.at(lo, c, lo_e)
            np.minimum.at(hi, c, hi_e)
            np.maximum.at(slo, c, lo_e - src)
            np.minimum.at(shi, c, hi_e + src)
            # a child listed by several parents repeats in c: every step
            # below is idempotent, so it is classified once per listing
            s_in = shi[c] <= eps
            s_out = ~s_in & (slo[c] > eps)
            settled[c[s_in | s_out]] = True
            accept[c[s_in]] = True
            c = c[~(s_in | s_out)]
            reached[c] = True            # deferred to its level, or a leaf
            c = c[~decided[c]]
            v_in = hi[c] <= eps
            v_out = ~v_in & (lo[c] > eps)
            inside[c[v_in]] = True
            decided[c[v_in | v_out]] = True
        pass_down(0)

        # final object verdicts for leaves no parent managed to decide free;
        # only the <= eps verdict is consumed, so the LB cascade may prune
        rem = np.flatnonzero(reached & ~snap.has_children & ~decided
                             & ~settled & ~known)
        if rem.size:
            yield from request(rem, batch_engine.VERDICT)
        hit = np.where(decided, inside, settled & accept)
        return np.flatnonzero(hit).tolist()

    def _subtree(self, n: int, include_self: bool = True) -> List[int]:
        out = [n] if include_self else []
        stack = list(self.nodes[n].children)
        seen = set(stack)
        while stack:
            c = stack.pop()
            out.append(c)
            cn = self.nodes.get(c)
            if cn:
                for g in cn.children:
                    if g not in seen:
                        seen.add(g)
                        stack.append(g)
        return out

    # -- invariants & stats (used by tests / benchmarks) ----------------------

    def check_invariants(self) -> None:
        levels: Dict[int, List[int]] = {}
        for n in self.nodes.values():
            levels.setdefault(n.level, []).append(n.idx)
        # exclusive
        for l, members in levels.items():
            if l < 0 or len(members) < 2:
                continue
            eps_l = self.eps(l)
            for a_i, a in enumerate(members):
                rest = members[a_i + 1:]
                if not rest:
                    continue
                ds = np.asarray(self.counter._batch(
                    np.repeat(self.data[a][None], len(rest), 0),
                    self.data[rest]))
                if np.any(ds <= eps_l):
                    bad = rest[int(np.argmax(ds <= eps_l))]
                    raise AssertionError(
                        f"exclusive violated at level {l}: {a} vs {bad}")
        # inclusive + link metadata consistency
        for n in self.nodes.values():
            if n.idx != self.root:
                assert n.parents, f"node {n.idx} has no parent"
                if self.num_max is not None:
                    assert len(n.parents) <= self.num_max
            for k, c in enumerate(n.children):
                cn = self.nodes.get(c)
                if cn is None:
                    continue
                d = float(self.counter._batch(
                    self.data[n.idx][None], self.data[c][None])[0])
                assert abs(d - n.child_dist[k]) <= 1e-3, \
                    f"stored link distance wrong for {n.idx}->{c}"
                assert d <= self.eps(n.child_level[k]) + 1e-4, \
                    f"link {n.idx}->{c} exceeds its attach-level radius"
        # subtree radii are genuine upper bounds
        for n in self.nodes.values():
            sub = self._subtree(n.idx, include_self=False)
            if not sub:
                continue
            ds = np.asarray(self.counter._batch(
                np.repeat(self.data[n.idx][None], len(sub), 0),
                self.data[sub]))
            assert np.all(ds <= n.sub_radius + 1e-3), \
                f"sub_radius understates subtree extent at {n.idx}"
            assert np.all(ds <= self.eps(n.level + 1) + 1e-3), \
                f"Lemma-4 radius violated at {n.idx}"
        # reachability
        reach = set(self._subtree(self.root))
        missing = set(self.nodes) - reach
        assert not missing, f"unreachable nodes: {sorted(missing)[:5]}"

    def stats(self) -> Dict[str, float]:
        n_list_entries = sum(len(n.children) for n in self.nodes.values())
        n_refs = sum(1 for n in self.nodes.values() if n.level >= 0)
        parents = [len(n.parents) for n in self.nodes.values()
                   if n.idx != self.root]
        return {
            "n_objects": len(self.nodes),
            "n_references": n_refs,
            "n_levels": self.top_level + 1,
            "n_list_entries": n_list_entries,
            "avg_parents": float(np.mean(parents)) if parents else 0.0,
            "max_parents": int(np.max(parents)) if parents else 0,
            # per link: child idx (8B) + distance (4B) + level (4B); per node:
            # idx/level/radius/record overhead ~24B
            "size_bytes": 16 * n_list_entries + 24 * len(self.nodes),
        }
