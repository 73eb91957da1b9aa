"""Device-mode (TPU-native) retrieval: the reference net flattened into
dense arrays + a shard_map fleet query (DESIGN.md §4.2/§4.3).

Host mode chases pointers; accelerators want dense batched work.  The net
is flattened at a pivot level m: every reference with level >= m becomes a
*pivot*; every window belongs to exactly one pivot's member list (its
parent chain's level-m ancestor), carrying its exact link distance.  A
batched range query is then:

  1. one wavefront-kernel call: queries x pivots distances  (Q, P);
  2. triangle-inequality verdicts per pivot:
       d + sub_radius <= eps  -> accept all members free,
       d - sub_radius >  eps  -> prune all members free;
  3. per-member ring bound |d(q,pivot) - d(pivot,w)| > eps prunes members
     of undecided pivots elementwise (free — the link distances are dense
     arrays);
  4. survivors are *compacted* (jnp.nonzero with a static capacity) and
     evaluated in one batched kernel call.

Pruning therefore saves real compute and HBM traffic, not just a counter —
the static capacity is the TPU translation of data-dependent work.  The
fleet version shard_maps this over the data axis (stacked per-shard arrays)
with queries replicated; results are exact unions, since shards partition
the windows.

Since PR 6 this one-shot stacked fleet query is the elastic layer's
*fallback* serving mode (``ElasticIndex(..., fleet_mode="oneshot")``): it
pays exactly one device dispatch per batch, but only the flat pivot/ring
bounds prune.  The default fleet path is round-based — shard-local
frontier plans merged per round through the packed fused-ε dispatcher
(``core/batch_engine.FleetBatchEngine`` + ``kernels/dispatch.py``) — which
keeps the reference net's full pruning power (see ``launch/elastic.py``
and ``docs/architecture.md``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import _deprecation
from repro.core.refnet import ReferenceNet
from repro.distances import bounds
from repro.distances import np_backend
from repro.kernels import registry as kernel_registry


@dataclasses.dataclass
class FlatNet:
    """Flattened (pivot -> members) arrays; all padded to static shapes."""
    pivots: np.ndarray          # (P, l[, d]) pivot windows
    pivot_radius: np.ndarray    # (P,) exact derived-subtree radius
    members: np.ndarray         # (P, M) window ids, -1 padding
    member_dist: np.ndarray     # (P, M) exact delta(pivot, member)
    data: np.ndarray            # (N, l[, d]) all windows
    n_pivots: int
    dist_name: str
    pivot_ids: Optional[np.ndarray] = None   # (P,) window id of each pivot
    #: precomputed per-window envelope statistics (boxes + ERP gap masses;
    #: ``distances/bounds.py``), built in ONE stacked pass at flatten time.
    #: Fleet rounds and the device query path gather these instead of
    #: recomputing O(N*L) row reductions per query; None when the distance
    #: has no envelope bound.
    envelopes: Optional[bounds.EnvelopeSet] = None

    @property
    def eval_width(self) -> int:
        return self.members.shape[1]

    def append(self, pivot_rows: Sequence[int], member_ids: Sequence[int],
               member_dists: Sequence[float], new_data: Optional[np.ndarray]
               = None) -> "FlatNet":
        """Incrementally attach members (``member_ids[k]`` under pivot row
        ``pivot_rows[k]`` at distance ``member_dists[k]``) in place.

        ``new_data`` extends the window database when the ids are fresh
        (online inserts after flattening); member lists re-pad to the new
        width and pivot radii grow monotonically, so a refreshed net never
        needs a full re-flatten to stay queryable on device.
        """
        if new_data is not None and len(new_data):
            new_data = np.asarray(new_data)
            self.data = np.concatenate([self.data, new_data])
            if self.envelopes is not None:  # incremental envelope refresh
                self.envelopes.extend(bounds.build_envelopes(new_data))
        pivot_rows = np.asarray(pivot_rows, np.int64)
        member_ids = np.asarray(member_ids, np.int64)
        member_dists = np.asarray(member_dists, np.float32)
        counts = (self.members >= 0).sum(axis=1)
        need = counts.copy()
        for p in pivot_rows:
            need[p] += 1
        grow = int(need.max() - self.members.shape[1])
        if grow > 0:
            P = self.members.shape[0]
            self.members = np.concatenate(
                [self.members, np.full((P, grow), -1, np.int64)], axis=1)
            self.member_dist = np.concatenate(
                [self.member_dist, np.zeros((P, grow), np.float32)], axis=1)
        for p, w, d in zip(pivot_rows, member_ids, member_dists):
            k = int(counts[p])
            self.members[p, k] = w
            self.member_dist[p, k] = d
            counts[p] += 1
            if d > self.pivot_radius[p]:
                self.pivot_radius[p] = d
        return self

    def remove(self, member_ids: Sequence[int]) -> "FlatNet":
        """Mask windows out of every member list in place — zero distance
        evaluations.

        The elastic layer calls this when rendezvous resharding moves
        windows *out* of a shard: the departed ids can never be reported as
        hits again, while pivot rows stay behind as routing-only ghosts
        (a pivot is just a stored vector, so it keeps partitioning the
        survivors even after its own window left) and ``pivot_radius``
        keeps its monotone upper-bound property untouched.  ``envelopes``
        keep their rows too: a departed id never reappears as a candidate,
        so its (stale) envelope row is simply never gathered again.
        """
        ids = np.asarray(list(member_ids), np.int64)
        if ids.size == 0:
            return self
        drop = np.isin(self.members, ids) & (self.members >= 0)
        masked = np.where(drop, -1, self.members)
        # re-compact each row (live entries left, padding right): `append`
        # writes at the first slot past the live count, so holes must not
        # hide live members behind them
        order = np.argsort(masked < 0, axis=1, kind="stable")
        self.members = np.take_along_axis(masked, order, axis=1)
        self.member_dist = np.take_along_axis(self.member_dist, order, axis=1)
        return self


def flatten_net(net: ReferenceNet, pivot_level: Optional[int] = None
                ) -> FlatNet:
    """Flatten a host reference net at ``pivot_level`` (default ~sqrt(N)).

    Pivot->member distances come from the net itself where a member is a
    direct child of its pivot (the exact link distance is already stored —
    a bulk- or sequentially-built net hands those over for free); only the
    remaining pairs are evaluated, in a single stacked dispatch through the
    net's counter (``build`` bucket, so the flatten cost is measured on
    whichever backend the counter runs).
    """
    N = len(net.data)
    levels = sorted({n.level for n in net.nodes.values() if n.level >= 0})
    if pivot_level is None:
        # lowest level whose reference count is <= sqrt-ish of N
        target = max(1, int(math.sqrt(N)))
        pivot_level = levels[-1]
        for l in levels:
            cnt = sum(1 for n in net.nodes.values() if n.level >= l)
            if cnt <= 4 * target:
                pivot_level = l
                break
    pivot_ids = [n.idx for n in net.nodes.values() if n.level >= pivot_level]
    pivot_of = {}

    def assign(pid):
        for x in net._subtree(pid, include_self=True):
            node = net.nodes.get(x)
            if x not in pivot_of and (node is None or
                                      node.level < pivot_level or x == pid):
                pivot_of[x] = pid

    for pid in pivot_ids:
        assign(pid)
    members: List[List[int]] = [[] for _ in pivot_ids]
    pidx = {p: i for i, p in enumerate(pivot_ids)}
    for x, p in pivot_of.items():
        members[pidx[p]].append(x)
    M = max(len(m) for m in members)
    P = len(pivot_ids)
    mem = np.full((P, M), -1, np.int64)
    mdist = np.zeros((P, M), np.float32)
    # reuse stored link distances for direct children; stack the rest into
    # one batched dispatch (no per-pivot host loop)
    eval_l: List[int] = []
    eval_r: List[int] = []
    eval_at: List[Tuple[int, int]] = []
    for i, (pid, ms) in enumerate(zip(pivot_ids, members)):
        mem[i, :len(ms)] = ms
        pn = net.nodes[pid]
        link = {c: pn.child_dist[k] for k, c in enumerate(pn.children)}
        for j, x in enumerate(ms):
            if x == pid:
                mdist[i, j] = 0.0
            elif x in link:
                mdist[i, j] = link[x]
            else:
                eval_l.append(pid)
                eval_r.append(x)
                eval_at.append((i, j))
    if eval_l:
        ds = net.counter.eval_pairs(eval_l, eval_r)
        for (i, j), d in zip(eval_at, ds):
            mdist[i, j] = float(d)
    valid = mem >= 0
    radius = np.where(valid.any(axis=1),
                      np.where(valid, mdist, 0.0).max(axis=1),
                      0.0).astype(np.float32)
    # one stacked envelope pass over the whole window database (reused by
    # fleet rounds and the device query path instead of per-query rebuilds)
    envs = bounds.build_envelopes(net.data) \
        if net.dist.envelope_bound is not None else None
    return FlatNet(
        pivots=np.asarray(net.data[pivot_ids]),
        pivot_radius=radius,
        members=mem, member_dist=mdist,
        data=np.asarray(net.data), n_pivots=P, dist_name=net.dist.name,
        pivot_ids=np.asarray(pivot_ids, np.int64),
        envelopes=envs)


def _batch_dist(dist_name: str, qs, xs, interpret=None):
    """Deprecated since v0.1, removed in v0.2: batched distance lives in
    the kernel registry — call
    ``repro.kernels.registry.get(name).device_call(qs, xs)`` (or, from the
    facade, serve through ``repro.retrieval.Retriever``, which never needs
    a raw batched distance).  The device query path composes
    :meth:`KernelSpec.device_call` directly; this wrapper keeps external
    callers working for one release (the warning is suppressed inside
    facade-internal construction, mirroring the legacy-constructor
    shims)."""
    _deprecation.warn_moved("core.distributed._batch_dist",
                            "repro.kernels.registry.get(name).device_call")
    return kernel_registry.get(dist_name).device_call(
        qs, xs, interpret=interpret).dist


def device_range_query(flat: FlatNet, qs: np.ndarray, eps: float, *,
                       capacity: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       q_lens: Optional[np.ndarray] = None,
                       lb_cascade="off") -> Tuple[np.ndarray, dict]:
    """Batched exact range query on one shard.

    Returns (hits (Q, N) bool, stats).  ``capacity`` is the static budget of
    survivor evaluations; on overflow the query is retried with 2x budget
    (each retry is one recompile — production sets it from telemetry).
    ``q_lens`` gives per-query actual lengths (ragged batches padded to a
    common width — the fleet layer packs every length bucket into one call).

    ``lb_cascade="envelope"`` adds an envelope-bound stage between the ring
    compaction and the exact kernel call, gathering the PRECOMPUTED
    per-window envelopes stored on the FlatNet (``flat.envelopes``): rows
    whose bound already certifies ``> eps`` are compacted away before the
    wavefront runs, and ``member_evals`` counts only the rows that reached
    it (``lb_rows`` / ``lb_pruned`` report the stage itself).  Off by
    default — counts are then bit-identical to the pre-cascade path.
    """
    Q = qs.shape[0]
    N = len(flat.data)
    # resolved outside the jit: a static None would pin a stale policy
    interpret = kernel_registry.resolve_interpret(interpret)
    if capacity is None:
        capacity = max(64, N // 4) * Q
    if q_lens is None:
        q_lens = np.full(Q, qs.shape[1], np.int32)
    mem_valid = flat.members >= 0                     # (P, M)
    mem_safe = np.maximum(flat.members, 0)
    tier = bounds.normalize_tier(lb_cascade)
    use_env = tier == "envelope" and flat.envelopes is not None
    if use_env:
        env_lo = jnp.asarray(flat.envelopes.lo)
        env_hi = jnp.asarray(flat.envelopes.hi)
        env_mass = jnp.asarray(flat.envelopes.mass)
    else:  # dummies keep operand shapes rank-stable under the static flag
        d = flat.data.shape[2] if flat.data.ndim == 3 else 1
        env_lo = jnp.zeros((1, d), jnp.float32)
        env_hi = jnp.zeros((1, d), jnp.float32)
        env_mass = jnp.zeros((1,), jnp.float32)

    def run(cap: int):
        return _device_query_jit(
            jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32),
            jnp.asarray(flat.pivots),
            jnp.asarray(flat.pivot_radius), jnp.asarray(mem_safe),
            jnp.asarray(mem_valid), jnp.asarray(flat.member_dist),
            jnp.asarray(flat.data), env_lo, env_hi, env_mass,
            float(eps), cap, flat.dist_name, interpret, use_env)

    cap = int(capacity)
    while True:
        # lint: allow[trace-static-rebound] -- capacity-doubling retry: the rare overflow path recompiles by design (one trace per power of two)
        hits, n_need, n_evals, n_pruned, lb_rows, lb_pruned = run(cap)
        if int(n_need) <= cap:
            break
        cap *= 2
    stats = {"pivot_evals": Q * flat.n_pivots,
             "member_evals": int(n_evals),
             "fused_pruned": int(n_pruned),
             "lb_rows": int(lb_rows),
             "lb_pruned": int(lb_pruned),
             "capacity": cap,
             "total_evals": Q * flat.n_pivots + int(n_evals)}
    return np.asarray(hits), stats


from functools import partial


@partial(jax.jit, static_argnums=(11, 12, 13, 14, 15))
def _device_query_jit(qs, q_lens, pivots, pradius, members, mem_valid,
                      mem_dist, data, env_lo, env_hi, env_mass,
                      eps, capacity, dist_name, interpret, use_env):
    Q = qs.shape[0]
    P, M = members.shape
    N = data.shape[0]
    spec = kernel_registry.get(dist_name)
    # 1. queries x pivots — value-consuming (feeds the ring bounds)
    qs_rep = jnp.repeat(qs, P, axis=0)
    pv_rep = jnp.tile(pivots, (Q,) + (1,) * (pivots.ndim - 1))
    dp = spec.device_call(qs_rep, pv_rep, lx=jnp.repeat(q_lens, P),
                          interpret=interpret).dist.reshape(Q, P)
    # 2. pivot verdicts
    acc_all = dp + pradius[None, :] <= eps            # accept whole list
    prune_all = dp - pradius[None, :] > eps
    undecided = ~(acc_all | prune_all)
    # 3. member ring bounds for undecided pivots
    lo = jnp.abs(dp[:, :, None] - mem_dist[None, :, :])   # (Q, P, M)
    hi = dp[:, :, None] + mem_dist[None, :, :]
    member_live = mem_valid[None, :, :] & undecided[:, :, None]
    accept_m = member_live & (hi <= eps)
    need_eval = member_live & (lo <= eps) & (hi > eps)
    # scatter free verdicts into the (Q, N) hit mask
    hits = jnp.zeros((Q, N), bool)
    qq = jnp.broadcast_to(jnp.arange(Q)[:, None, None], (Q, P, M)).reshape(-1)
    ww = jnp.broadcast_to(members[None], (Q, P, M)).reshape(-1)
    free_in = ((acc_all[:, :, None] & mem_valid[None]) | accept_m).reshape(-1)
    hits = hits.at[qq, ww].max(free_in)
    # 4. compact survivors and evaluate — fused ε: the kernel returns the
    # hit mask directly and never materializes distances of pruned rows
    flat_need = need_eval.reshape(-1)
    n_need = jnp.sum(flat_need)
    sel = jnp.nonzero(flat_need, size=capacity, fill_value=0)[0]
    # jnp.nonzero pads with index 0; when flat_need[0] is genuinely true the
    # padding aliases a real survivor, so validity must be positional (the
    # first n_need rows are real), never looked up by value
    valid_sel = jnp.arange(capacity) < n_need
    q_of = sel // (P * M)
    pm = sel % (P * M)
    w_of = members.reshape(-1)[pm]
    lb_rows = jnp.zeros((), jnp.int32)
    lb_pruned = jnp.zeros((), jnp.int32)
    if use_env:
        # 4b. envelope stage on the compacted survivors: gather the
        # PRECOMPUTED per-window boxes/masses (built once at flatten time)
        # and compact a second time, so only rows the envelope bound cannot
        # certify as > eps reach the exact wavefront.  One-direction form of
        # the sound bounds in ``distances/bounds.py::lb_envelope_rows``.
        xq = qs[q_of]
        if xq.ndim == 2:
            xq = xq[..., None]
        Lq = xq.shape[1]
        mx = jnp.arange(Lq)[None, :] < q_lens[q_of][:, None]    # (C, L)
        lo_r = env_lo[w_of][:, None, :]                         # (C, 1, d)
        hi_r = env_hi[w_of][:, None, :]
        gap = jnp.maximum(lo_r - xq, 0.0) + jnp.maximum(xq - hi_r, 0.0)
        bd = jnp.sqrt(jnp.maximum(jnp.sum(gap * gap, -1), 0.0))  # (C, L)
        if dist_name == "frechet":
            lb = jnp.max(jnp.where(mx, bd, 0.0), axis=1)
        elif dist_name == "dtw":
            lb = jnp.sum(jnp.where(mx, bd, 0.0), axis=1)
        else:  # erp: element consumption + global gap-mass bound
            gx = jnp.where(mx, jnp.sqrt(
                jnp.maximum(jnp.sum(xq * xq, -1), 0.0)), 0.0)
            cons = jnp.sum(jnp.where(mx, jnp.minimum(gx, bd), 0.0), axis=1)
            gm = jnp.abs(gx.sum(axis=1) - env_mass[w_of])
            lb = jnp.maximum(cons, gm)
        keep = valid_sel & (lb <= eps)
        lb_rows = jnp.sum(valid_sel)
        lb_pruned = jnp.sum(valid_sel & ~keep)
        n_keep = jnp.sum(keep)
        sel2 = jnp.nonzero(keep, size=capacity, fill_value=0)[0]
        valid_sel = jnp.arange(capacity) < n_keep
        q_of, w_of = q_of[sel2], w_of[sel2]
    out = spec.device_call(qs[q_of], data[w_of], lx=q_lens[q_of], eps=eps,
                           interpret=interpret)
    good = valid_sel & out.hit
    hits = hits.at[q_of, w_of].max(good)
    return (hits, n_need, jnp.sum(valid_sel),
            jnp.sum(valid_sel & out.pruned), lb_rows, lb_pruned)


def host_reference_hits(flat: FlatNet, qs: np.ndarray, eps: float
                        ) -> np.ndarray:
    """Oracle: exact (Q, N) hit mask by brute force (numpy backend)."""
    batch = np_backend.batch_for(flat.dist_name)
    Q, N = qs.shape[0], len(flat.data)
    # ONE stacked oracle call over the full (Q, N) cross product
    ds = np.asarray(batch(
        np.repeat(qs, N, axis=0),
        np.tile(flat.data, (Q,) + (1,) * (flat.data.ndim - 1))))
    return ds.reshape(Q, N) <= eps


# -- fleet (multi-shard) version ---------------------------------------------

def merge_flats(flats: Sequence[FlatNet]) -> Tuple[FlatNet, List[int]]:
    """Stack per-shard FlatNets into ONE flat net over the union.

    Shards partition the windows, so concatenating pivot rows (member ids
    offset into the concatenated data array, member widths padded to the
    fleet maximum) yields a FlatNet whose single device query equals the
    union of the per-shard queries.  Pivot identities survive the merge —
    ``pivot_ids`` concatenate with the same per-shard offsets, so post-merge
    :meth:`FlatNet.append` refreshes keep working — when every input carries
    them (otherwise the merged net's are None).  Returns the merged net plus
    each shard's column offset into the merged hit mask.
    """
    assert flats, "nothing to merge"
    assert len({f.dist_name for f in flats}) == 1, "mixed distances"
    M = max(f.members.shape[1] for f in flats)
    offsets: List[int] = []
    mems, mdists, off = [], [], 0
    for f in flats:
        offsets.append(off)
        pad = M - f.members.shape[1]
        mem = np.pad(f.members, ((0, 0), (0, pad)), constant_values=-1)
        mems.append(np.where(mem >= 0, mem + off, -1))
        mdists.append(np.pad(f.member_dist, ((0, 0), (0, pad))))
        off += len(f.data)
    pivot_ids = None
    if all(f.pivot_ids is not None for f in flats):
        pivot_ids = np.concatenate(
            [np.asarray(f.pivot_ids, np.int64) + o
             for f, o in zip(flats, offsets)])
    envs = None
    if all(f.envelopes is not None for f in flats):
        e0 = flats[0].envelopes
        envs = bounds.EnvelopeSet(e0.lo.copy(), e0.hi.copy(),
                                  e0.mass.copy(), e0.cum.copy(),
                                  e0.lens.copy())
        for f in flats[1:]:
            envs.extend(f.envelopes)
    return FlatNet(
        pivots=np.concatenate([f.pivots for f in flats]),
        pivot_radius=np.concatenate([f.pivot_radius for f in flats]),
        members=np.concatenate(mems),
        member_dist=np.concatenate(mdists),
        data=np.concatenate([f.data for f in flats]),
        n_pivots=sum(f.n_pivots for f in flats),
        dist_name=flats[0].dist_name, pivot_ids=pivot_ids,
        envelopes=envs), offsets


def fleet_range_query(flats: List[FlatNet], qs: np.ndarray, eps: float,
                      *, dead: Tuple[int, ...] = (), stacked: bool = True,
                      merged: Optional[Tuple[FlatNet, List[int]]] = None,
                      **kw):
    """Union of per-shard device queries (shards partition the windows).

    This is the fleet's *one-shot* serving primitive — since PR 6 the
    elastic layer's fallback mode (``mode="oneshot"``); default serving
    goes round-based through ``FleetBatchEngine`` instead, which prunes
    with the full reference-net frontier (see ``launch/elastic.py``).

    ``dead`` shards are skipped (the elastic layer rebuilds them); the
    returned mask is per-shard so the caller can re-issue stolen work.

    ``stacked`` (default) merges the alive shards' FlatNet arrays with
    :func:`merge_flats` and runs ONE device query over the stack — one
    pivot-kernel call and one survivor compaction for the whole fleet
    instead of a sequential host-Python loop over shards.  Results are
    identical; per-shard masks are column slices of the merged mask.  A
    merged run cannot attribute evaluations to individual shards, so each
    alive shard's stats entry is an independent dict tagged
    ``merged=True`` whose counters use ``fleet_*`` keys (summing them
    across shards would double-count — old per-shard keys are absent on
    purpose).  ``stacked=False`` keeps the per-shard loop with the
    classic per-shard stats (useful when shards genuinely live on
    different processes).

    ``merged`` lets a serving layer pass a precomputed
    ``merge_flats``-of-the-alive-shards result (net, offsets) so repeated
    queries against an unchanged fleet skip the per-call merge; it MUST
    correspond to the current alive list or the column slicing is wrong.
    """
    alive = [(i, f) for i, f in enumerate(flats) if i not in dead]
    results: List[Optional[np.ndarray]] = [None] * len(flats)
    stats: List[Optional[dict]] = [None] * len(flats)
    if stacked and len(alive) > 1:
        if merged is not None:
            mnet, offsets = merged
        else:
            mnet, offsets = merge_flats([f for _, f in alive])
        hits, s = device_range_query(mnet, qs, eps, **kw)
        fleet = {"merged": True, "n_shards": len(alive),
                 "capacity": s["capacity"],
                 "fleet_pivot_evals": s["pivot_evals"],
                 "fleet_member_evals": s["member_evals"],
                 "fleet_fused_pruned": s.get("fused_pruned", 0),
                 "fleet_lb_rows": s.get("lb_rows", 0),
                 "fleet_lb_pruned": s.get("lb_pruned", 0),
                 "fleet_total_evals": s["total_evals"]}
        for (i, f), off in zip(alive, offsets):
            results[i] = hits[:, off:off + len(f.data)]
            stats[i] = dict(fleet)
        return results, stats
    for i, f in alive:
        h, st = device_range_query(f, qs, eps, **kw)
        results[i] = h
        stats[i] = st
    return results, stats
