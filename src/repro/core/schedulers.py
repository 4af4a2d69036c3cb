"""Warm-up chunk schedulers (paper §III-C) + vanilla-BT slot scheduling.

The family ships as :class:`~repro.core.policy.SchedulerPolicy` classes
registered under their paper names (``SwarmConfig.scheduler`` accepts a
name or an instance; see core/policy.py for the plugin API and
examples/custom_policy.py for a 20-line custom policy).  The policy
layer is a thin declaration of *what the mode may see*; a slot
*engine* does the work behind every policy
(``SwarmConfig.scheduler_impl``).

Implements the paper's scheduler family:

* ``random_fifo``            — §III-C.3: random feasible sender, FIFO-ish
                               (random receiver processing order).
* ``random_fastest_first``   — §III-C.4: senders prioritize the fastest
                               requesters (receivers processed by
                               remaining downlink, senders random).
* ``greedy_fastest_first``   — §III-C.5: each request assigned to the
                               fastest feasible sender (max remaining
                               uplink); the paper's default.
* ``distributed``            — §III-C.6: clients only see the
                               neighborhood-level availability union
                               C^TA(v); requests may miss.
* ``flooding``               — §III-C.7: random push without receiver
                               state; wastes bandwidth.

All centralized schedulers apply the **non-owner-first** refinement
(§III-C): a sender that is not the chunk's original source is preferred;
the source is a fallback.  During warm-up, senders only serve chunks
from their *eligible* buffer (cover-set gating + owner throttling,
state.py), so every emitted transfer honors Eq. (1).

Budgets per slot: sender u uploads <= up[u] chunks to <= tau distinct
receivers; receiver v downloads <= down[v] chunks; duplicate deliveries
of a (receiver, chunk) pair are never scheduled.

Two slot engines are provided (``SwarmConfig.scheduler_impl``):

* ``"jit"`` (default) — the engine the chip runs.  The centralized
  modes resolve each slot as budgeted grant rounds over all receivers
  at once in fixed-shape JAX kernels over packed bitplanes
  (core/jit_engine.py); the distributed mode runs the one-shot
  vectorized :func:`_schedule_distributed` below.
* ``"loop"`` — the per-receiver reference engine, kept byte-for-byte
  so equivalence tests can assert the jit engine schedules legally and
  matches its aggregate throughput.

Flooding has one implementation, shared by both engines.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .policy import (SchedulerPolicy, SlotView, VISIBILITY_FULL,
                     VISIBILITY_NEIGHBORHOOD, VISIBILITY_NONE,
                     register_policy)
from . import jit_engine
from .state import SwarmState


def _empty():
    return (np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, np.int64))


# ----------------------------------------------------------------------
# Supply-restricted candidate columns (loop-engine helpers; max-flow,
# the jit engine and the distributed fast path use the vectorized
# SwarmState API)
# ----------------------------------------------------------------------

def _candidate_columns(state: SwarmState, sactive: np.ndarray) -> np.ndarray:
    """Chunk ids that at least one sender could serve this slot."""
    cfg = state.cfg
    if state.phase == "bt" or not cfg.enable_gating:
        # Everything any active client holds is eligible; cheapest
        # over-approximation is "all chunks" (every chunk has an owner).
        return np.arange(cfg.total_chunks)
    mask = state.replicas > 1          # replicated => some non-owner holds it
    for u in np.flatnonzero(sactive):
        win = state.eligible_owner_slice(int(u))
        if win.size:
            mask[win] = True
    cand = np.flatnonzero(mask)
    cap = cfg.cand_cap
    if cap and cand.size > cap:
        # Rarity-stratified cap, mirroring SwarmState.candidate_columns:
        # the rarest cap/2 plus an even stride over the rest, so large
        # swarms keep servable supply in every neighborhood.
        half = cap // 2
        sel = np.argpartition(state.replicas[cand], half - 1)[:half]
        covered = np.zeros(cand.size, dtype=bool)
        covered[sel] = True
        rest = np.flatnonzero(~covered)
        take = cap - half
        pos = (np.arange(take, dtype=np.int64) * rest.size) // max(take, 1)
        cand = np.sort(cand[np.concatenate([sel, rest[pos]])])
    return cand


def _supply_matrix(state: SwarmState, nbr_idx: np.ndarray,
                   cand: np.ndarray, cand_owner: np.ndarray) -> np.ndarray:
    """(len(nbrs), len(cand)) bool: can neighbor j serve candidate chunk?"""
    sup = state.have[np.ix_(nbr_idx, cand)]
    if state.phase != "bt" and state.cfg.enable_gating:
        for j, u in enumerate(nbr_idx):
            own = cand_owner == u
            if not own.any():
                continue
            win = state.eligible_owner_slice(int(u))
            allowed = np.isin(cand, win, assume_unique=True)
            sup[j] &= (~own) | allowed
    return sup


# ----------------------------------------------------------------------
# Centralized scheduler family — loop (reference) engine
# ----------------------------------------------------------------------

def _schedule_centralized_loop(state: SwarmState, mode: str):
    """One stage of tracker-assigned transfers.  Returns (snd, rcv, chk)."""
    cfg = state.cfg
    rng = state.rng
    n = cfg.n

    sactive = state.senders_active()
    rem_up = np.where(sactive, state.up, 0).astype(np.int64)
    rem_down = np.where(state.active, state.down, 0).astype(np.int64)
    recv_slots = np.full(n, cfg.tau_concurrent, dtype=np.int64)
    serving = np.zeros((n, n), dtype=bool)   # sender already serving recv

    cand = _candidate_columns(state, sactive)
    if cand.size == 0:
        return _empty()
    cand_owner = state.owners[cand]
    # Rarest-first priority with random tie-break (recomputed per slot).
    prio = state.replicas[cand].astype(np.float64)
    prio += rng.random(cand.size)

    if mode == "random_fastest_first":
        # sender-side "tau fastest requesters": fast receivers get
        # first claim on the per-sender serving slots
        recv_order = np.argsort(-(rem_down + rng.random(n)))
    else:
        # request arrival order is random; GFF greediness lives in
        # the per-request fastest-SENDER assignment below
        recv_order = rng.permutation(n)

    out_s, out_r, out_c = [], [], []

    warm = state.phase != "bt"
    for v in recv_order:
        v = int(v)
        if rem_down[v] <= 0 or not state.active[v]:
            continue
        # Warm-up serves only clients still below the cover-set
        # threshold (§III-B: "until all active clients reach the k-chunk
        # threshold"); satisfied clients stop issuing warm-up requests.
        if warm and state.hold[v] >= cfg.k_term:
            continue
        nbr_mask = state.adj[v] & (rem_up > 0) & (recv_slots > 0)
        nbr_mask |= state.adj[v] & (rem_up > 0) & serving[:, v]
        nbr_idx = np.flatnonzero(nbr_mask)
        if nbr_idx.size == 0:
            continue

        sup = _supply_matrix(state, nbr_idx, cand, cand_owner)
        need_v = ~state.have[v, cand]
        sup &= need_v[None, :]
        if not sup.any():
            continue

        taken = np.zeros(cand.size, dtype=bool)
        budget = int(rem_down[v])
        # pass 0: non-owner-first; pass 1: owner fallback
        passes = (0, 1) if cfg.enable_nonowner_first else (1,)
        if mode == "greedy_fastest_first":
            # Per-REQUEST assignment (§III-C.5): every missing chunk goes
            # to the currently-fastest feasible sender; rem_up decrements
            # re-rank senders between requests, spreading load instead of
            # letting one receiver drain the fastest sender's uplink+tau.
            # Per-sender rarest-first queues with lazy deletion keep each
            # request O(log)-ish instead of rescanning all candidates.
            queues = []
            qcap = max(4 * int(rem_down[v]) + 8, 64)
            for jj in range(nbr_idx.size):
                ids = np.flatnonzero(sup[jj])
                if ids.size > qcap:   # only ever need ~rem_down picks
                    sel = np.argpartition(prio[ids], qcap - 1)[:qcap]
                    ids = ids[sel]
                queues.append(ids[np.argsort(prio[ids])])
            ptr = np.zeros(nbr_idx.size, dtype=np.int64)
            deferred: list = [[] for _ in range(nbr_idx.size)]
            for pass_id in passes:
                while budget > 0:
                    feas = (rem_up[nbr_idx] > 0) & (
                        (recv_slots[nbr_idx] > 0) | serving[nbr_idx, v])
                    if not feas.any():
                        break
                    jidx = np.flatnonzero(feas)
                    jorder = jidx[np.argsort(-(rem_up[nbr_idx[jidx]]
                                               + rng.random(jidx.size)))]
                    progressed = False
                    for jj in jorder:
                        if budget <= 0:
                            break
                        u = int(nbr_idx[jj])
                        q = queues[jj]
                        p = int(ptr[jj])
                        pick = -1
                        if pass_id != 0:     # owner chunks deferred first
                            while deferred[jj]:
                                c = deferred[jj].pop(0)
                                if not taken[c]:
                                    pick = c
                                    break
                        while pick < 0 and p < len(q):
                            c = int(q[p])
                            p += 1
                            if taken[c]:
                                continue
                            if pass_id == 0 and cand_owner[c] == u:
                                deferred[jj].append(c)  # wait for pass 1
                                continue
                            pick = c
                        ptr[jj] = p
                        if pick < 0:
                            continue
                        taken[pick] = True
                        rem_up[u] -= 1
                        budget -= 1
                        if not serving[u, v]:
                            serving[u, v] = True
                            recv_slots[u] -= 1
                        out_s.append(np.full(1, u, dtype=np.int64))
                        out_r.append(np.full(1, v, dtype=np.int64))
                        out_c.append(cand[pick:pick + 1])
                        progressed = True
                    if not progressed:
                        break
        else:
            sender_order = rng.permutation(nbr_idx.size)
            for pass_id in passes:
                if budget <= 0:
                    break
                for jj in sender_order:
                    if budget <= 0:
                        break
                    u = int(nbr_idx[jj])
                    cap = int(rem_up[u])
                    if cap <= 0:
                        continue
                    if recv_slots[u] <= 0 and not serving[u, v]:
                        continue
                    row = sup[jj] & ~taken
                    if pass_id == 0:
                        row = row & (cand_owner != u)
                    ids = np.flatnonzero(row)
                    if ids.size == 0:
                        continue
                    take_n = min(cap, budget, ids.size)
                    if take_n < ids.size:
                        sel = np.argpartition(prio[ids],
                                              take_n - 1)[:take_n]
                        ids = ids[sel]
                    taken[ids] = True
                    rem_up[u] -= len(ids)
                    budget -= len(ids)
                    if not serving[u, v]:
                        serving[u, v] = True
                        recv_slots[u] -= 1
                    out_s.append(np.full(len(ids), u, dtype=np.int64))
                    out_r.append(np.full(len(ids), v, dtype=np.int64))
                    out_c.append(cand[ids])
        rem_down[v] = budget

    if not out_s:
        return _empty()
    return (np.concatenate(out_s), np.concatenate(out_r),
            np.concatenate(out_c))


# ----------------------------------------------------------------------
# Distributed scheduling (neighborhood-level announcements, §III-C.6)
# ----------------------------------------------------------------------

def _schedule_distributed_loop(state: SwarmState):
    """Clients request random missing chunks from random neighbors.

    The tracker only publishes the neighborhood union C^TA(v, s), so a
    request may land on a neighbor that cannot serve it (wasted).
    """
    cfg = state.cfg
    rng = state.rng
    n = cfg.n
    sactive = state.senders_active()
    rem_up = np.where(sactive, state.up, 0).astype(np.int64)
    rem_down = np.where(state.active, state.down, 0).astype(np.int64)

    cand = _candidate_columns(state, sactive)
    if cand.size == 0:
        return _empty()
    cand_owner = state.owners[cand]

    warm = state.phase != "bt"
    req_s, req_r, req_c = [], [], []
    for v in range(n):
        v = int(v)
        if rem_down[v] <= 0 or not state.active[v]:
            continue
        if warm and state.hold[v] >= cfg.k_term:
            continue
        nbr_idx = np.flatnonzero(state.adj[v])
        if nbr_idx.size == 0:
            continue
        # Neighborhood-level availability: union over neighbors, no map.
        sup = _supply_matrix(state, nbr_idx, cand, cand_owner)
        union = sup.any(axis=0) & ~state.have[v, cand]
        ids = np.flatnonzero(union)
        if ids.size == 0:
            continue
        want = min(int(rem_down[v]), ids.size)
        pick = rng.choice(ids, size=want, replace=False)
        # Random neighbor per request (client cannot target the holder).
        tgt = rng.choice(nbr_idx, size=want, replace=True)
        ok = sup[np.searchsorted(nbr_idx, tgt), pick]  # request hit?
        req_s.append(tgt[ok])
        req_r.append(np.full(int(ok.sum()), v, dtype=np.int64))
        req_c.append(cand[pick[ok]])

    if not req_s:
        return _empty()
    snd = np.concatenate(req_s)
    rcv = np.concatenate(req_r)
    chk = np.concatenate(req_c)
    # Senders serve FIFO up to their uplink budget.
    order = rng.permutation(len(snd))
    snd, rcv, chk = snd[order], rcv[order], chk[order]
    keep = np.zeros(len(snd), dtype=bool)
    for i in range(len(snd)):
        u = snd[i]
        if rem_up[u] > 0:
            keep[i] = True
            rem_up[u] -= 1
    return snd[keep], rcv[keep], chk[keep]


def _schedule_distributed(state: SwarmState):
    """Vectorized distributed mode: one supply build, one request draw.

    The eligible supply is built once; the per-receiver neighborhood
    union is accumulated sender-major (each sender ORs its row into its
    neighbors), request chunks are drawn per receiver via random-score
    top-k over the union, targets are uniform random neighbors, and the
    sender-side FIFO uplink trim is resolved with a stable grouped rank
    instead of a per-request Python loop.
    """
    cfg = state.cfg
    rng = state.rng
    n = cfg.n
    sactive = state.senders_active()
    rem_up = np.where(sactive, state.up, 0).astype(np.int64)
    rem_down = np.where(state.active, state.down, 0).astype(np.int64)

    cand = state.candidate_columns(sactive)
    if cand.size == 0:
        return _empty()
    m = cand.size

    sup = state.eligible_supply(cand)          # (n, m), built once
    warm = state.phase != "bt"
    recv_ok = state.active & (rem_down > 0)
    if warm:
        recv_ok &= state.hold < cfg.k_term
    deg = state.adj.sum(axis=1)
    recv_ok &= deg > 0

    # Neighborhood availability union, sender-major accumulation.
    union = np.zeros((n, m), dtype=bool)
    for u in range(n):
        row = sup[u]
        if row.any():
            union[state.adj[u]] |= row[None, :]
    union &= ~state.have[:, cand]
    union &= recv_ok[:, None]

    ridx = np.flatnonzero(union.any(axis=1))
    if ridx.size == 0:
        return _empty()
    avail = union[ridx]
    counts = avail.sum(axis=1)
    want = np.minimum(rem_down[ridx], counts).astype(np.int64)

    # Distinct random picks per receiver: random scores, row-wise sort,
    # take the first want[i] columns of each row.
    scores = np.where(avail, rng.random((ridx.size, m)), np.inf)
    order = np.argsort(scores, axis=1)
    take_mask = np.arange(m)[None, :] < want[:, None]
    rows = np.repeat(np.arange(ridx.size), want)
    cols = order[take_mask]
    rcv = ridx[rows]
    chk = cand[cols]

    # Uniform random neighbor per request via padded neighbor lists.
    nz_r, nz_c = np.nonzero(state.adj)
    starts = np.searchsorted(nz_r, np.arange(n))
    pick = (rng.random(len(rcv)) * deg[rcv]).astype(np.int64)
    snd = nz_c[starts[rcv] + pick]
    hit = sup[snd, cols]                       # request hit the holder?
    snd, rcv, chk = snd[hit], rcv[hit], chk[hit]
    if len(snd) == 0:
        return _empty()

    # FIFO uplink trim: random arrival order, then rank within each
    # sender group (stable sort preserves arrival order).
    arrival = rng.permutation(len(snd))
    snd, rcv, chk = snd[arrival], rcv[arrival], chk[arrival]
    grp = np.argsort(snd, kind="stable")
    ss = snd[grp]
    first = np.searchsorted(ss, ss)            # start index of own group
    rank = np.arange(len(ss)) - first
    keep_sorted = rank < rem_up[ss]
    keep = np.zeros(len(snd), dtype=bool)
    keep[grp] = keep_sorted
    return snd[keep], rcv[keep], chk[keep]


# ----------------------------------------------------------------------
# Flooding (§III-C.7) — one implementation for both engines
# ----------------------------------------------------------------------

@dataclass
class FloodRoundState:
    """Typed per-round flooding memory, owned by the policy instance.

    ``sent`` maps the directed pair (sender, receiver) to the set of
    chunk ids already pushed over it; flooding never repeats a push, but
    receivers may already hold the chunk (wasted bandwidth), which is
    exactly why flooding under-performs coordinated warm-up (§III-C).
    """

    sent: dict = field(default_factory=dict)   # (u, v) -> set[int]

    def seen(self, u: int, v: int) -> set:
        return self.sent.setdefault((u, v), set())


def schedule_flooding(state: SwarmState, memory: FloodRoundState):
    """Push random eligible chunks to random neighbors, never repeating
    a push recorded in the round's pair ``memory``."""
    cfg = state.cfg
    rng = state.rng
    n = cfg.n
    sactive = state.senders_active()
    rem_down = np.where(state.active, state.down, 0).astype(np.int64)

    out_s, out_r, out_c = [], [], []
    for u in np.flatnonzero(sactive):
        u = int(u)
        budget = int(state.up[u])
        elig = np.flatnonzero(state.eligible_row(u))
        nbr_idx = np.flatnonzero(state.adj[u] & state.active)
        if elig.size == 0 or nbr_idx.size == 0:
            continue
        tgts = rng.choice(nbr_idx, size=budget, replace=True)
        picks = rng.choice(elig, size=budget, replace=True)
        for t, c in zip(tgts, picks):
            seen = memory.seen(u, int(t))
            if int(c) in seen or rem_down[t] <= 0:
                continue
            seen.add(int(c))
            rem_down[t] -= 1
            out_s.append(u)
            out_r.append(int(t))
            out_c.append(int(c))
    if not out_s:
        return _empty()
    return (np.asarray(out_s, np.int64), np.asarray(out_r, np.int64),
            np.asarray(out_c, np.int64))


# ----------------------------------------------------------------------
# Policy classes: the §III-C family on the SchedulerPolicy protocol.
# Either slot engine runs behind each policy
# (``SwarmConfig.scheduler_impl``); each engine's schedules are pinned
# byte for byte in tests/golden_schedules.json.
# ----------------------------------------------------------------------

CENTRALIZED = {"random_fifo", "random_fastest_first", "greedy_fastest_first"}


def _impl(state: SwarmState) -> str:
    impl = state.cfg.scheduler_impl
    if impl not in ("loop", "jit"):
        raise ValueError(f"unknown scheduler_impl {impl!r}; "
                         f"expected 'loop' or 'jit'")
    return impl


class CentralizedPolicy(SchedulerPolicy):
    """Tracker-assigned modes (§III-C.3-5): full supply-matrix view."""

    visibility = VISIBILITY_FULL
    mode: str = ""

    def schedule(self, view: SlotView):
        state = view._engine_state()
        if _impl(state) == "loop":
            return _schedule_centralized_loop(state, self.mode)
        # looked up on the module at call time, so a test that swaps
        # in a faulty engine (tests/chipbench_harness) reaches it here
        return jit_engine.schedule_centralized_jit(state, self.mode)


@register_policy
class RandomFIFOPolicy(CentralizedPolicy):
    """§III-C.3: random feasible sender, random receiver order."""

    name = mode = "random_fifo"


@register_policy
class RandomFastestFirstPolicy(CentralizedPolicy):
    """§III-C.4: senders prioritize the fastest requesters."""

    name = mode = "random_fastest_first"


@register_policy
class GreedyFastestFirstPolicy(CentralizedPolicy):
    """§III-C.5: each request to the fastest feasible sender (paper
    default)."""

    name = mode = "greedy_fastest_first"


@register_policy
class VanillaBTPolicy(CentralizedPolicy):
    """Vanilla BitTorrent swarming slot (§III-A step 4): ungated
    rarest-first with random feasible senders — the BT-phase backend
    behind :func:`repro.core.bittorrent.bt_exact_slot`."""

    name = "bt_vanilla"
    mode = "random_fifo"
    phases = ("bt",)


@register_policy
class DistributedPolicy(SchedulerPolicy):
    """§III-C.6: clients see only the neighborhood availability union
    C^T A(v); requests target random neighbors and may miss."""

    name = "distributed"
    visibility = VISIBILITY_NEIGHBORHOOD

    def schedule(self, view: SlotView):
        state = view._engine_state()
        # "jit" runs the vectorized host path here: the distributed
        # mode's slot is already one-shot (no budgeted round loop to
        # stage), so a separate kernel would buy nothing.
        if _impl(state) == "loop":
            return _schedule_distributed_loop(state)
        return _schedule_distributed(state)


@register_policy
class FloodingPolicy(SchedulerPolicy):
    """§III-C.7: random push without receiver state; the per-round pair
    memory is typed policy-owned state, reset every round."""

    name = "flooding"
    visibility = VISIBILITY_NONE

    def __init__(self):
        self.round_state = FloodRoundState()

    def reset(self, cfg) -> None:
        self.round_state = FloodRoundState()

    def schedule(self, view: SlotView):
        return schedule_flooding(view._engine_state(), self.round_state)
