"""Plain references for the swarm cells: warm-up legality and the
fair-share transport, in NumPy, importing nothing of the program.

The round's inputs are the benchmark's, not the program's:
:func:`instance` draws the deployment's peers (overlay, link rates,
per-cycle budgets, access latencies, lags) from the configuration file,
and the driver hands them to the program.  Two checks then decide
whether a warm-up window was correct:

* :func:`replay_warmup` replays every directive cycle from those inputs
  and the pre-round spray the program logged, and counts grants that
  break a rule of the paper's warm-up: adjacency,
  uplink and downlink budgets, the tau cap on distinct receivers per
  sender, a sender that does not hold the chunk or is still lagged, a
  receiver that already holds it (duplicate delivery) or is past the
  warm-up threshold, and the Eq. (1) gate -- an owner chunk may leave
  its owner only inside the owner's kappa-wide rotation window and only
  once the owner's non-owner buffer reaches ``k_gate - kappa``.
* :func:`cycle_stamps` recomputes one cycle's per-transfer
  ``t_start``/``t_end`` by max-min progressive filling over the access
  links (the semantics of ``net/fairshare.py`` and ``net/engine.py``),
  in float64, or in float32 for the control.
"""
from __future__ import annotations

import numpy as np

_EPS = 1e-9
# Per-sender rotation offset of the owner window (golden-ratio hash).
_ROT = 2654435761


# ----------------------------------------------------------------------
# the deployment instance
# ----------------------------------------------------------------------

def overlay(n: int, m: int, extra_frac: float, rng) -> np.ndarray:
    """A connected overlay of minimum degree ``m`` (paper Sec. V-A): the
    union of ``ceil(m/2)`` random Hamiltonian cycles, short nodes joined
    to random others, then ``extra_frac * n * m / 2`` random extra edges
    so that degrees above ``m`` differ."""
    adj = np.zeros((n, n), bool)
    for _ in range(-(-m // 2)):
        p = rng.permutation(n)
        q = np.roll(p, 1)
        adj[p, q] = adj[q, p] = True
    np.fill_diagonal(adj, False)
    for v in np.flatnonzero(adj.sum(1) < m):
        need = m - int(adj[v].sum())
        if need > 0:
            cand = np.flatnonzero(~adj[v])
            pick = rng.choice(cand[cand != v], need, replace=False)
            adj[v, pick] = adj[pick, v] = True
    n_extra = int(extra_frac * n * m / 2)
    u, w = rng.integers(0, n, (2, 4 * n_extra))
    keep = u != w
    u, w = u[keep][:n_extra], w[keep][:n_extra]
    adj[u, w] = adj[w, u] = True
    assert (adj.sum(1) >= m).all() and (adj == adj.T).all()
    return adj


def budgets(rate_bps, chunk_bytes: float, slot_seconds: float):
    """Per-cycle chunk budgets of links (paper Sec. II-B):
    ``max(1, floor(rate * slot / chunk))``."""
    return np.maximum(1, np.floor(np.asarray(rate_bps) * slot_seconds
                                  / chunk_bytes)).astype(np.int64)


def instance(conf: dict, seed: int) -> dict:
    """The deployment's peers as the benchmark hands them to the
    program: ``adj``, ``up_bps``/``down_bps`` (bytes/s), ``up``/``down``
    (chunks per cycle), ``lat`` (s, one way) and ``lag`` (cycles).

    One instance per configuration, drawn from its ``instance_seed``;
    ``seed`` only relabels the peers, so every seed carries the same
    work in another order."""
    dep, links, net = conf["deployment"], conf["links"], conf["net"]
    n = int(dep["n"])
    rng = np.random.default_rng(int(conf["instance_seed"]))
    adj = overlay(n, int(dep["min_degree"]), float(dep["extra_edge_frac"]),
                  rng)
    up_bps = rng.uniform(links["up_lo"], links["up_hi"], n)
    down_bps = rng.uniform(links["down_lo"], links["down_hi"], n)
    lat = (rng.uniform(net["latency_lo_s"], net["latency_hi_s"], n)
           if net["latency_hi_s"] > 0 else np.zeros(n))
    lag = (rng.integers(0, dep["lag_slots"], n)
           if dep["enable_timelag"] and dep["lag_slots"] > 1
           else np.zeros(n, np.int64))
    p = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & (2 ** 64 - 1), 1])).permutation(n)
    up_bps, down_bps, lat, lag = up_bps[p], down_bps[p], lat[p], lag[p]
    cb, slot = float(dep["chunk_bytes"]), float(dep["slot_seconds"])
    return {"adj": adj[np.ix_(p, p)], "up_bps": up_bps,
            "down_bps": down_bps, "up": budgets(up_bps, cb, slot),
            "down": budgets(down_bps, cb, slot), "lat": lat,
            "lag": lag.astype(np.int64)}


def thresholds(dep: dict) -> dict:
    """The warm-up's thresholds (paper Sec. II-D, III-B): ``k_term``
    chunks held ends a peer's warm-up, ``k_gate`` non-owner chunks
    open its own, ``sigma`` chunks sprayed per source."""
    n, K = int(dep["n"]), int(dep["chunks_per_update"])
    return {"k_term": int(np.ceil(dep["warmup_threshold_pct"] * (n * K))),
            "k_gate": int(np.ceil(dep["gate_alpha"] * K)),
            "sigma": int(np.floor(dep["spray_ratio"] * K))
            if dep["enable_preround"] else 0}


# ----------------------------------------------------------------------
# warm-up legality
# ----------------------------------------------------------------------

RULES = ("adjacency", "uplink", "downlink", "tau", "sender_lacks",
         "sender_lagged", "duplicate", "receiver_closed", "eq1_gate",
         "spray")


def replay_warmup(*, n: int, K: int, kappa: int, k_gate: int,
                  k_term: int, sigma: int, tau: int, adj, up, down, lag,
                  spray, cycles) -> tuple[dict, np.ndarray]:
    """Replay one round's warm-up; count rule violations.

    ``spray`` is ``(snd, rcv, chk)`` of the pre-round obfuscation, which
    gives every source ``sigma`` of its own chunks; ``cycles`` a list of
    ``(snd, rcv, chk)``, one per directive cycle from the round's first,
    each the grants exactly as the tracker issued them.  Returns the
    count of violating grants per rule and a per-cycle bool array, true
    where the cycle broke any rule.
    """
    adj = np.asarray(adj, bool)
    up = np.asarray(up, np.int64)
    down = np.asarray(down, np.int64)
    lag = np.asarray(lag, np.int64)
    C = n * K
    have = np.zeros((n, C), bool)
    have[np.repeat(np.arange(n), K), np.arange(C)] = True
    hold = np.full(n, K, np.int64)
    nonowner = np.zeros(n, np.int64)
    bad = dict.fromkeys(RULES, 0)

    s_snd, s_rcv, s_chk = (np.asarray(a, np.int64) for a in spray)
    bad["spray"] += int(np.abs(np.bincount(s_snd, minlength=n)
                               - sigma).sum())
    if s_snd.size:
        # Spray: a source's own chunks, to non-neighbors, each
        # (target, chunk) once.
        wrong = ((s_chk // K != s_snd) | adj[s_snd, s_rcv]
                 | (s_snd == s_rcv))
        key = s_rcv * C + s_chk
        wrong |= np.r_[False, np.diff(np.sort(key)) == 0]
        bad["spray"] += int(wrong.sum())
        have[s_rcv, s_chk] = True
        np.add.at(hold, s_rcv, 1)
        np.add.at(nonowner, s_rcv, 1)
    any_nonowner = bool(s_snd.size)

    cycle_bad = np.zeros(len(cycles), bool)
    for slot, (snd, rcv, chk) in enumerate(cycles):
        snd = np.asarray(snd, np.int64)
        rcv = np.asarray(rcv, np.int64)
        chk = np.asarray(chk, np.int64)
        if snd.size == 0:
            continue
        before = dict(bad)
        bad["adjacency"] += int((~adj[snd, rcv]).sum())
        bad["sender_lacks"] += int((~have[snd, chk]).sum())
        bad["sender_lagged"] += int((lag[snd] > slot).sum())
        bad["receiver_closed"] += int((hold[rcv] >= k_term).sum())
        # Duplicate delivery: already held, or granted twice this cycle.
        key = rcv * C + chk
        order = np.argsort(key, kind="stable")
        twice = np.zeros(key.size, bool)
        twice[order[1:]] = key[order][1:] == key[order][:-1]
        bad["duplicate"] += int((have[rcv, chk] | twice).sum())
        for who, cap, rule in ((snd, up, "uplink"), (rcv, down, "downlink")):
            cnt = np.bincount(who, minlength=n)
            over = np.maximum(cnt - cap, 0)
            bad[rule] += int(over.sum())
        pairs = np.unique(snd * n + rcv)
        fan = np.bincount(pairs // n, minlength=n)
        bad["tau"] += int(np.maximum(fan - tau, 0).sum())
        # Eq. (1): owner chunks only from an open gate, inside the
        # owner's rotation window (state at the start of the cycle).
        own = chk // K == snd
        if own.any():
            u = snd[own]
            gated = (nonowner[u] + kappa < k_gate) & any_nonowner
            start = (slot * kappa + (u * _ROT) % K) % K
            in_win = ((chk[own] - u * K - start) % K) < kappa
            bad["eq1_gate"] += int((gated | ~in_win).sum())
        cycle_bad[slot] = any(bad[k] != before[k] for k in RULES)
        # Apply the cycle (first grant of a duplicate pair wins).
        fresh = ~have[rcv, chk] & ~twice
        r, c = rcv[fresh], chk[fresh]
        have[r, c] = True
        np.add.at(hold, r, 1)
        mask = c // K != r
        np.add.at(nonowner, r[mask], 1)
        any_nonowner = any_nonowner or bool(mask.any())
    return bad, cycle_bad


# ----------------------------------------------------------------------
# fair-share transport
# ----------------------------------------------------------------------

def _maxmin(src, dst, up, down, max_passes, ft):
    """Max-min fair rates by progressive filling: one bottleneck level
    per pass, then one feasible min-share fill for the rest."""
    f = src.size
    n = len(up)
    cap_up = up.copy()
    cap_down = down.copy()
    rates = np.zeros(f, ft)
    eps = ft(_EPS)
    unfrozen = (cap_up[src] > eps) & (cap_down[dst] > eps)
    fill = ft(0.0)
    slack_u = eps * np.maximum(up, ft(1.0))
    slack_d = eps * np.maximum(down, ft(1.0))
    for _ in range(max_passes):
        if not unfrozen.any():
            return rates
        nu = np.bincount(src[unfrozen], minlength=n).astype(ft)
        nd = np.bincount(dst[unfrozen], minlength=n).astype(ft)
        with np.errstate(divide="ignore", invalid="ignore"):
            tu = np.where(nu > 0, cap_up / nu, ft(np.inf))
            td = np.where(nd > 0, cap_down / nd, ft(np.inf))
        t = min(tu.min(), td.min())
        fill = ft(fill + t)
        cap_up = (cap_up - t * nu).astype(ft)
        cap_down = (cap_down - t * nd).astype(ft)
        sat_u = (nu > 0) & (cap_up <= slack_u)
        sat_d = (nd > 0) & (cap_down <= slack_d)
        freeze = unfrozen & (sat_u[src] | sat_d[dst])
        if not freeze.any():
            freeze = unfrozen
        rates[freeze] = fill
        unfrozen &= ~freeze
    if unfrozen.any():
        nu = np.bincount(src[unfrozen], minlength=n).astype(ft)
        nd = np.bincount(dst[unfrozen], minlength=n).astype(ft)
        with np.errstate(divide="ignore", invalid="ignore"):
            su = np.where(nu > 0, cap_up / nu, ft(np.inf))
            sd = np.where(nd > 0, cap_down / nd, ft(np.inf))
        share = np.minimum(su[src], sd[dst])
        rates[unfrozen] = fill + np.maximum(share[unfrozen], ft(0.0))
    return rates


def transport(src, dst, counts, chunk_bytes, up, down, quantum_frac,
              dtype=np.float64):
    """Chunk completion instants of pipelined flows under max-min fair
    share, re-solved at flow finishes batched on a quantum of
    ``quantum_frac`` of the busiest link's congestion bound.

    Returns ``(finish, chunk_flow, chunk_end)``: per-flow finish
    instants and, per delivered chunk grouped by flow in pipeline
    order, its flow and completion instant (relative to the start).
    """
    ft = np.dtype(dtype).type
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    counts = np.asarray(counts, np.int64)
    up = np.asarray(up, ft)
    down = np.asarray(down, ft)
    cb = ft(chunk_bytes)
    n = len(up)
    nbytes = counts.astype(ft) * cb
    out_b = np.bincount(src, weights=nbytes, minlength=n).astype(ft)
    in_b = np.bincount(dst, weights=nbytes, minlength=n).astype(ft)
    eps = ft(_EPS)
    with np.errstate(divide="ignore", invalid="ignore"):
        t_up = np.where(out_b > 0, out_b / np.maximum(up, eps), ft(0.0))
        t_dn = np.where(in_b > 0, in_b / np.maximum(down, eps), ft(0.0))
    quantum = ft(quantum_frac) * max(t_up.max(initial=0.0),
                                     t_dn.max(initial=0.0))

    f = src.size
    rem = nbytes.copy()
    delivered = np.zeros(f, ft)
    finish = np.full(f, np.inf, ft)
    alive = rem > 0
    finish[~alive] = 0.0
    cf_parts, ce_parts = [], []
    t = ft(0.0)
    while alive.any():
        idx = np.flatnonzero(alive)
        r = _maxmin(src[idx], dst[idx], up, down, 16, ft)
        dead = r <= eps
        if dead.any():
            alive[idx[dead]] = False
            idx, r = idx[~dead], r[~dead]
            if idx.size == 0:
                break
        ttf = rem[idx] / r
        dt = max(ft(ttf.min()), quantum)
        adv = np.minimum(r * dt, rem[idx])
        old = delivered[idx]
        new = old + adv
        k0 = np.floor(old / cb + eps).astype(np.int64)
        k1 = np.minimum(np.floor(new / cb + eps), counts[idx]).astype(np.int64)
        ncross = k1 - k0
        if ncross.sum() > 0:
            which = np.flatnonzero(ncross > 0)
            reps = ncross[which]
            base = np.repeat(k0[which], reps)
            off = np.arange(reps.sum()) - np.repeat(np.cumsum(reps) - reps,
                                                    reps)
            kk = (base + off + 1).astype(ft)
            ce_parts.append(t + (kk * cb - np.repeat(old[which], reps))
                            / np.repeat(r[which], reps))
            cf_parts.append(np.repeat(idx[which], reps))
        t = ft(t + dt)
        delivered[idx] = new
        rem[idx] -= adv
        done = rem[idx] <= eps * cb
        if done.any():
            finish[idx[done]] = t - dt + ttf[done]
            alive[idx[done]] = False
    if cf_parts:
        chunk_flow = np.concatenate(cf_parts)
        chunk_end = np.concatenate(ce_parts)
        o = np.lexsort((chunk_end, chunk_flow))
        chunk_flow, chunk_end = chunk_flow[o], chunk_end[o]
    else:
        chunk_flow = np.zeros(0, np.int64)
        chunk_end = np.zeros(0, ft)
    return finish, chunk_flow, chunk_end


def cycle_stamps(snd, rcv, *, n, chunk_bytes, up, down, lat, t0,
                 quantum_frac, dtype=np.float64):
    """Per-transfer ``(t_start, t_end)`` of one directive cycle whose
    data may start at ``t0``: transfers between one pair form one flow,
    pipelined in issue order, delayed by both ends' access latency."""
    ft = np.dtype(dtype).type
    snd = np.asarray(snd, np.int64)
    rcv = np.asarray(rcv, np.int64)
    upair, inv = np.unique(snd * n + rcv, return_inverse=True)
    counts = np.bincount(inv, minlength=upair.size).astype(np.int64)
    fs, fd = upair // n, upair % n
    finish, cflow, cend = transport(fs, fd, counts, chunk_bytes, up, down,
                                    quantum_frac, dtype)
    emitted = np.bincount(cflow, minlength=counts.size)
    if (emitted < counts).any():
        # Under-emission by rounding: a flow's missing tail chunks end
        # at the flow's finish.
        miss = counts - emitted
        padf = np.repeat(np.flatnonzero(miss > 0), miss[miss > 0])
        cflow = np.concatenate([cflow, padf])
        cend = np.concatenate([cend, finish[padf]])
        o = np.lexsort((cend, cflow))
        cflow, cend = cflow[o], cend[o]
    cstart = np.zeros_like(cend)
    same = np.zeros(cend.size, bool)
    same[1:] = cflow[1:] == cflow[:-1]
    cstart[same] = cend[:-1][same[1:]]
    lat = np.asarray(lat, ft)
    lat_pair = lat[fs] + lat[fd]
    order = np.argsort(inv, kind="stable")
    inv_s = inv[order]
    rank = np.arange(inv_s.size) - np.searchsorted(inv_s, inv_s)
    pos = (np.cumsum(counts) - counts)[inv_s] + rank
    te = np.empty(snd.size, ft)
    ts = np.empty(snd.size, ft)
    te[order] = ft(t0) + lat_pair[inv_s] + cend[pos]
    ts[order] = ft(t0) + lat_pair[inv_s] + cstart[pos]
    return ts, te
