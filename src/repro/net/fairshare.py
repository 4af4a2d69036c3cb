"""Max-min fair-share flow allocation over access links (repro.net).

The event engine models every scheduled (sender, receiver) pair of a
stage as one *flow* over two shared resources — the sender's uplink and
the receiver's downlink, both in raw bytes/s — and allocates rates by
**progressive filling**: all flows ramp together, a link saturates when
its remaining capacity divided by its unfrozen-flow count is reached,
flows crossing a saturated link freeze at the current fill level, and
the rest keep ramping.  The fixed point is the classic max-min fair
allocation (no flow's rate can grow without shrinking a smaller one).

Both solvers run as fixed-shape jitted JAX kernels in float64 (under a
scoped ``jax.enable_x64``; the TPU emulates it — a float32 twin moved
segment boundaries and the solve count): :func:`maxmin_rates` is one
``lax.while_loop``
over freeze masks (one bottleneck level per pass, the same truncated
feasible tail fill past ``max_passes``), and :func:`transport` stages
the whole segment loop — nested water-fill solve, flow-death masking,
quantum-batched finish events and per-chunk crossing emission over a
padded chunk grid — in a single ``lax.while_loop`` whose carry replaces
the host's ``while alive.any()``.  A water-fill pass counts each
link's unfrozen flows and finds the flows on saturated links through a
flow-link incidence built once per call (:func:`_incidence`), not by
scattering into the links: the TPU serializes a scatter's updates.
Flow and chunk extents pad to powers of two so re-solves across cycles
hit a handful of compiled shapes; pad flows are dead on entry and pad
chunk rows can never satisfy a crossing predicate, so padding never
changes an allocation.  The NumPy host
implementations (``_maxmin_host``/``_transport_host``) are the kernels'
test oracles and are never called by the program; the kernels mirror
their IEEE operation order, so the two agree to rounding
(tests/test_net.py).

Chunk-level completion instants come from the piecewise-linear
delivered-bytes curve of each flow: chunks are pipelined back-to-back
over the flow (BitTorrent keeps a connection's pipe full), so chunk
``j`` completes when ``j * chunk_bytes`` cumulative bytes have arrived.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from repro import obs

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 1e-9


def _pow2(x) -> int:
    """Smallest power of two >= x (>= 1): static kernel extents."""
    x = int(x)
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


# ---------------------------------------------------------------------------
# max-min progressive filling
# ---------------------------------------------------------------------------

def maxmin_rates(src: np.ndarray, dst: np.ndarray,
                 up: np.ndarray, down: np.ndarray,
                 max_passes: int = 16) -> np.ndarray:
    """Max-min fair rates (bytes/s) for flows ``src[f] -> dst[f]``.

    ``up``/``down`` are per-peer access-link capacities in bytes/s.
    Flows whose uplink or downlink has no capacity get rate 0.

    Progressive filling freezes one bottleneck *level* per pass; with
    heterogeneous links a stage can have O(#links) distinct levels, so
    after ``max_passes`` exact levels the remaining (least-constrained)
    flows are finished with one feasible min-share fill — each takes
    ``fill + min(residual_up / n_up, residual_down / n_down)``, which
    never oversubscribes a link and coincides with the exact fixpoint
    whenever one pass would have finished anyway.  Small stages and the
    homogeneous limit are always exact.
    """
    if len(src) == 0:
        return np.zeros(0, np.float64)
    s = np.asarray(src, np.int64)
    d = np.asarray(dst, np.int64)
    u = np.asarray(up, np.float64)
    w = np.asarray(down, np.float64)
    rec = obs.get()
    if rec.enabled:
        rec.counter("fairshare.maxmin_calls")
    return _maxmin_jax(s, d, u, w, max_passes)


def _maxmin_host(src, dst, up, down, max_passes):
    """Host progressive filling: the kernels' test oracle.  Returns the
    rates and the water-fill passes run."""
    f = src.size
    n = len(up)
    cap_up = up.copy()
    cap_down = down.copy()
    rates = np.zeros(f, np.float64)
    unfrozen = (cap_up[src] > _EPS) & (cap_down[dst] > _EPS)
    fill = 0.0
    slack_u = _EPS * np.maximum(up, 1.0)
    slack_d = _EPS * np.maximum(down, 1.0)
    # Each pass saturates >= 1 link, so <= 2n passes; the tail fill
    # bounds the worst case.
    passes = 0
    for _ in range(max_passes):
        if not unfrozen.any():
            return rates, passes
        passes += 1
        nu = np.bincount(src[unfrozen], minlength=n).astype(np.float64)
        nd = np.bincount(dst[unfrozen], minlength=n).astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            tu = np.where(nu > 0, cap_up / nu, np.inf)
            td = np.where(nd > 0, cap_down / nd, np.inf)
        t = min(tu.min(), td.min())
        fill += t
        cap_up -= t * nu
        cap_down -= t * nd
        # Freeze flows through any just-saturated link (relative slack
        # so heterogeneous-magnitude links compare fairly).
        sat_u = (nu > 0) & (cap_up <= slack_u)
        sat_d = (nd > 0) & (cap_down <= slack_d)
        freeze = unfrozen & (sat_u[src] | sat_d[dst])
        if not freeze.any():        # numerical stall: freeze everything
            freeze = unfrozen
        rates[freeze] = fill
        unfrozen &= ~freeze
    if unfrozen.any():
        # Truncated tail: one feasible min-share fill for the rest.
        nu = np.bincount(src[unfrozen], minlength=n).astype(np.float64)
        nd = np.bincount(dst[unfrozen], minlength=n).astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            su = np.where(nu > 0, cap_up / nu, np.inf)
            sd = np.where(nd > 0, cap_down / nd, np.inf)
        share = np.minimum(su[src], sd[dst])
        rates[unfrozen] = fill + np.maximum(share[unfrozen], 0.0)
    return rates, passes


def _incidence(src, dst, n: int):
    """Flow-link incidence of a kernel call: the (f_pad, n) one-hots of
    each flow's uplink and downlink.  ``src``/``dst`` are fixed for the
    whole call, so it is built once, outside the loops, and every
    water-fill pass reads it in place of scattering into the links (or
    XLA fuses the compare into the pass's reductions).  Peer ids fit
    int32, whose compare the TPU does natively; int64 it emulates."""
    peers = jnp.arange(n, dtype=jnp.int32)
    return (src.astype(jnp.int32)[:, None] == peers,
            dst.astype(jnp.int32)[:, None] == peers)


def _link_counts(inc, unfrozen):
    """Unfrozen flows on each uplink and downlink, as exact int32 counts
    (the ``np.bincount`` of the host twin)."""
    inc_s, inc_d = inc
    m = unfrozen[:, None]
    return (jnp.sum(inc_s & m, axis=0, dtype=jnp.int32),
            jnp.sum(inc_d & m, axis=0, dtype=jnp.int32))


def _crosses(inc, on_up, on_down):
    """Per flow: ``on_up[src] | on_down[dst]``, through the incidence."""
    inc_s, inc_d = inc
    return (jnp.any(inc_s & on_up, axis=1)
            | jnp.any(inc_d & on_down, axis=1))


def _maxmin_fill(src, dst, inc, up, down, unfrozen0, max_passes: int):
    """Traced progressive filling over a fixed flow extent.

    The staged twin of :func:`_maxmin_host`: one ``lax.while_loop``
    iteration per bottleneck level, freeze masks in place of boolean
    indexing, the same stall guard and truncated feasible tail.  Also
    inlined per segment by the transport kernel.  ``inc`` is the call's
    :func:`_incidence`; link counts are exact integers, so they convert
    to the host's float64 counts bit for bit.  Returns the rates and
    the passes run.
    """
    f_pad = src.shape[0]
    slack_u = _EPS * jnp.maximum(up, 1.0)
    slack_d = _EPS * jnp.maximum(down, 1.0)

    def counts(unfrozen):
        nu, nd = _link_counts(inc, unfrozen)
        return nu.astype(jnp.float64), nd.astype(jnp.float64)

    def cond(c):
        i, unfrozen = c[0], c[1]
        return (i < max_passes) & jnp.any(unfrozen)

    def body(c):
        i, unfrozen, cap_up, cap_down, rates, fill = c
        nu, nd = counts(unfrozen)
        tu = jnp.where(nu > 0, cap_up / nu, jnp.inf)
        td = jnp.where(nd > 0, cap_down / nd, jnp.inf)
        t = jnp.minimum(jnp.min(tu), jnp.min(td))
        fill = fill + t
        cap_up = cap_up - t * nu
        cap_down = cap_down - t * nd
        sat_u = (nu > 0) & (cap_up <= slack_u)
        sat_d = (nd > 0) & (cap_down <= slack_d)
        freeze = unfrozen & _crosses(inc, sat_u, sat_d)
        freeze = jnp.where(jnp.any(freeze), freeze, unfrozen)
        rates = jnp.where(freeze, fill, rates)
        return (i + 1, unfrozen & ~freeze, cap_up, cap_down, rates, fill)

    init = (jnp.int32(0), unfrozen0, up, down,
            jnp.zeros(f_pad, jnp.float64), jnp.float64(0.0))
    passes, unfrozen, cap_up, cap_down, rates, fill = lax.while_loop(
        cond, body, init)
    nu, nd = counts(unfrozen)
    su = jnp.where(nu > 0, cap_up / nu, jnp.inf)
    sd = jnp.where(nd > 0, cap_down / nd, jnp.inf)
    share = jnp.minimum(su[src], sd[dst])
    return (jnp.where(unfrozen, fill + jnp.maximum(share, 0.0), rates),
            passes)


@functools.lru_cache(maxsize=None)
def _maxmin_compiled(n: int, f_pad: int, max_passes: int):
    def fairshare_maxmin(src, dst, up, down, valid):
        unfrozen0 = valid & (up[src] > _EPS) & (down[dst] > _EPS)
        return _maxmin_fill(src, dst, _incidence(src, dst, n), up, down,
                            unfrozen0, max_passes)[0]
    return jax.jit(fairshare_maxmin)


def _maxmin_jax(src, dst, up, down, max_passes):
    f = len(src)
    f_pad = _pow2(f)
    sp = np.zeros(f_pad, np.int64)
    dp = np.zeros(f_pad, np.int64)
    sp[:f] = src
    dp[:f] = dst
    valid = np.zeros(f_pad, bool)
    valid[:f] = True
    with jax.enable_x64(True):
        r = _maxmin_compiled(len(up), f_pad, int(max_passes))(
            sp, dp, up, down, valid)
        return np.asarray(r)[:f]


# ---------------------------------------------------------------------------
# chunked transport
# ---------------------------------------------------------------------------

@dataclass
class FlowTimings:
    """Result of :func:`transport`.

    ``finish``      (F,) completion instant of each flow (relative to
                    the stage start, seconds).
    ``chunk_flow``  (M,) flow index of each delivered chunk, grouped by
                    flow in pipeline order (chunk rank ascending).
    ``chunk_end``   (M,) completion instant of each chunk.
    ``makespan``    instant the last flow finished.
    ``n_solves``    water-filling re-solves performed (diagnostics).
    ``n_passes``    water-fill passes over all solves (diagnostics).
    """

    finish: np.ndarray
    chunk_flow: np.ndarray
    chunk_end: np.ndarray
    makespan: float
    n_solves: int
    n_passes: int

    def chunk_starts(self) -> np.ndarray:
        """Pipelined start instant of each chunk (see
        :func:`pipeline_starts`)."""
        return pipeline_starts(self.chunk_flow, self.chunk_end)


def pipeline_starts(chunk_flow: np.ndarray,
                    chunk_end: np.ndarray) -> np.ndarray:
    """Pipelined start instant of each chunk: the previous chunk's
    completion within the same flow (0.0 for each flow's first).
    ``chunk_flow`` must be grouped by flow with ``chunk_end``
    non-decreasing within each group."""
    starts = np.zeros_like(chunk_end)
    if len(starts) == 0:
        return starts
    same = np.zeros(len(starts), dtype=bool)
    same[1:] = chunk_flow[1:] == chunk_flow[:-1]
    starts[same] = chunk_end[:-1][same[1:]]
    return starts


def congestion_bound(src: np.ndarray, dst: np.ndarray,
                     nbytes: np.ndarray, up: np.ndarray,
                     down: np.ndarray) -> float:
    """Congestion lower bound (seconds) on moving ``nbytes[f]`` bytes
    over flows ``src[f] -> dst[f]``: no transport discipline can beat
    the busiest access link.  The canonical implementation — both the
    transport quantum sizing below and the time-domain efficiency
    denominator (:func:`repro.core.maxflow.stage_time_lower_bound`)
    use it, so the two can never desynchronize."""
    n = len(up)
    out_b = np.bincount(src, weights=nbytes, minlength=n)
    in_b = np.bincount(dst, weights=nbytes, minlength=n)
    with np.errstate(divide="ignore", invalid="ignore"):
        t_up = np.where(out_b > 0, out_b / np.maximum(
            np.asarray(up, np.float64), _EPS), 0.0)
        t_dn = np.where(in_b > 0, in_b / np.maximum(
            np.asarray(down, np.float64), _EPS), 0.0)
    return float(max(t_up.max(initial=0.0), t_dn.max(initial=0.0)))


def transport(src: np.ndarray, dst: np.ndarray, counts: np.ndarray,
              chunk_bytes: float, up: np.ndarray, down: np.ndarray,
              *, quantum_frac: float = 1 / 64) -> FlowTimings:
    """Simulate max-min fair-share transport of chunked flows.

    Flow ``f`` carries ``counts[f]`` pipelined chunks of ``chunk_bytes``
    bytes from ``src[f]`` to ``dst[f]``.  Rates are re-solved at flow
    finish events, batched on a time quantum of ``quantum_frac`` of the
    congestion lower bound so the number of solves stays bounded: a
    flow finishing mid-segment still records its *exact* finish instant
    under its current rate; only the redistribution of its freed
    capacity waits for the segment boundary.  ``quantum_frac=0`` gives
    the exact per-event progressive-filling process.
    """
    if len(src) == 0:
        return FlowTimings(np.zeros(0), np.zeros(0, np.int64),
                           np.zeros(0), 0.0, 0, 0)
    rec = obs.get()
    with rec.span("transport.prep"):
        s = np.asarray(src, np.int64)
        d = np.asarray(dst, np.int64)
        c = np.asarray(counts, np.int64)
        u = np.asarray(up, np.float64)
        w = np.asarray(down, np.float64)
        nbytes = c.astype(np.float64) * float(chunk_bytes)
        # Congestion lower bound on the makespan: the busiest access
        # link.
        quantum = quantum_frac * congestion_bound(s, d, nbytes, u, w)
        grids = _transport_grids(s, d, c, nbytes)
    with rec.span("transport.kernel"):
        tm = _transport_jax(grids, float(chunk_bytes), u, w, quantum)
    if rec.enabled:
        rec.counter("fairshare.transport_calls")
        rec.counter("fairshare.solves", tm.n_solves)
        rec.counter("fairshare.passes", tm.n_passes)
    return tm


def _transport_host(src, dst, counts, nbytes, chunk_bytes, up, down,
                    quantum):
    """Host segment loop: the transport kernel's test oracle."""
    f = src.size
    rem = nbytes.copy()
    delivered = np.zeros(f, np.float64)
    finish = np.full(f, np.inf, np.float64)
    alive = rem > 0
    finish[~alive] = 0.0

    cf_parts: list[np.ndarray] = []
    ce_parts: list[np.ndarray] = []
    t = 0.0
    n_solves = n_passes = 0
    while alive.any():
        idx = np.flatnonzero(alive)
        r, passes = _maxmin_host(src[idx], dst[idx], up, down, 16)
        n_solves += 1
        n_passes += passes
        dead = r <= _EPS
        if dead.any():
            # No capacity left for these flows (caller scheduled onto a
            # zero-rate link): they can never complete.
            alive[idx[dead]] = False
            idx, r = idx[~dead], r[~dead]
            if idx.size == 0:
                break
        ttf = rem[idx] / r
        dt = max(float(ttf.min()), quantum)
        adv = np.minimum(r * dt, rem[idx])
        # Chunk boundaries crossed inside this segment, per flow.
        old = delivered[idx]
        new = old + adv
        k0 = np.floor(old / chunk_bytes + _EPS).astype(np.int64)
        k1 = np.minimum(np.floor(new / chunk_bytes + _EPS), counts[idx]
                        ).astype(np.int64)
        ncross = k1 - k0
        if ncross.sum() > 0:
            which = np.flatnonzero(ncross > 0)
            reps = ncross[which]
            fl = np.repeat(idx[which], reps)
            base = np.repeat(k0[which], reps)
            off = np.arange(reps.sum()) - np.repeat(
                np.cumsum(reps) - reps, reps)
            kk = base + off + 1                     # 1-based chunk rank
            rr = np.repeat(r[which], reps)
            oo = np.repeat(old[which], reps)
            ce_parts.append(t + (kk * chunk_bytes - oo) / rr)
            cf_parts.append(fl)
        t += dt
        delivered[idx] = new
        rem[idx] -= adv
        done = rem[idx] <= _EPS * chunk_bytes
        if done.any():
            # Exact finish instants under the segment's constant rates.
            finish[idx[done]] = t - dt + ttf[done]
            alive[idx[done]] = False

    if cf_parts:
        chunk_flow = np.concatenate(cf_parts)
        chunk_end = np.concatenate(ce_parts)
        o = np.lexsort((chunk_end, chunk_flow))
        chunk_flow, chunk_end = chunk_flow[o], chunk_end[o]
    else:
        chunk_flow = np.zeros(0, np.int64)
        chunk_end = np.zeros(0, np.float64)
    fin = finish[np.isfinite(finish)]
    makespan = float(fin.max(initial=0.0))
    return FlowTimings(finish=finish, chunk_flow=chunk_flow,
                       chunk_end=chunk_end, makespan=makespan,
                       n_solves=n_solves, n_passes=n_passes)


@functools.lru_cache(maxsize=None)
def _transport_compiled(n: int, f_pad: int, m_pad: int,
                        max_passes: int):
    """Whole-segment-loop transport kernel over fixed extents.

    Carries the host loop's entire mutable state — wall clock, per-flow
    residual bytes, delivered curve, finish instants, alive mask, the
    padded per-chunk completion grid and the solve and pass counters —
    through one ``lax.while_loop``.  The flow-link incidence and the
    flows' link-capacity mask are fixed for the call and built before
    it.  Chunk rows record a completion instant the
    segment their 1-based rank is crossed by the flow's delivered-bytes
    curve; rows never crossed (dead flows, padding) stay ``inf`` and
    are dropped at the host boundary, reproducing the host path's
    emit-on-cross behaviour exactly.
    """

    def fairshare_transport(src, dst, counts_f, nbytes, cb, up, down,
                            quantum, c_flow, c_rank):

        inc = _incidence(src, dst, n)
        linked = (up[src] > _EPS) & (down[dst] > _EPS)

        def cond(carry):
            return jnp.any(carry[4])

        def body(carry):
            t, rem, delivered, finish, alive, cend, nsol, npass = carry
            r, passes = _maxmin_fill(src, dst, inc, up, down,
                                     alive & linked, max_passes)
            nsol = nsol + 1
            npass = npass + passes
            live = alive & (r > _EPS)       # dead: no capacity, ever
            ttf = jnp.where(live, rem / jnp.where(live, r, 1.0),
                            jnp.inf)
            tmin = jnp.min(ttf)
            dt = jnp.where(jnp.isfinite(tmin),
                           jnp.maximum(tmin, quantum), 0.0)
            adv = jnp.where(live, jnp.minimum(r * dt, rem), 0.0)
            old = delivered
            new = old + adv
            k0 = jnp.floor(old / cb + _EPS).astype(jnp.int64)
            k1 = jnp.minimum(jnp.floor(new / cb + _EPS),
                             counts_f).astype(jnp.int64)
            crossed = (c_rank > k0[c_flow]) & (c_rank <= k1[c_flow])
            endv = t + (c_rank.astype(jnp.float64) * cb
                        - old[c_flow]) / jnp.where(
                            crossed, r[c_flow], 1.0)
            cend = jnp.where(crossed, endv, cend)
            rem = rem - adv
            done = live & (rem <= _EPS * cb)
            finish = jnp.where(done, t + ttf, finish)
            return (t + dt, rem, new, finish, live & ~done, cend, nsol,
                    npass)

        alive0 = nbytes > 0
        init = (jnp.float64(0.0), nbytes,
                jnp.zeros(f_pad, jnp.float64),
                jnp.where(alive0, jnp.inf, 0.0), alive0,
                jnp.full(m_pad, jnp.inf, jnp.float64), jnp.int32(0),
                jnp.int32(0))
        out = lax.while_loop(cond, body, init)
        return out[3], out[5], out[6], out[7]

    return jax.jit(fairshare_transport)


def _transport_grids(src, dst, counts, nbytes):
    """The transport kernel's flow and chunk operands, padded to their
    power-of-two extents: ``(f, total, flows, chunks)`` with ``flows``
    the padded (src, dst, counts, nbytes) and ``chunks`` the padded
    (flow, 1-based rank) grid."""
    f = len(src)
    f_pad = _pow2(f)
    total = int(counts.sum())
    m_pad = _pow2(max(total, 1))
    sp = np.zeros(f_pad, np.int64)
    dp = np.zeros(f_pad, np.int64)
    cp = np.zeros(f_pad, np.float64)     # counts as float: k1 clamp
    bp = np.zeros(f_pad, np.float64)
    sp[:f] = src
    dp[:f] = dst
    cp[:f] = counts
    bp[:f] = nbytes
    # Chunk grid in (flow, rank) order — precisely the host path's
    # final lexsort((chunk_end, chunk_flow)) order, because ends are
    # strictly increasing with rank inside a flow.
    c_flow = np.zeros(m_pad, np.int64)
    c_rank = np.zeros(m_pad, np.int64)   # rank 0 pads can never cross
    c_flow[:total] = np.repeat(np.arange(f, dtype=np.int64), counts)
    c_rank[:total] = (np.arange(total, dtype=np.int64)
                      - np.repeat(np.cumsum(counts) - counts, counts)
                      + 1)
    return f, total, (sp, dp, cp, bp), (c_flow, c_rank)


def _transport_jax(grids, chunk_bytes, up, down, quantum):
    """The compiled transport on :func:`_transport_grids`' operands and
    one fetch of its four results."""
    f, total, (sp, dp, cp, bp), (c_flow, c_rank) = grids
    with jax.enable_x64(True):
        finish, cend, n_solves, n_passes = jax.device_get(
            _transport_compiled(len(up), len(sp), len(c_flow), 16)(
                sp, dp, cp, bp, np.float64(chunk_bytes), up, down,
                np.float64(quantum), c_flow, c_rank))
    finish = finish[:f]
    cend = cend[:total]
    emitted = np.isfinite(cend)
    fin = finish[np.isfinite(finish)]
    return FlowTimings(finish=finish, chunk_flow=c_flow[:total][emitted],
                       chunk_end=cend[emitted],
                       makespan=float(fin.max(initial=0.0)),
                       n_solves=int(n_solves), n_passes=int(n_passes))
