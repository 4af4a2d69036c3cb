"""Jitted slot engine: fixed-shape budgeted-round matching in JAX.

The default slot engine (``SwarmConfig.scheduler_impl="jit"``, the
one the chip runs) resolves a centralized slot as budgeted grant rounds
over all receivers at once — feasible-sender selection, GFF
loser-retry, grouped-cumsum uplink splits, tau concurrency gating,
non-owner-first two-tier grants and rarest-first prefix extraction —
as ONE ``lax.while_loop`` over packed uint32 bitplanes, with masked
convergence flags in place of data-dependent branches and retry loops.

Contract (docs/INVARIANTS.md "jit-engine contract"):

* **fixed shapes** — candidate columns pad to a power-of-two count and
  pack into ``W = m_pad/32`` uint32 words; per-grant batches extract
  into a ``t_cap``-wide buffer and rounds run under a static ``r_max``
  bound.  Pad bits are zero in both the supply and the need planes, so
  padding can never add a transfer; ``r_max``/``t_cap`` are sized from
  the slot budgets so they never truncate a legal grant sequence.
* **masked convergence** — every round updates all receivers under
  boolean masks; the loop exits early through its carry flag the first
  round that finds no feasible (receiver, sender) pair.
* **schedule legality is engine-independent** — uplink/downlink
  budgets, tau concurrency, adjacency, duplicate-freedom and the Eq. 1
  eligibility gate (the same owner-window maths as
  :meth:`SwarmState.eligible_supply`, staged on device) hold exactly as
  in the ``"loop"`` reference engine; the two engines are
  aggregate-equivalent, not byte-identical (each consumes randomness
  differently).

Scaling: the swarm-wide inventory lives on device as a packed
``(n, ceil(nK/32))`` uint32 plane, synced incrementally from the
transfer log by a buffer-donating scatter (delivery-exactly-once makes
bitwise-or and add interchangeable), so a slot never re-reads the
O(n * nK) boolean ``have`` matrix.  Per-slot host work is limited to
candidate selection, two O(m) gating vectors and decoding the kernel's
fixed-shape grant grids back into (sender, receiver, chunk) triples.

Randomness: exactly two host draws per slot — the rarest-first
tie-break and one 31-bit seed that keys the kernel's own hash-derived
noise streams — so a fixed ``SwarmConfig.seed`` replays the same
schedule byte for byte (tests/test_scheduler_equivalence.py).
"""
from __future__ import annotations

import functools

import numpy as np

from repro import obs

from .state import SwarmState

import jax
import jax.numpy as jnp
from jax import lax

_MODE_IDS = {"random_fifo": 0, "random_fastest_first": 1,
             "greedy_fastest_first": 2}
_GFF_RETRIES = 3          # loser re-picks per GFF round
_BIG = 1 << 30            # "unbounded" batch cap for the BT phase


def _empty():
    return (np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, np.int64))


def _pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1): pads every data-dependent
    extent to a small set of static shapes so jit recompiles O(log)
    times per run instead of once per slot."""
    return 1 << max(int(x) - 1, 0).bit_length()


def _pack_words(bits: np.ndarray, w: int) -> np.ndarray:
    """(n, m) bool -> (n, w) uint32, bit ``c & 31`` of word ``c >> 5``
    is column ``c`` (little-endian bit order; pad bits stay zero)."""
    p = np.packbits(bits, axis=1, bitorder="little")
    buf = np.zeros((bits.shape[0], w * 4), dtype=np.uint8)
    buf[:, :p.shape[1]] = p
    words = buf.view(np.uint32)
    if not np.little_endian:            # pragma: no cover - x86/arm are LE
        words = words.byteswap()
    return words


def _neighbor_lists(state: SwarmState) -> np.ndarray:
    """Padded (n, d_pad) neighbor lists (-1 pad) for the round's static
    overlay, device-cached so every slot reuses one upload."""
    cached = getattr(state, "_jit_nbr_cache", None)
    if cached is not None:
        return cached
    adj = state.adj
    n = adj.shape[0]
    deg = adj.sum(axis=1)
    d_pad = _pow2(max(int(deg.max(initial=1)), 1))
    nbr = np.full((n, d_pad), -1, dtype=np.int32)
    rows, cols = np.nonzero(adj)
    first = np.searchsorted(rows, np.arange(n))
    nbr[rows, np.arange(rows.size) - first[rows]] = cols
    dev = jnp.asarray(nbr)
    state._jit_nbr_cache = dev
    return dev


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_bits(words, rows, wcol, vals):
    # Delivery-exactly-once (state.apply_transfers de-dups against
    # ``have``) keeps every (row, chunk) bit unique for the whole
    # round, so add == bitwise-or; pad entries carry vals == 0.
    return words.at[rows, wcol].add(vals)


def _log_scatter(state: SwarmState, pos: int, nb: int):
    """Scatter operands (rows, word column, bit value) for transfer-log
    batches ``[pos:nb)``, padded to a power of two with zero values."""
    if pos < nb:
        rcv = np.concatenate(state.log.receivers[pos:nb])
        chk = np.concatenate(state.log.chunks[pos:nb])
    else:
        rcv = np.zeros(0, np.int32)
        chk = np.zeros(0, np.int64)
    pad = _pow2(rcv.size)
    rows = np.zeros(pad, dtype=np.int32)
    wcol = np.zeros(pad, dtype=np.int32)
    vals = np.zeros(pad, dtype=np.uint32)
    rows[:rcv.size] = rcv
    wcol[:rcv.size] = chk >> 5
    vals[:rcv.size] = np.left_shift(
        np.uint32(1), (chk & 31).astype(np.uint32))
    return jnp.asarray(rows), jnp.asarray(wcol), jnp.asarray(vals)


def _diag_words(state: SwarmState, w_full: int) -> np.ndarray:
    """Packed owner-diagonal inventory (client v holds exactly chunks
    [vK, vK+K)) — the analytic post-construction state, built directly
    in the bit domain."""
    n = state.cfg.n
    K = state.cfg.chunks_per_update
    v = np.arange(n, dtype=np.int64)
    lo = (v * K)[:, None]
    wj = lo // 32 + np.arange(K // 32 + 2)[None, :]
    s = np.clip(lo - 32 * wj, 0, 32).astype(np.uint64)
    e = np.clip(lo + K - 32 * wj, 0, 32).astype(np.uint64)
    mask = (((np.uint64(1) << e) - 1)
            ^ ((np.uint64(1) << s) - 1)).astype(np.uint32)
    words = np.zeros((n, w_full), dtype=np.uint32)
    np.bitwise_or.at(
        words,
        (np.broadcast_to(v[:, None], wj.shape),
         np.minimum(wj, w_full - 1)),
        np.where(wj < w_full, mask, np.uint32(0)))
    return words


def _sync_have_dev(state: SwarmState):
    """Device copy of the packed swarm inventory, synced incrementally.

    The transfer log is the single write path for ``state.have`` after
    construction, so replaying batches appended since the last call
    reproduces the matrix bit for bit.  A swapped ``have`` identity
    (Byzantine claimed inventories) falls back to a full repack.
    """
    nb = len(state.log.receivers)
    cache = getattr(state, "_jit_have_cache", None)
    if cache is not None and cache[0] is state.have:
        dev, pos = cache[1], cache[2]
        if pos < nb:
            dev = _scatter_bits(dev, *_log_scatter(state, pos, nb))
        state._jit_have_cache = (state.have, dev, nb)
        return dev
    w_full = -(-state.have.shape[1] // 32)
    if cache is None and state.have is getattr(
            state, "_have_pristine", None):
        # First build of the genuine inventory: the owner diagonal is
        # analytic and the log already records every later delivery, so
        # packing in the bit domain skips an np.packbits pass over the
        # multi-GB bool matrix.
        dev = _scatter_bits(jnp.asarray(_diag_words(state, w_full)),
                            *_log_scatter(state, 0, nb))
        state._jit_have_cache = (state.have, dev, nb)
        return dev
    dev = jnp.asarray(_pack_words(state.have, w_full))
    state._jit_have_cache = (state.have, dev, nb)
    return dev


# ----------------------------------------------------------------------
# Kernel-side helpers (jit-slated: JIT_TARGETS tracks them)
# ----------------------------------------------------------------------

def _mix32(x):
    """32-bit finalizer hash: one fresh tie-break lattice per round and
    retry from a single per-slot seed, without a per-round PRNG walk."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _seed_stream(seed, salt: int, shape):
    """uint32 noise lattice keyed by the per-slot seed and a stream salt
    alone.  Two finalizer passes over (seed, salt, flat index): unlike
    ``jax.random``, whose bits follow the global
    ``jax_threefry_partitionable`` flag, the stream is a pure function
    of its inputs, so a seed replays the same schedule on any jax
    configuration and any backend."""
    s = _mix32(jnp.asarray(seed).astype(jnp.uint32)
               ^ jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF))
    idx = lax.iota(jnp.uint32, int(np.prod(shape))).reshape(shape)
    return _mix32(_mix32(idx ^ s) + s)


def _kth_set_bit(word, k):
    """Bit index of the ``k``-th (0-based) set bit of each uint32 word.

    Five-level binary descent over word halves (16/8/4/2/1) — no loop
    over bit positions, undefined when ``k >= popcount(word)`` (callers
    mask those lanes).
    """
    w = word
    kk = k.astype(jnp.int32)
    bit = jnp.zeros_like(kk)
    for half in (16, 8, 4, 2, 1):
        lo = w & jnp.uint32((1 << half) - 1)
        c = lax.population_count(lo).astype(jnp.int32)
        hi = kk >= c
        kk = kk - jnp.where(hi, c, 0)
        bit = bit + jnp.where(hi, half, 0)
        w = jnp.where(hi, w >> half, lo)
    return bit


def _rank_counts(rows):
    """Per-superblock inclusive popcount cumsum of a packed plane.

    One fused pass over the (n, W) plane; the (n, S) cumsum (S = 16
    superblocks, or 1 when W is not divisible) is everything
    :func:`_extract_ranked` needs to locate ranks without touching the
    plane again, and its last column is each row's total popcount.
    """
    n, W = rows.shape
    S = 16 if W % 16 == 0 else 1           # superblocks per row
    B = W // S                             # words per superblock
    sb = jnp.sum(lax.population_count(rows.reshape(n, S, B)), axis=2,
                 dtype=jnp.int32)
    return jnp.cumsum(sb, axis=1)          # (n, S) inclusive


def _extract_ranked(rows, sb_cum, want, t_cap: int):
    """First ``want[i]`` set bits of each packed row, rarest first.

    ``sb_cum`` is the plane's :func:`_rank_counts`.  Returns ``(sel,
    cols)``: the selected bits as a plane of the same shape and the
    (n, t_cap) word*32+bit column ids (-1 past the batch).
    Hierarchical rank search: rank k's superblock falls out of the
    tiny cumsum, only that superblock's words are gathered, and a binary
    descent (:func:`_kth_set_bit`) finds the bit — no further
    full-plane pass.
    """
    n, W = rows.shape
    S = sb_cum.shape[1]
    B = W // S
    ridx = jnp.arange(n)
    total = sb_cum[:, -1]
    ks = jnp.arange(t_cap, dtype=jnp.int32)
    # superblock holding rank k: first s with sb_cum[s] > k
    sbk = jnp.sum((sb_cum[:, None, :] <= ks[None, :, None]).astype(
        jnp.int32), axis=2)
    sbk = jnp.minimum(sbk, S - 1)
    prev_sb = jnp.where(
        sbk > 0,
        jnp.take_along_axis(sb_cum, jnp.maximum(sbk - 1, 0), axis=1), 0)
    k_in = ks[None, :] - prev_sb           # rank within superblock
    widx = sbk[:, :, None] * B + jnp.arange(B)[None, None, :]
    words = rows[ridx[:, None, None], widx]          # (n, t_cap, B)
    wcum = jnp.cumsum(lax.population_count(words).astype(jnp.int32),
                      axis=2)
    wk_in = jnp.sum((wcum <= k_in[:, :, None]).astype(jnp.int32),
                    axis=2)
    wk_in = jnp.minimum(wk_in, B - 1)
    prev_w = jnp.where(
        wk_in > 0,
        jnp.take_along_axis(
            wcum, jnp.maximum(wk_in - 1, 0)[..., None],
            axis=2)[..., 0], 0)
    word = jnp.take_along_axis(words, wk_in[..., None], axis=2)[..., 0]
    bit = _kth_set_bit(word, k_in - prev_w)
    wk = sbk * B + wk_in
    valid = (ks[None, :] < want[:, None]) & (ks[None, :] < total[:, None])
    cols = jnp.where(valid, wk * 32 + bit, -1)
    sel = jnp.zeros_like(rows).at[ridx[:, None], wk].add(
        jnp.where(valid,
                  jnp.left_shift(jnp.uint32(1), bit.astype(jnp.uint32)),
                  jnp.uint32(0)))
    return sel, cols


def _first_bits(rows, want, t_cap: int):
    """:func:`_extract_ranked` with the rank pass folded in (tests and
    one-shot callers)."""
    return _extract_ranked(rows, _rank_counts(rows), want, t_cap)


def _slot_rounds(mode_id: int, nonowner: bool, ungated: bool,
                 t_cap: int, r_max: int, have_dev, cand, owner_row,
                 own_allowed, m_cnt, recv_ok, nbr, rem_up, rem_down,
                 batch_cap, tau, seed):
    """One slot — plane build plus budgeted-round matching, fully staged.

    Stage 1 gathers the candidate columns out of the device-resident
    packed inventory, repacks them in rarest-first bit order and applies
    the owner-window gate (the :meth:`SwarmState.eligible_supply`
    single-owner-cell fix-up) on device.  Stage 2 is the
    ``lax.while_loop`` over grant rounds: it carries the need planes,
    the remaining uplink/downlink and tau budgets, the serving and
    tombstone pair masks and the fixed-shape output grids; every round
    is fully masked so the trace stays shape-stable.  Returns
    ``(out_snd, out_col, rounds)``: per (round, receiver) the granted
    sender (-1 none) and its rarest-first column batch (-1 pad),
    non-owner tier first within each grant, and the number of rounds
    the loop ran (its carry slot 0, between 1 and ``r_max``).
    """
    n = have_dev.shape[0]
    m_pad = cand.shape[0]
    w_words = m_pad // 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    cidx = jnp.arange(m_pad)
    valid = cidx < m_cnt
    col_w = (cidx >> 5).astype(jnp.int32)
    col_b = (cidx & 31).astype(jnp.uint32)
    col_bit = jnp.where(valid, jnp.left_shift(jnp.uint32(1), col_b),
                        jnp.uint32(0))

    # ---- stage 1: candidate planes from the packed inventory ----
    bits = ((have_dev[:, (cand >> 5).astype(jnp.int32)]
             >> (cand & 31).astype(jnp.uint32)[None, :]) & jnp.uint32(1))
    bits = jnp.where(valid[None, :], bits, jnp.uint32(0))
    hv_w = jnp.sum(bits.reshape(n, w_words, 32) << shifts, axis=2,
                   dtype=jnp.uint32)
    valid_w = jnp.sum(valid.reshape(w_words, 32).astype(jnp.uint32)
                      << shifts, axis=1, dtype=jnp.uint32)
    if nonowner or not ungated:
        own_w = jnp.zeros((n, w_words), dtype=jnp.uint32).at[
            owner_row, col_w].add(col_bit)
    else:
        own_w = None
    if ungated:
        sup_w = hv_w
    else:
        # eligible_supply's owner fix-up: each column has exactly one
        # owner cell — clear it, then restore iff the window is open
        # and the owner actually holds the chunk.
        have_own = (hv_w[owner_row, col_w] >> col_b) & jnp.uint32(1)
        set_bit = jnp.where(own_allowed & (have_own > 0), col_bit,
                            jnp.uint32(0))
        own_set = jnp.zeros((n, w_words), dtype=jnp.uint32).at[
            owner_row, col_w].add(set_bit)
        sup_w = (hv_w & ~own_w) | own_set
    if nonowner:
        # Tier planes once per slot: the round body then pays one
        # gather per tier instead of re-deriving them from own_w.
        sup_no_w = sup_w & ~own_w
        sup_ow_w = sup_w & own_w
    need_w0 = jnp.where(recv_ok[:, None], ~hv_w & valid_w[None, :],
                        jnp.uint32(0))
    sup_any = jnp.sum(lax.population_count(sup_w), axis=1) > 0
    nbrc = jnp.maximum(nbr, 0)
    valid_nbr = nbr >= 0
    live0 = valid_nbr & sup_any[nbrc]
    need_cnt0 = jnp.sum(lax.population_count(need_w0), axis=1,
                        dtype=jnp.int32)

    vidx = jnp.arange(n)
    noise_base = _seed_stream(seed, 1, nbr.shape)
    tie_base = _seed_stream(seed, 2, (n,))
    prio_base = _seed_stream(seed, 3, (n,))
    u01 = jnp.float32(2.0 ** -32)

    # ---- stage 2: budgeted grant rounds ----
    def cond(carry):
        return (carry[0] < r_max) & ~carry[1]

    def body(carry):
        (r, _stop, need_w, need_cnt, rem_up, rem_down, recv_slots,
         serving, live, out_snd, out_col) = carry
        ru = r.astype(jnp.uint32)

        needy = (rem_down > 0) & (need_cnt > 0)
        feas = (live & valid_nbr & needy[:, None]
                & (rem_up[nbrc] > 0)
                & ((recv_slots[nbrc] > 0) | serving))
        noise = _mix32(noise_base ^ (ru * jnp.uint32(0x9E3779B9))
                       ).astype(jnp.float32) * u01
        if mode_id == 2:                 # GFF: fastest remaining uplink
            score = rem_up[nbrc].astype(jnp.float32) + noise
        else:
            score = noise
        score = jnp.where(feas, score, -jnp.inf)

        if mode_id == 2:
            # One receiver per sender; losers re-pick among untaken
            # senders, a fixed number of masked retries.
            d_sel = jnp.argmax(score, axis=1).astype(jnp.int32)
            act = jnp.take_along_axis(feas, d_sel[:, None], 1)[:, 0]
            pair = jnp.zeros(n, dtype=bool)
            d_v = jnp.zeros(n, dtype=jnp.int32)
            taken = jnp.zeros(n, dtype=jnp.int32)
            for it in range(_GFF_RETRIES):
                salt = jnp.uint32((it * 0xC2B2AE35) & 0xFFFFFFFF)
                tie = _mix32(tie_base ^ (ru * jnp.uint32(0x85EBCA6B)
                                         + salt)
                             ).astype(jnp.float32) * u01
                tie = jnp.where(act, tie, -1.0)
                u_sel = nbrc[vidx, d_sel]
                wkey = jnp.full(n, -2.0).at[u_sel].max(tie)
                win = act & (tie >= 0.0) & (tie == wkey[u_sel])
                pair = pair | win
                d_v = jnp.where(win, d_sel, d_v)
                taken = taken.at[u_sel].max(win.astype(jnp.int32))
                score = jnp.where(taken[nbrc] > 0, -jnp.inf, score)
                act = act & ~win
                d_sel = jnp.argmax(score, axis=1).astype(jnp.int32)
                best = jnp.take_along_axis(score, d_sel[:, None], 1)[:, 0]
                act = act & jnp.isfinite(best)
        else:
            # Sender multi-serve: every receiver keeps its chosen
            # sender; the grouped split below divides each uplink.
            d_v = jnp.argmax(score, axis=1).astype(jnp.int32)
            best = jnp.take_along_axis(score, d_v[:, None], 1)[:, 0]
            pair = jnp.isfinite(best)

        u_v = jnp.where(pair, nbrc[vidx, d_v], n)     # n = no pair
        u_c = jnp.minimum(u_v, n - 1)
        # Unpaired rows gather garbage (clamped sender n-1); every
        # consumer below is masked on pair/take, so no plane-wide
        # where() is spent zeroing them.
        if nonowner:
            rows_no = sup_no_w[u_c] & need_w
            sbc_no = _rank_counts(rows_no)
            cnt_no = jnp.where(pair, sbc_no[:, -1], 0)
            # owner-tier overlap: fused gather+and+popcount reduction,
            # the plane itself only materializes under the lax.cond
            cnt_ow = jnp.where(pair, jnp.sum(
                lax.population_count(sup_ow_w[u_c] & need_w), axis=1,
                dtype=jnp.int32), 0)
            cnt = cnt_no + cnt_ow
        else:
            rows = sup_w[u_c] & need_w
            sbc = _rank_counts(rows)
            cnt = jnp.where(pair, sbc[:, -1], 0)
        dead = pair & (cnt == 0)                      # tombstone
        live = live.at[vidx, d_v].set(live[vidx, d_v] & ~dead)

        req = jnp.minimum(jnp.minimum(rem_down, cnt), batch_cap)
        req = jnp.where(pair, req, 0)
        # Mode-priority order within each sender group: fastest
        # downlink first for RFF, random arrival otherwise.
        pn = _mix32(prio_base ^ (ru * jnp.uint32(0x27D4EB2F))
                    ).astype(jnp.float32) * u01
        if mode_id == 1:
            recv_prio = -(rem_down.astype(jnp.float32) + pn)
        else:
            recv_prio = pn
        order = jnp.lexsort((recv_prio, u_v))
        us = u_v[order]
        us_c = jnp.minimum(us, n - 1)
        reqs = req[order]
        is_new = pair & ~serving[vidx, d_v]
        isn = is_new[order]
        first = jnp.searchsorted(us, us)
        # tau gate: only the first recv_slots[u] NEW pairs of each
        # sender group may open a serve slot this round.
        cn = jnp.cumsum(isn)
        excl_new = cn - isn
        new_rank = excl_new - excl_new[first]
        reqs = jnp.where((us < n) & (~isn | (new_rank < recv_slots[us_c])),
                         reqs, 0)
        # uplink split: grouped exclusive cumsum of requests caps each
        # pair at what its sender has left after earlier pairs.
        cq = jnp.cumsum(reqs)
        excl = cq - reqs
        take_s = jnp.minimum(reqs, jnp.maximum(
            rem_up[us_c] - (excl - excl[first]), 0))
        take = jnp.zeros(n, dtype=jnp.int32).at[order].set(
            take_s.astype(jnp.int32))
        granted = take > 0

        if nonowner:
            # Non-owner-first WITHIN each grant: fill from the
            # non-owner overlap, owner chunks only for the remainder.
            t_no = jnp.minimum(take, cnt_no)
            t_ow = take - t_no
            sel_no, cols_no = _extract_ranked(rows_no, sbc_no, t_no,
                                              t_cap)

            def owner_tier(_):
                return _first_bits(sup_ow_w[u_c] & need_w, t_ow, t_cap)

            def no_owner_tier(_):
                return (jnp.zeros_like(need_w),
                        jnp.full((n, t_cap), -1, dtype=jnp.int32))

            sel_ow, cols_ow = lax.cond(jnp.any(t_ow > 0), owner_tier,
                                       no_owner_tier, None)
            sel = sel_no | sel_ow
            ks = jnp.arange(t_cap)[None, :]
            shift = jnp.clip(ks - t_no[:, None], 0, t_cap - 1)
            cols = jnp.where(ks < t_no[:, None], cols_no,
                             jnp.take_along_axis(cols_ow, shift, axis=1))
            cols = jnp.where(ks < take[:, None], cols, -1)
        else:
            sel, cols = _extract_ranked(rows, sbc, take, t_cap)

        need_w = need_w & ~sel
        need_cnt = need_cnt - take
        rem_down = rem_down - take
        rem_up = rem_up.at[u_c].add(jnp.where(granted, -take, 0))
        fresh = granted & is_new
        serving = serving.at[vidx, d_v].set(serving[vidx, d_v] | fresh)
        recv_slots = recv_slots.at[u_c].add(-fresh.astype(jnp.int32))
        out_snd = out_snd.at[r].set(
            jnp.where(granted, u_v.astype(jnp.int32), jnp.int32(-1)))
        out_col = out_col.at[r].set(cols)
        stop = ~jnp.any(pair)
        return (r + 1, stop, need_w, need_cnt, rem_up, rem_down,
                recv_slots, serving, live, out_snd, out_col)

    init = (jnp.int32(0), jnp.bool_(False), need_w0, need_cnt0,
            rem_up, rem_down, jnp.full((n,), tau, dtype=jnp.int32),
            jnp.zeros_like(live0), live0,
            jnp.full((r_max, n), -1, dtype=jnp.int32),
            jnp.full((r_max, n, t_cap), -1, dtype=jnp.int32))
    out = lax.while_loop(cond, body, init)
    return out[-2], out[-1], out[0]


@functools.lru_cache(maxsize=64)
def _compiled(mode_id: int, nonowner: bool, ungated: bool, t_cap: int,
              r_max: int):
    # A named function, so the device program reads
    # ``jit_matching_rounds`` in a profiler trace.
    def matching_rounds(*operands):
        return _slot_rounds(mode_id, nonowner, ungated, t_cap, r_max,
                            *operands)
    return jax.jit(matching_rounds)


# ----------------------------------------------------------------------
# Host boundary: candidate prep, kernel dispatch, grant-grid decode
# ----------------------------------------------------------------------

def schedule_centralized_jit(state: SwarmState, mode: str):
    """One slot of the centralized family on the jitted engine.

    Under an enabled recorder four wall spans time it:
    ``matching.prep`` (candidates, the two rng draws, owner windows,
    padding), ``matching.sync`` (device inventory and neighbour lists),
    ``matching.kernel`` (kernel lookup, uploads, dispatch and the
    blocking fetch of the grant grids) and ``matching.decode``.  Only
    then is the kernel's round count fetched: it feeds the
    ``matching.rounds`` counter and is noted, with the slot's static
    buckets, on the ``matching.kernel`` row.
    """
    cfg = state.cfg
    rng = state.rng
    rec = obs.get()

    with rec.span("matching.prep"):
        sactive = state.senders_active()
        rem_up = np.where(sactive, state.up, 0).astype(np.int32)
        rem_down = np.where(state.active, state.down, 0).astype(np.int32)

        cand = state.candidate_columns(sactive)
        if cand.size == 0:
            return _empty()
        # Rarest-first priority draw with a random tie-break, then
        # one seed draw for the kernel streams: two draws per slot,
        # always in this order (rng discipline: the twin tests replay
        # on it).
        prio = state.replicas[cand].astype(np.float32)
        prio += rng.random(cand.size, dtype=np.float32)
        cand = cand[np.argsort(prio)]
        seed = int(rng.integers(0, 2 ** 31 - 1))
        m = cand.size

        max_up = int(rem_up.max(initial=0))
        max_down = int(rem_down.max(initial=0))
        if max_up == 0 or max_down == 0:
            return _empty()
        warm = state.phase != "bt"
        recv_ok = state.active & (rem_down > 0)
        if warm:
            recv_ok = recv_ok & (state.hold < cfg.k_term)
        if not recv_ok.any():
            return _empty()

        # Static-shape buckets: the candidate count pads to a power of
        # two (floored near the universe size so small swarms compile
        # once).
        universe = state.have.shape[1]
        m_pad = max(_pow2(max(m, min(universe, 512))), 32)
        cand_p = np.zeros(m_pad, dtype=np.int32)
        cand_p[:m] = cand
        owner_p = np.zeros(m_pad, dtype=np.int32)
        owner_p[:m] = state.owners[cand]
        ungated = (not warm) or (not cfg.enable_gating)
        allowed_p = np.zeros(m_pad, dtype=bool)
        if not ungated:
            K = cfg.chunks_per_update
            kappa = cfg.owner_throttle
            _, starts, gated = state.owner_windows()
            co = state.owners[cand]
            off = cand - co * K
            allowed_p[:m] = ((((off - starts[co]) % K) < kappa)
                             & ~gated[co])
        nonowner_pass = bool(cfg.enable_nonowner_first) and warm

        # Warm-up grants carry a fan-in cap (§IV-C: the attack surface
        # depends on receivers fanning in from ~all feasible
        # neighbors); BT batches stay budget-bound.
        batch_cap = max(max_up // 4, 1) if warm else _BIG
        t_cap = _pow2(min(batch_cap, max_down, max_up))
        r_max = min(_pow2(-(-max_down // min(batch_cap, max_down)) + 8),
                    64)

    with rec.span("matching.sync"):
        have_dev = _sync_have_dev(state)
        nbr_dev = _neighbor_lists(state)

    with rec.span("matching.kernel") as sp:
        kernel = _compiled(_MODE_IDS[mode], nonowner_pass, ungated,
                           t_cap, r_max)
        out_snd, out_col, rounds = kernel(
            have_dev, jnp.asarray(cand_p), jnp.asarray(owner_p),
            jnp.asarray(allowed_p), jnp.int32(m), jnp.asarray(recv_ok),
            nbr_dev, jnp.asarray(rem_up), jnp.asarray(rem_down),
            jnp.int32(min(batch_cap, _BIG)),
            jnp.int32(cfg.tau_concurrent), seed)
        out_snd = np.asarray(out_snd)
        out_col = np.asarray(out_col)
        if rec.enabled:
            rounds = int(rounds)
            rec.counter("matching.rounds", rounds)
            sp.note(rounds=rounds, m_pad=m_pad, t_cap=t_cap, r_max=r_max)

    with rec.span("matching.decode"):
        # Decode the grant grids in (round, receiver, pick) order:
        # within a grant picks are rarity-ordered with the non-owner
        # tier first.
        r_i, v_i, k_i = np.nonzero(out_col >= 0)
        if r_i.size == 0:
            return _empty()
        snd = out_snd[r_i, v_i].astype(np.int64)
        rcv = v_i.astype(np.int64)
        chk = cand[out_col[r_i, v_i, k_i]]
    return snd, rcv, chk
