"""repro.net: fair-share allocation, event-engine cross-validation
against the slot engine, wall-clock metrics, and the capacity-clamp
warning (ISSUE 5 acceptance surface)."""
import warnings

import numpy as np
import pytest

from repro.core import SwarmConfig, SwarmSession, simulate_round
from repro.core.capacities import MBPS, LinkModel
from repro.core.maxflow import (stage_time_lower_bound,
                                warmup_time_bounds)
from repro.core.simulator import RoundSimulator
from repro.net import NetConfig, maxmin_rates, transport


# ---------------------------------------------------------------------------
# fairshare: max-min progressive filling
# ---------------------------------------------------------------------------

def test_maxmin_single_shared_uplink():
    # 3 flows out of sender 0 to uncontended receivers: equal thirds.
    src = np.array([0, 0, 0])
    dst = np.array([1, 2, 3])
    up = np.array([9.0, 9.0, 9.0, 9.0])
    down = np.array([100.0] * 4)
    r = maxmin_rates(src, dst, up, down)
    assert np.allclose(r, 3.0)


def test_maxmin_bottleneck_redistribution():
    # Flow A: 0->1 (down[1]=2 caps it); flow B: 0->2.  Uplink 10 shared:
    # progressive filling freezes A at 2, B gets the rest up to down[2].
    src = np.array([0, 0])
    dst = np.array([1, 2])
    up = np.array([10.0, 100.0, 100.0])
    down = np.array([100.0, 2.0, 100.0])
    r = maxmin_rates(src, dst, up, down)
    assert np.isclose(r[0], 2.0)
    assert np.isclose(r[1], 8.0)


def test_maxmin_never_oversubscribes():
    rng = np.random.default_rng(0)
    n, f = 12, 60
    src = rng.integers(0, n, f)
    dst = (src + 1 + rng.integers(0, n - 1, f)) % n
    up = rng.uniform(1.0, 20.0, n)
    down = rng.uniform(1.0, 20.0, n)
    r = maxmin_rates(src, dst, up, down)
    out = np.bincount(src, weights=r, minlength=n)
    inn = np.bincount(dst, weights=r, minlength=n)
    assert (out <= up * (1 + 1e-6)).all()
    assert (inn <= down * (1 + 1e-6)).all()
    assert (r > 0).all()


def test_maxmin_truncated_tail_stays_feasible():
    # Force many distinct bottleneck levels with max_passes=1: the tail
    # fill must stay feasible (no link over capacity).
    rng = np.random.default_rng(1)
    n, f = 30, 200
    src = rng.integers(0, n, f)
    dst = (src + 1 + rng.integers(0, n - 1, f)) % n
    up = rng.uniform(1.0, 50.0, n)
    down = rng.uniform(1.0, 50.0, n)
    r = maxmin_rates(src, dst, up, down, max_passes=1)
    out = np.bincount(src, weights=r, minlength=n)
    inn = np.bincount(dst, weights=r, minlength=n)
    assert (out <= up * (1 + 1e-6)).all()
    assert (inn <= down * (1 + 1e-6)).all()


def test_transport_emits_every_chunk_in_pipeline_order():
    src = np.array([0, 1])
    dst = np.array([2, 2])
    counts = np.array([5, 3])
    tm = transport(src, dst, counts, 10.0,
                   up=np.array([10.0, 10.0, 10.0]),
                   down=np.array([10.0, 10.0, 8.0]))
    emitted = np.bincount(tm.chunk_flow, minlength=2)
    assert (emitted == counts).all()
    # within each flow, completion instants are non-decreasing
    for fl in (0, 1):
        t = tm.chunk_end[tm.chunk_flow == fl]
        assert (np.diff(t) >= -1e-9).all()
    assert np.isclose(tm.makespan, np.nanmax(tm.finish))
    # total bytes / makespan cannot beat the receiver's downlink
    assert tm.makespan >= (counts.sum() * 10.0) / 8.0 - 1e-6


def test_transport_homogeneous_equal_flows_tie():
    # identical flows finish together at bytes/(cap/f)
    f = 4
    src = np.arange(f)
    dst = np.full(f, f)
    counts = np.full(f, 6)
    up = np.full(f + 1, 100.0)
    down = np.full(f + 1, 12.0)
    tm = transport(src, dst, counts, 2.0, up, down)
    assert np.allclose(tm.finish, 6 * 2.0 / (12.0 / f))


# ---------------------------------------------------------------------------
# fairshare: jitted kernels vs the host twins
# ---------------------------------------------------------------------------

# The kernels mirror the host twins' IEEE operation order, so the two
# agree to a few ulps: rates, finish instants, the makespan and every
# chunk's completion instant within this relative tolerance.
TWIN_RTOL = 1e-12


def _stage(seed: int, n: int, flows: int):
    """A heterogeneous stage of unique (sender, receiver) flows: links
    spread over two decades of bytes/s, 1-12 chunks of 256 KiB each."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, flows)
    dst = (src + 1 + rng.integers(0, n - 1, flows)) % n
    pair = np.unique(src * n + dst)
    src, dst = pair // n, pair % n
    up = 10.0 ** rng.uniform(5.0, 7.0, n)
    down = 10.0 ** rng.uniform(5.5, 7.5, n)
    counts = rng.integers(1, 13, len(src))
    return src, dst, counts, up, down


@pytest.mark.parametrize("seed,n,flows", [(0, 12, 40), (1, 40, 150),
                                          (2, 100, 600), (3, 500, 1500)])
def test_fairshare_kernels_match_host_twins(seed, n, flows):
    from repro.net.fairshare import (_maxmin_host, _transport_host,
                                     congestion_bound)
    src, dst, counts, up, down = _stage(seed, n, flows)
    r = maxmin_rates(src, dst, up, down)
    np.testing.assert_allclose(r, _maxmin_host(src, dst, up, down, 16)[0],
                               rtol=TWIN_RTOL, atol=0)

    cb = 256 * 1024.0
    frac = 1 / 32
    tm = transport(src, dst, counts, cb, up, down, quantum_frac=frac)
    nbytes = counts * cb
    quantum = frac * congestion_bound(src, dst, nbytes, up, down)
    host = _transport_host(src, dst, counts, nbytes, cb, up, down,
                           quantum)
    assert tm.n_solves == host.n_solves
    np.testing.assert_allclose(tm.finish, host.finish, rtol=TWIN_RTOL,
                               atol=0)
    assert abs(tm.makespan - host.makespan) <= TWIN_RTOL * host.makespan
    np.testing.assert_array_equal(tm.chunk_flow, host.chunk_flow)
    np.testing.assert_allclose(tm.chunk_end, host.chunk_end,
                               rtol=TWIN_RTOL, atol=0)


@pytest.mark.parametrize("seed,n,flows,f_pad", [
    (0, 12, 40, 64),          # pad flows and peers that carry no flow
    (1, 500, 1500, 2048),     # a residential warm-up cycle
    (2, 500, 20000, 32768),   # the residential round's spray
])
def test_link_incidence_counts_equal_bincount(seed, n, flows, f_pad):
    import jax
    from repro.net.fairshare import _crosses, _incidence, _link_counts
    rng = np.random.default_rng(seed)
    # peers n-3..n-1 send nothing, peers 0..2 receive nothing
    src = np.zeros(f_pad, np.int64)
    dst = np.zeros(f_pad, np.int64)
    src[:flows] = rng.integers(0, n - 3, flows)
    dst[:flows] = rng.integers(3, n, flows)
    unfrozen = np.zeros(f_pad, bool)
    unfrozen[:flows] = rng.random(flows) < 0.7
    on_up = rng.random(n) < 0.1
    on_down = rng.random(n) < 0.1

    @jax.jit
    def kernel(src, dst, unfrozen, on_up, on_down):
        inc = _incidence(src, dst, n)
        return _link_counts(inc, unfrozen), _crosses(inc, on_up, on_down)

    with jax.enable_x64(True):
        (nu, nd), crosses = kernel(src, dst, unfrozen, on_up, on_down)
    np.testing.assert_array_equal(
        nu, np.bincount(src[unfrozen], minlength=n))
    np.testing.assert_array_equal(
        nd, np.bincount(dst[unfrozen], minlength=n))
    assert nu[n - 3:].sum() == 0 and nd[:3].sum() == 0
    np.testing.assert_array_equal(crosses, on_up[src] | on_down[dst])


@pytest.mark.parametrize("seed,n,flows,frac", [(0, 12, 40, 1 / 64),
                                               (1, 40, 150, 0.0),
                                               (2, 100, 600, 1 / 32)])
def test_transport_counts_the_host_twins_passes(seed, n, flows, frac):
    from repro.net.fairshare import _transport_host, congestion_bound
    src, dst, counts, up, down = _stage(seed, n, flows)
    cb = 256 * 1024.0
    tm = transport(src, dst, counts, cb, up, down, quantum_frac=frac)
    nbytes = counts * cb
    quantum = frac * congestion_bound(src, dst, nbytes, up, down)
    host = _transport_host(src, dst, counts, nbytes, cb, up, down,
                           quantum)
    assert tm.n_solves == host.n_solves
    assert tm.n_passes == host.n_passes
    assert host.n_solves <= host.n_passes <= 16 * host.n_solves


@pytest.mark.parametrize("seed,n,flows", [(0, 12, 40), (3, 500, 1500)])
def test_water_fill_passes_match_host_twin_solve_by_solve(seed, n, flows):
    """One solve per shrinking alive set, as the segment loop runs them:
    the kernel's water-fill runs the host twin's passes each time."""
    import jax
    from repro.net.fairshare import _incidence, _maxmin_fill, _maxmin_host
    src, dst, _, up, down = _stage(seed, n, flows)

    @jax.jit
    def solve(src, dst, up, down, alive):
        return _maxmin_fill(src, dst, _incidence(src, dst, n), up, down,
                            alive, 16)

    rng = np.random.default_rng(seed)
    alive = np.ones(len(src), bool)
    while alive.any():
        with jax.enable_x64(True):
            rates, passes = solve(src, dst, up, down, alive)
        want, host_passes = _maxmin_host(src[alive], dst[alive], up, down,
                                         16)
        assert int(passes) == host_passes
        np.testing.assert_allclose(np.asarray(rates)[alive], want,
                                   rtol=TWIN_RTOL, atol=0)
        alive &= rng.random(len(src)) < 0.6


def test_transport_pass_counters_recorded():
    from repro import obs
    src, dst, counts, up, down = _stage(4, 30, 100)
    with obs.recording() as rec:
        tm = transport(src, dst, counts, 256 * 1024.0, up, down)
    assert rec.metrics["fairshare.passes"]["value"] == tm.n_passes
    assert rec.metrics["fairshare.solves"]["value"] == tm.n_solves
    assert tm.n_passes >= tm.n_solves > 0


def test_event_engine_records_foreground_passes():
    from repro import obs
    cfg = SwarmConfig(n=16, chunks_per_update=16, s_max=4000, seed=3)
    with obs.recording() as rec:
        simulate_round(cfg, time_engine="event",
                       net=NetConfig(tracker_rtt_s=0.05))
    m = rec.metrics
    assert m["net.fg_passes"]["value"] == m["fairshare.passes"]["value"]
    assert m["net.fg_passes"]["value"] >= m["net.fg_solves"]["value"] > 0


# ---------------------------------------------------------------------------
# cross-validation: event engine == slot engine schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["greedy_fastest_first",
                                    "distributed"])
@pytest.mark.parametrize("seed", [0, 1])
def test_event_engine_reproduces_slot_schedule(policy, seed):
    cfg = SwarmConfig(n=24, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=seed, scheduler=policy)
    rs = simulate_round(cfg)
    re = simulate_round(cfg, time_engine="event",
                        net=NetConfig(tracker_rtt_s=0.05))
    # identical schedules: same rows, transfer for transfer
    assert len(rs.log) == len(re.log)
    assert np.array_equal(rs.log.slot, re.log.slot)
    assert np.array_equal(rs.log.sender, re.log.sender)
    assert np.array_equal(rs.log.receiver, re.log.receiver)
    assert np.array_equal(rs.log.chunk, re.log.chunk)
    assert rs.metrics.t_warm == re.metrics.t_warm
    assert rs.metrics.t_round == re.metrics.t_round


@pytest.mark.parametrize("seed", [0, 1])
def test_t_start_order_consistent_with_slot_order(seed):
    cfg = SwarmConfig(n=24, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=seed)
    res = simulate_round(cfg, time_engine="event",
                         net=NetConfig(tracker_rtt_s=0.05))
    tr = res.log
    assert (tr.t_end >= tr.t_start - 1e-12).all()
    # post-spray rows, sorted by start instant: slot indices
    # non-decreasing (cycles are sequential barriers)
    post = tr.select(tr.phase > 0)
    order = np.argsort(post.t_start, kind="stable")
    assert (np.diff(post.slot[order]) >= 0).all()


def test_slot_engine_stamps_slot_boundaries():
    cfg = SwarmConfig(n=16, chunks_per_update=16, s_max=4000, seed=3,
                      slot_seconds=2.0)
    res = simulate_round(cfg)
    tr = res.log
    assert np.allclose(tr.t_start, tr.slot * 2.0)
    assert np.allclose(tr.t_end, tr.slot * 2.0 + 2.0)
    m = res.metrics
    assert np.isclose(m.t_round_s, m.t_round * 2.0)
    assert np.isclose(m.t_warm_s, m.t_warm * 2.0)


# ---------------------------------------------------------------------------
# wall-clock metrics
# ---------------------------------------------------------------------------

def test_event_metrics_account_control_plane():
    net = NetConfig(tracker_rtt_s=0.2, tracker_solve_s=0.1,
                    spray_setup_s=0.5)
    cfg = SwarmConfig(n=20, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=0)
    res = simulate_round(cfg, time_engine="event", net=net)
    m = res.metrics
    # control time = spray setup + one (rtt + solve) per warm-up cycle
    assert np.isclose(m.control_s, 0.5 + m.t_warm * 0.3)
    assert m.t_spray_s > 0.5          # setup + spray transport
    assert m.t_warm_s >= m.t_spray_s + m.t_warm * 0.3
    assert m.t_round_s > m.t_warm_s   # BT tail exists
    assert 0.0 < m.warmup_share_s < 1.0
    assert res.tracker_log is not None
    assert res.tracker_log["n_cycles"] == m.t_warm + 1   # + spray setup


def test_event_latency_delays_first_byte():
    net = NetConfig(tracker_rtt_s=0.0, latency_lo_s=0.5,
                    latency_hi_s=0.5)
    cfg = SwarmConfig(n=16, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=1, enable_preround=False,
                      enable_timelag=False)
    res = simulate_round(cfg, time_engine="event", net=net)
    warm = res.log.warmup()
    # every transfer crosses two 0.5 s access legs
    assert (warm.t_start >= 1.0 - 1e-9).all()


def test_congestion_bound_holds_per_cycle():
    cfg = SwarmConfig(n=20, chunks_per_update=24, min_degree=5,
                      s_max=4000, seed=2)
    sim = RoundSimulator(cfg, time_engine="event",
                         net=NetConfig(tracker_rtt_s=0.0))
    res = sim.run()
    lbs, real = warmup_time_bounds(res.log, cfg.chunk_bytes,
                                   sim.up_bps, sim.down_bps)
    assert (real >= lbs - 1e-9).all()
    assert lbs.sum() > 0
    # realized transport stays within a small factor of the bound
    assert real.sum() <= 3.0 * lbs.sum()


def test_stage_time_lower_bound_simple():
    # 4 chunks of 10 B out of a 5 B/s uplink: >= 8 s regardless of fan.
    lb = stage_time_lower_bound(np.zeros(4, np.int64),
                                np.arange(1, 5), 10.0,
                                np.array([5.0, 9, 9, 9, 9]),
                                np.full(5, 100.0))
    assert np.isclose(lb, 8.0)


# ---------------------------------------------------------------------------
# session integration
# ---------------------------------------------------------------------------

def test_session_event_engine_wall_clock_across_churn():
    cfg = SwarmConfig(n=24, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=0)
    ses = SwarmSession(cfg, churn_rate=0.15,
                       time_engine="event",
                       net=NetConfig(tracker_rtt_s=0.05))
    ses.run(3)
    wc = ses.wall_clock()
    assert len(wc["t_round_s"]) == 3
    assert (wc["t_round_s"] > 0).all()
    assert (wc["t_warm_s"] > 0).all()
    assert ((wc["warmup_share_s"] > 0)
            & (wc["warmup_share_s"] < 1)).all()
    # the session trace carries the continuous-time columns
    tr = ses.trace()
    assert (tr.t_end >= tr.t_start).all()
    assert tr.t_start.max() > 0


def test_session_slot_engine_unchanged_with_rates():
    """Persisting raw rates must not perturb the evolving-overlay slot
    session (same draws, quantized identically)."""
    cfg = SwarmConfig(n=20, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=5)
    a = SwarmSession(cfg, churn_rate=0.1)
    b = SwarmSession(cfg, churn_rate=0.1, time_engine="slot")
    ra = a.run(3)
    rb = b.run(3)
    for x, y in zip(ra, rb):
        assert np.array_equal(x.active_ids, y.active_ids)
        assert np.array_equal(x.result.log.chunk, y.result.log.chunk)


# ---------------------------------------------------------------------------
# capacity clamp (satellite): warn when floor(rate * Δ / C) < 1 binds
# ---------------------------------------------------------------------------

def test_clamp_warns_when_it_binds():
    slow = LinkModel(up_lo=0.5 * MBPS, up_hi=0.6 * MBPS,
                     down_lo=50 * MBPS, down_hi=60 * MBPS)
    rng = np.random.default_rng(0)
    with pytest.warns(RuntimeWarning, match="clamp binds"):
        u, d = slow.sample_chunks_per_slot(8, 256 * 1024, 1.0, rng)
    assert (u == 1).all()          # clamped, not zero

    fast_rng = np.random.default_rng(0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        from repro.core.capacities import RESIDENTIAL
        RESIDENTIAL.sample_chunks_per_slot(8, 256 * 1024, 1.0, fast_rng)


def test_event_engine_rejects_zero_rate_links():
    """A zero-rate link could never deliver, but the scheduling layer
    would still mark its chunks delivered (t_end = inf): reject the
    injection up front instead."""
    cfg = SwarmConfig(n=8, chunks_per_update=4, min_degree=3,
                      s_max=1000, seed=0)
    up = np.ones(8, np.int64)
    with pytest.raises(ValueError, match="positive link rates"):
        RoundSimulator(cfg, up=up, down=up,
                       up_bps=np.zeros(8), down_bps=np.ones(8) * 1e6,
                       time_engine="event").run()


def test_event_engine_honest_on_clamped_links():
    """A sub-chunk/slot uplink: the slot engine inflates it to 1
    chunk/slot; the event engine transports its real bytes/s, so its
    transfers take > 1 slot of wall clock each."""
    slow = LinkModel(up_lo=0.5 * MBPS, up_hi=0.6 * MBPS,
                     down_lo=50 * MBPS, down_hi=60 * MBPS)
    cfg = SwarmConfig(n=12, chunks_per_update=8, min_degree=4,
                      s_max=4000, seed=0, enable_preround=False,
                      enable_timelag=False, enable_gating=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        res = simulate_round(cfg, link_model=slow, time_engine="event",
                             net=NetConfig(tracker_rtt_s=0.0))
    m = res.metrics
    # ~3.5 s per chunk of real uplink vs 1 chunk/slot pretended: wall
    # clock must stretch well past the slot count
    assert m.t_round_s > 1.5 * m.t_round * cfg.slot_seconds
