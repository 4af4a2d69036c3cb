"""Slot-engine equivalence: the jit engine against the loop reference.

Both engines must schedule *legally* — per-slot uplink/downlink
budgets, tau concurrency, adjacency, duplicate-free delivery, cover-set
gating (Eq. 1) — and the jit engine must match the loop reference in
*aggregate* throughput (t_warm, utilization) within tolerance, across
every scheduler mode and on both time engines.  Exact per-transfer equality across engines is not
required (each consumes randomness differently); legality plus
aggregate parity is the contract.  Within one engine, a fixed seed must
replay a byte-identical trace (the determinism twins below pin this for
the jit engine under ``SwarmSession`` on both time engines).
"""
from collections import Counter

import numpy as np
import pytest

from repro.core import SwarmConfig, SwarmSession, simulate_round
from repro.core import privacy
from repro.net import RESIDENTIAL_NET

MODES = ["random_fifo", "random_fastest_first", "greedy_fastest_first",
         "distributed", "flooding"]
CENTRALIZED = {"random_fifo", "random_fastest_first",
               "greedy_fastest_first"}
IMPLS = ["loop", "jit"]
# the chip cells' time engine: wall-clock event transport, residential
EVENT = dict(time_engine="event", net=RESIDENTIAL_NET)


def _cfg(mode, seed, impl, **kw):
    base = dict(n=16, chunks_per_update=24, s_max=5000, seed=seed,
                scheduler=mode, scheduler_impl=impl)
    base.update(kw)
    return SwarmConfig(**base)


def _replay_legality(cfg, res, check_tau):
    """Replay the log slot by slot against reconstructed inventories."""
    n, K = cfg.n, cfg.chunks_per_update
    log = res.log
    have = np.zeros((n, cfg.total_chunks), dtype=bool)
    for v in range(n):
        have[v, v * K:(v + 1) * K] = True
    # spray (phase 0) applies before warm-up slot 0
    key = log["slot"].astype(np.int64) * 4 + log["phase"]
    order = np.argsort(key, kind="stable")
    snd = log["sender"][order]
    rcv = log["receiver"][order]
    chk = log["chunk"][order]
    ph = log["phase"][order]
    key = key[order]
    for s in np.unique(key):
        sl = key == s
        # sender must hold every chunk it sends, receiver must miss it
        assert have[snd[sl], chk[sl]].all(), "sender missing chunk"
        assert not have[rcv[sl], chk[sl]].any(), "duplicate delivery"
        have[rcv[sl], chk[sl]] = True
        if (ph[sl] == 0).any():
            continue                    # spray is tracker-tunnelled
        assert (np.bincount(snd[sl], minlength=n) <= res.up).all(), \
            "uplink budget exceeded"
        assert (np.bincount(rcv[sl], minlength=n) <= res.down).all(), \
            "downlink budget exceeded"
        assert res.adj[snd[sl], rcv[sl]].all(), "non-adjacent transfer"
        if check_tau:
            pairs = set(zip(snd[sl].tolist(), rcv[sl].tolist()))
            per_sender = Counter(u for u, _ in pairs)
            assert max(per_sender.values(), default=0) \
                <= cfg.tau_concurrent, "tau concurrency exceeded"


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [1, 9])
def test_schedules_legally(mode, seed, impl):
    cfg = _cfg(mode, seed, impl)
    res = simulate_round(cfg)
    # tau applies to the tracker-assigned centralized modes only
    _replay_legality(cfg, res, check_tau=mode in CENTRALIZED)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("mode", MODES)
def test_satisfies_eq1(mode, impl):
    """Gating cap Eq. (1) holds on every warm-up send of either engine."""
    cfg = _cfg(mode, 3, impl)
    res = simulate_round(cfg)
    assert privacy.check_eq1(res.log, cfg.owner_throttle, cfg.k_gate)


@pytest.mark.parametrize("mode", MODES)
def test_jit_event_engine_schedules_legally(mode):
    """The chip cells' path — the jit engine on the event time engine
    over residential links — schedules legally and honours Eq. (1)."""
    cfg = _cfg(mode, 5, "jit")
    res = simulate_round(cfg, **EVENT)
    _replay_legality(cfg, res, check_tau=mode in CENTRALIZED)
    assert privacy.check_eq1(res.log, cfg.owner_throttle, cfg.k_gate)


@pytest.mark.parametrize("time_engine", ["slot", "event"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [1, 9])
def test_aggregate_parity(mode, seed, time_engine):
    """t_warm and warm-up utilization agree loop-vs-jit within
    tolerance (small swarms are noisy; bands are deliberately loose but
    tight enough to catch a broken engine, which degrades >2x)."""
    kw = EVENT if time_engine == "event" else {}
    rl = simulate_round(_cfg(mode, seed, "loop"), **kw).metrics
    rj = simulate_round(_cfg(mode, seed, "jit"), **kw).metrics
    assert not rj.failed_open and not rl.failed_open
    assert abs(rj.t_warm - rl.t_warm) <= max(3, 0.6 * rl.t_warm)
    assert abs(rj.warmup_utilization - rl.warmup_utilization) <= 0.2
    assert abs(rj.t_round - rl.t_round) <= max(5, 0.35 * rl.t_round)


# ---------------------------------------------------------------------------
# jit engine: determinism twins
# ---------------------------------------------------------------------------

def _session_traces(time_engine):
    cfg = SwarmConfig(n=20, chunks_per_update=16, min_degree=5,
                      s_max=4000, seed=7, scheduler_impl="jit")
    kw = {}
    if time_engine == "event":
        from repro.net import NetConfig
        kw = dict(time_engine="event",
                  net=NetConfig(tracker_rtt_s=0.05))
    ses = SwarmSession(cfg, churn_rate=0.1, **kw)
    ses.run(2)
    return ses.trace()


@pytest.mark.parametrize("time_engine", ["slot", "event"])
def test_jit_determinism_twin(time_engine):
    """A fixed seed replays a byte-identical multi-round TransferTrace
    under SwarmSession on both time engines: the jit engine draws
    exactly two host rng values per slot and keys its kernel noise from
    the second, so schedules cannot depend on device iteration order."""
    a = _session_traces(time_engine)
    b = _session_traces(time_engine)
    for key in ("slot", "sender", "receiver", "chunk", "owner",
                "b_size", "o_size", "phase"):
        assert np.array_equal(a[key], b[key]), (time_engine, key)
    assert np.array_equal(a.t_start, b.t_start)
    assert np.array_equal(a.t_end, b.t_end)


@pytest.mark.parametrize("time_engine", ["slot", "event"])
def test_jit_determinism_twin_recorded(time_engine):
    """Recording on the perf clock changes no byte of the jit engine's
    schedule: its spans and its round-count fetch only observe."""
    import time

    from repro import obs
    a = _session_traces(time_engine)
    with obs.recording(clock=time.perf_counter) as rec:
        b = _session_traces(time_engine)
    assert rec.metrics["matching.rounds"]["value"] > 0
    for key in ("slot", "sender", "receiver", "chunk", "owner",
                "b_size", "o_size", "phase"):
        assert a[key].tobytes() == b[key].tobytes(), (time_engine, key)
    assert a.t_start.tobytes() == b.t_start.tobytes()
    assert a.t_end.tobytes() == b.t_end.tobytes()


def test_aggregate_parity_paper_scale_warm():
    """At n=64 the engines' warm-up phases track each other closely."""
    kw = dict(n=64, chunks_per_update=32, s_max=20000)
    rl = simulate_round(
        SwarmConfig(seed=0, scheduler_impl="loop", **kw),
        bt_mode="fluid").metrics
    rj = simulate_round(
        SwarmConfig(seed=0, scheduler_impl="jit", **kw),
        bt_mode="fluid").metrics
    assert abs(rj.t_warm - rl.t_warm) <= max(2, 0.25 * rl.t_warm)
    # the jit engine's parallel grant rounds pack slots a little better
    # than the sequential loop engine; allow it to win, bounded
    assert rj.warmup_utilization >= rl.warmup_utilization - 0.12
    assert rj.warmup_utilization <= rl.warmup_utilization + 0.16


def test_nonowner_first_preference():
    """Non-owner-first lowers the owner-sent fraction for the jit
    engine, mirroring the loop-engine property test."""
    def owner_frac(flag):
        cfg = SwarmConfig(n=16, chunks_per_update=24, s_max=4000, seed=8,
                          enable_nonowner_first=flag,
                          scheduler_impl="jit")
        log = simulate_round(cfg).log
        warm = log["phase"] == 1
        return float((log["sender"][warm] == log["owner"][warm]).mean())

    assert owner_frac(True) <= owner_frac(False) + 1e-9


def test_respects_maxflow_bound():
    """Per-slot jit-engine throughput never exceeds the offline max-flow
    stage bound (legality implies this; checked end-to-end)."""
    cfg = SwarmConfig(n=14, chunks_per_update=20, s_max=3000, seed=5,
                      scheduler_impl="jit")
    res = simulate_round(cfg, collect_maxflow=True)
    sent = res.warmup_sent_per_slot[:len(res.maxflow_ub)]
    assert (sent <= res.maxflow_ub + 1e-9).all()


def test_handles_ablations_and_dropouts():
    """Gating/spray/lag toggles and dropouts run clean under jit."""
    for pr in (False, True):
        for gate in (False, True):
            cfg = SwarmConfig(n=12, chunks_per_update=16, s_max=4000,
                              seed=2, enable_preround=pr,
                              enable_timelag=not pr, enable_gating=gate,
                              scheduler_impl="jit")
            res = simulate_round(cfg)
            assert not res.metrics.failed_open
    cfg = SwarmConfig(n=12, chunks_per_update=16, s_max=4000, seed=4,
                      scheduler_impl="jit")
    res = simulate_round(cfg, dropouts={2: [0, 1]})
    assert not res.active[0] and not res.active[1]


def test_loop_impl_still_selectable():
    """scheduler_impl='loop' routes to the reference engine; "jit" is
    the default, and any other name — the retired "batched" engine
    included — is refused at the first schedule."""
    assert SwarmConfig().scheduler_impl == "jit"
    cfg = SwarmConfig(n=12, chunks_per_update=16, s_max=3000, seed=0,
                      scheduler_impl="loop")
    res = simulate_round(cfg)
    assert not res.metrics.failed_open
    for impl in ("nope", "batched"):
        with pytest.raises(ValueError, match="'loop' or 'jit'"):
            simulate_round(SwarmConfig(n=8, chunks_per_update=8,
                                       s_max=50, min_degree=4,
                                       scheduler_impl=impl))
