"""Dynamic determinism twin for the rng-discipline family (ISSUE 6):
the static rules promise one threaded rng stream; this pins the
observable consequence — the same seed replays the same session
byte-for-byte, on BOTH time engines, including the continuous-time
``t_start``/``t_end`` stamps the event engine adds."""
import numpy as np
import pytest

from repro.core import SwarmConfig, SwarmSession
from repro.core.overlay import random_overlay
from repro.net import NetConfig

CFG = SwarmConfig(n=16, chunks_per_update=8, min_degree=4,
                  s_max=3000, seed=11)
NET = NetConfig(tracker_rtt_s=0.1, latency_lo_s=0.005,
                latency_hi_s=0.030)


def _session_trace(engine: str):
    ses = SwarmSession(CFG, churn_rate=0.15, time_engine=engine,
                       net=NET if engine == "event" else None)
    ses.run(3)
    return ses.trace()


@pytest.mark.parametrize("engine", ["slot", "event"])
def test_session_twin_trace_byte_identical(engine):
    a = _session_trace(engine)
    b = _session_trace(engine)
    assert len(a) == len(b) and len(a) > 0
    for k in a.keys():
        col_a, col_b = getattr(a, k), getattr(b, k)
        assert col_a.dtype == col_b.dtype, k
        assert col_a.tobytes() == col_b.tobytes(), (
            f"column {k!r} differs between twin runs at seed "
            f"{CFG.seed} on the {engine!r} engine")


def test_event_twin_time_columns_are_real_and_identical():
    a = _session_trace("event")
    assert (a.t_end >= a.t_start).all() and a.t_end.max() > 0
    b = _session_trace("event")
    assert a.t_start.tobytes() == b.t_start.tobytes()
    assert a.t_end.tobytes() == b.t_end.tobytes()


@pytest.mark.parametrize("engine", ["slot", "event"])
def test_async_twin_trace_byte_identical(engine):
    """Async extension: a deadline-cut session whose tail delivers
    LATE must still replay byte-for-byte, including the per-update
    ``generation``/``staleness`` columns the async path stamps."""
    def once():
        ses = SwarmSession(CFG, time_engine=engine,
                           net=NET if engine == "event" else None,
                           evolve_overlay=True)
        ses.run(3, quorum_k=CFG.n, tail_mode="drain", bt_budget=3)
        return ses.trace(include_late=True)
    a, b = once(), once()
    assert len(a) == len(b) and len(a) > 0
    assert (a.staleness > 0).any(), "twin must exercise the async path"
    for k in a.keys():
        col_a, col_b = getattr(a, k), getattr(b, k)
        assert col_a.dtype == col_b.dtype, k
        assert col_a.tobytes() == col_b.tobytes(), (
            f"column {k!r} differs between async twin runs at seed "
            f"{CFG.seed} on the {engine!r} engine")


@pytest.mark.parametrize("engine", ["slot", "event"])
def test_telemetry_twin_trace_byte_identical(engine):
    """ISSUE 10: telemetry is determinism-inert — recording a session
    perturbs no byte of its trace, on either engine (the recorder only
    observes: no rng draws, no feedback into simulated time)."""
    from repro import obs
    a = _session_trace(engine)
    with obs.recording() as rec:
        b = _session_trace(engine)
    assert rec.rows, "the recorded twin must actually record"
    assert obs.get().enabled is False, "recorder leaked past the scope"
    assert len(a) == len(b) and len(a) > 0
    for k in a.keys():
        col_a, col_b = getattr(a, k), getattr(b, k)
        assert col_a.dtype == col_b.dtype, k
        assert col_a.tobytes() == col_b.tobytes(), (
            f"column {k!r} differs with telemetry enabled at seed "
            f"{CFG.seed} on the {engine!r} engine")


@pytest.mark.parametrize("engine", ["slot", "event"])
def test_telemetry_twin_async_carry(engine):
    """Telemetry on/off parity through the async tail path too (quorum
    cut, boundary drain, staleness columns)."""
    from repro import obs

    def once(record: bool):
        ses = SwarmSession(CFG, time_engine=engine,
                           net=NET if engine == "event" else None,
                           evolve_overlay=True)
        if record:
            with obs.recording():
                ses.run(3, quorum_k=CFG.n, tail_mode="drain",
                        bt_budget=3)
        else:
            ses.run(3, quorum_k=CFG.n, tail_mode="drain", bt_budget=3)
        return ses.trace(include_late=True)
    a, b = once(False), once(True)
    assert len(a) == len(b) and (a.staleness > 0).any()
    for k in a.keys():
        assert getattr(a, k).tobytes() == getattr(b, k).tobytes(), (
            f"column {k!r} differs with telemetry enabled on the "
            f"{engine!r} engine (async drain path)")


def test_random_overlay_requires_threaded_rng():
    """Regression pin for the RNG004 fix: the old constant-seed
    fallback handed every un-threaded caller the SAME overlay."""
    with pytest.raises(ValueError, match="threaded np.random.Generator"):
        random_overlay(8, 3)
    rng = np.random.default_rng(3)
    adj = random_overlay(8, 3, rng=rng)
    assert adj.shape == (8, 8) and (adj.sum(1) >= 3).all()


@pytest.mark.parametrize("mode", ["random_fifo", "greedy_fastest_first"])
def test_jit_engine_schedule_ignores_threefry_flag(mode):
    """The jit engine's noise is a pure function of the per-slot seed:
    flipping jax's global ``jax_threefry_partitionable`` flag (whose
    value changes every ``jax.random`` draw) leaves the schedule
    byte-identical."""
    import jax

    from repro.core import simulate_round
    cfg = SwarmConfig(n=20, chunks_per_update=16, s_max=3000, seed=5,
                      scheduler=mode, scheduler_impl="jit")
    logs = []
    prev = jax.config.jax_threefry_partitionable
    try:
        for flag in (True, False):
            jax.config.update("jax_threefry_partitionable", flag)
            logs.append(simulate_round(cfg).log)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
    for k in ("slot", "sender", "receiver", "chunk"):
        a, b = (np.ascontiguousarray(log[k]) for log in logs)
        assert a.tobytes() == b.tobytes(), k
