"""repro.obs: recorder semantics, profiler annotations, the program's
wall spans and round counter, JSONL schema golden, Perfetto export,
report-vs-metrics reproduction, the tracker control-plane audit, and
the zero-overhead-when-disabled bound."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core import SwarmConfig, SwarmSession
from repro.core import jit_engine
from repro.core.simulator import RoundSimulator
from repro.net import NetConfig
from repro.net import fairshare
from repro.obs import recorder as recorder_mod

REPO = Path(__file__).resolve().parent.parent

CFG = SwarmConfig(n=16, chunks_per_update=8, min_degree=4,
                  s_max=3000, seed=11)
NET = NetConfig(tracker_rtt_s=0.1, latency_lo_s=0.005,
                latency_hi_s=0.030)


def _record_round(**kw):
    """One n=16 event-engine round under a fresh recorder."""
    with obs.recording(meta={"test": "obs"}) as rec:
        res = RoundSimulator(CFG, time_engine="event", net=NET,
                             **kw).run()
    return rec, res


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------

def test_null_recorder_is_default_and_inert():
    rec = obs.get()
    assert isinstance(rec, obs.NullRecorder) and rec.enabled is False
    # every hook is a no-op returning nothing to clean up
    rec.event("x", t=1.0)
    rec.counter("c")
    rec.gauge("g", 2.0)
    rec.hist("h", [1, 2])
    rec.flows("bt", [0], [1], [0.0], [1.0])
    with rec.span("s") as sp:
        sp.note(k=1)


def test_recording_restores_previous_recorder_on_exception():
    before = obs.get()
    with pytest.raises(RuntimeError):
        with obs.recording() as rec:
            assert obs.get() is rec
            raise RuntimeError("boom")
    assert obs.get() is before


def test_span_measures_injected_clock():
    ticks = iter([10.0, 13.5])
    rec = obs.Recorder(clock=lambda: next(ticks))
    with rec.span("work", round=2):
        pass
    (row,) = rec.rows
    assert row["name"] == "work" and row["round"] == 2
    assert row["wall_s"] == pytest.approx(3.5)


def test_time_base_shifts_simulated_instants_not_wall():
    rec = obs.Recorder()
    rec.time_base = 100.0
    rec.event("e", t=1.0)
    rec.span_at("p", 2.0, 3.0, wall_s=0.25)
    rec.flows("bt", [0], [1], [0.5], [0.75])
    ev, sp, fl = rec.rows
    assert ev["t"] == 101.0
    assert (sp["t0"], sp["t1"]) == (102.0, 103.0)
    assert sp["wall_s"] == 0.25          # wall durations are not shifted
    assert fl["t_start"][0] == 100.5 and fl["t_end"][0] == 100.75


def test_set_ctx_merges_and_removes():
    rec = obs.Recorder()
    rec.set_ctx(round=1)
    rec.event("a")
    rec.set_ctx(round=None)
    rec.event("b")
    a, b = rec.rows
    assert a["round"] == 1 and "round" not in b


def test_metrics_registry_counter_gauge_hist():
    rec = obs.Recorder()
    rec.counter("c")
    rec.counter("c", 2.5)
    rec.gauge("g", 1.0)
    rec.gauge("g", 7.0)
    rec.hist("h", 3)
    rec.hist("h", np.array([1.0, 2.0]))
    assert rec.metrics["c"] == {"metric": "counter", "value": 3.5}
    assert rec.metrics["g"] == {"metric": "gauge", "value": 7.0}
    assert rec.metrics["h"]["values"] == [3.0, 1.0, 2.0]


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, exc[0]))
        return False


def test_span_opens_and_closes_a_profiler_annotation(monkeypatch):
    log = []
    _FakeAnnotation.log = log
    monkeypatch.setattr(recorder_mod, "_annotation_cls",
                        lambda: _FakeAnnotation)
    ticks = iter([2.0, 2.75])

    def clock():
        t = next(ticks)
        log.append(("clock", t))
        return t

    rec = obs.Recorder(clock=clock)
    with rec.span("matching.prep"):
        log.append(("body",))
    assert log == [("enter", "matching.prep"), ("clock", 2.0), ("body",),
                   ("clock", 2.75), ("exit", "matching.prep", None)]
    (row,) = rec.rows
    assert row["wall_s"] == pytest.approx(0.75)
    # an exception in the span still closes the annotation and the row
    with pytest.raises(KeyError):
        with obs.Recorder().span("transport.kernel"):
            raise KeyError("x")
    assert log[-1] == ("exit", "transport.kernel", KeyError)


def test_null_recorder_span_opens_no_annotation(monkeypatch):
    def refuse():
        raise AssertionError("the null recorder must not annotate")
    monkeypatch.setattr(recorder_mod, "_annotation_cls", refuse)
    rec = obs.NullRecorder()
    assert rec.span("a") is rec.span("b")       # one shared no-op handle
    with rec.span("matching.kernel") as sp:
        sp.note(rounds=3)


def test_span_lands_in_a_profiler_trace(tmp_path):
    import glob

    import jax
    rec = obs.Recorder()
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("driver.apply"):
            pass
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes for ln in plane.lines
             for ev in ln.events}
    assert "driver.apply" in names


def test_recording_restores_null_recorder_when_block_raises():
    """A perf clock installed by recording() cannot outlive its block:
    the null recorder is back even when the block raises."""
    with pytest.raises(ValueError):
        with obs.recording(clock=time.perf_counter) as rec:
            assert obs.get() is rec and rec.clock is time.perf_counter
            raise ValueError("bench blew up")
    after = obs.get()
    assert isinstance(after, obs.NullRecorder) and not after.enabled
    assert not hasattr(after, "clock")


# ---------------------------------------------------------------------------
# the program's wall spans and counters
# ---------------------------------------------------------------------------

JIT_CFG = SwarmConfig(n=16, chunks_per_update=8, min_degree=4,
                      s_max=3000, seed=11, lag_slots=1,
                      scheduler="greedy_fastest_first",
                      scheduler_impl="jit")


def _spans(rec, prefix=""):
    return [r for r in rec.rows
            if r["kind"] == "span" and r["name"].startswith(prefix)]


def test_jit_schedule_spans_and_rounds_counter(monkeypatch):
    """One schedule call: one row each of the four matching phases, in
    order, and a matching.rounds increment equal to the kernel's own
    round count, between 1 and r_max; the schedule is the one an
    unrecorded call on the same state gives."""
    seen = []
    orig = jit_engine._compiled

    def spy(*static):
        kern = orig(*static)

        def call(*operands):
            out = kern(*operands)
            seen.append(int(out[2]))
            return out
        return call
    monkeypatch.setattr(jit_engine, "_compiled", spy)

    plain = jit_engine.schedule_centralized_jit(
        RoundSimulator(JIT_CFG).state, "greedy_fastest_first")
    with obs.recording() as rec:
        out = jit_engine.schedule_centralized_jit(
            RoundSimulator(JIT_CFG).state, "greedy_fastest_first")
    assert len(out[0]) > 0
    for a, b in zip(plain, out):
        assert a.tobytes() == b.tobytes()
    assert [r["name"] for r in _spans(rec, "matching.")] == [
        "matching.prep", "matching.sync", "matching.kernel",
        "matching.decode"]
    kern = _spans(rec, "matching.kernel")[0]
    assert len(seen) == 2 and seen[0] == seen[1] == kern["rounds"]
    assert 1 <= kern["rounds"] <= kern["r_max"]
    assert rec.metrics["matching.rounds"]["value"] == kern["rounds"]
    assert kern["m_pad"] >= 32 and kern["t_cap"] >= 1


def test_event_cycle_spans():
    """An event-engine warm-up on the jit engine: every cycle applies
    its transfers inside driver.apply, every transport runs its kernel
    between a prep and a stamp span, and round construction is
    driver.build."""
    with obs.recording() as rec:
        res = RoundSimulator(JIT_CFG, time_engine="event",
                             net=NET).run(warmup_only=True)
    names = [r["name"] for r in _spans(rec)]
    assert names.count("driver.apply") == res.metrics.t_warm
    assert names.count("driver.build") == 2      # construction, spray
    n_kern = names.count("transport.kernel")
    assert n_kern > 0 and names.count("transport.stamp") == n_kern
    # prep: the engine's pair grouping and the transport's own grids
    assert names.count("transport.prep") == 2 * n_kern
    assert names.count("matching.kernel") == \
        names.count("matching.decode") > 0
    assert rec.metrics["matching.rounds"]["value"] == sum(
        r["rounds"] for r in _spans(rec, "matching.kernel"))


def test_round_spans_carry_wall_time_under_a_perf_clock():
    with obs.recording(clock=time.perf_counter) as rec:
        RoundSimulator(CFG, time_engine="event", net=NET).run()
    rows = {r["name"]: r for r in _spans(rec, "round.")}
    assert set(rows) == {"round.spray", "round.warmup", "round.bt",
                         "round.emit", "round.total"}
    for name, r in rows.items():
        assert r["wall_s"] > 0.0, name
    parts = sum(rows[f"round.{p}"]["wall_s"]
                for p in ("spray", "warmup", "bt", "emit"))
    assert parts == pytest.approx(rows["round.total"]["wall_s"])
    # under the default zero clock the same rows read zero
    rec0, _ = _record_round()
    assert all(r["wall_s"] == 0.0 for r in _spans(rec0, "round."))


def _program_name(fn, specs, x64: bool) -> str:
    import re

    import jax
    with jax.enable_x64(x64):
        text = fn.lower(*[jax.ShapeDtypeStruct(s, d)
                          for s, d in specs]).as_text()
    return re.search(r"module @(\w+)", text).group(1)


@pytest.mark.parametrize("program", ["matching", "transport", "maxmin"])
def test_device_programs_have_distinct_names(program):
    import jax.numpy as jnp
    i32, i64, f64 = jnp.int32, jnp.int64, jnp.float64
    if program == "matching":
        n, m_pad, d = 16, 128, 8
        fn = jit_engine._compiled(2, True, False, 2, 16)
        specs = [((n, 4), jnp.uint32), ((m_pad,), i32), ((m_pad,), i32),
                 ((m_pad,), jnp.bool_), ((), i32), ((n,), jnp.bool_),
                 ((n, d), i32), ((n,), i32), ((n,), i32), ((), i32),
                 ((), i32), ((), i32)]
        want = "jit_matching_rounds"
    elif program == "transport":
        n, f, m = 16, 8, 32
        fn = fairshare._transport_compiled(n, f, m, 16)
        specs = [((f,), i64), ((f,), i64), ((f,), f64), ((f,), f64),
                 ((), f64), ((n,), f64), ((n,), f64), ((), f64),
                 ((m,), i64), ((m,), i64)]
        want = "jit_fairshare_transport"
    else:
        n, f = 16, 8
        fn = fairshare._maxmin_compiled(n, f, 16)
        specs = [((f,), i64), ((f,), i64), ((n,), f64), ((n,), f64),
                 ((f,), jnp.bool_)]
        want = "jit_fairshare_maxmin"
    # the fair-share kernels run in float64, the matching in 32 bits
    assert _program_name(fn, specs, x64=program != "matching") == want


# ---------------------------------------------------------------------------
# JSONL schema golden (n=16 event-engine round)
# ---------------------------------------------------------------------------

def test_jsonl_golden_round_trip_and_schema(tmp_path):
    rec, res = _record_round()
    path = tmp_path / "round.jsonl"
    n = obs.write_jsonl(rec, path)
    rows = obs.read_jsonl(path)
    assert len(rows) == n
    assert obs.validate_rows(rows) == []

    # Structural golden: header first, then every phase span exactly
    # once, tracker events matching the engine's control log, and flow
    # batches on all three foreground tracks.
    assert rows[0]["kind"] == "header"
    assert rows[0]["version"] == 1 and rows[0]["meta"] == {"test": "obs"}
    spans = [r["name"] for r in rows if r["kind"] == "span"]
    assert sorted(n for n in spans if n.startswith("round.")) == [
        "round.bt", "round.emit", "round.spray", "round.total",
        "round.warmup"]
    # the program's wall spans: round driver, matching (the default jit
    # engine) and transport phases
    assert {n for n in spans if not n.startswith("round.")} == {
        "driver.build", "driver.apply", "matching.prep", "matching.sync",
        "matching.kernel", "matching.decode", "transport.prep",
        "transport.kernel", "transport.stamp"}
    cycles = [r for r in rows if r.get("name") == "tracker.cycle"]
    setups = [r for r in rows if r.get("name") == "tracker.spray_setup"]
    # the tracker ledger counts spray setup as a cycle entry (slot=-1)
    assert len(cycles) + len(setups) == res.tracker_log["n_cycles"]
    assert len(setups) == 1
    tracks = {r["track"] for r in rows if r["kind"] == "flows"}
    assert {"spray", "warmup", "bt"} <= tracks
    # seq strictly increasing over the recorded (non-header/metric) rows
    seqs = [r["seq"] for r in rows if "seq" in r]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # metric registry present and trailing
    kinds = [r["kind"] for r in rows]
    first_metric = kinds.index("metric")
    assert set(kinds[first_metric:]) == {"metric"}
    names = {r["name"] for r in rows if r["kind"] == "metric"}
    assert {"net.flows_solved", "net.chunks_moved", "net.bytes_moved",
            "tracker.control_s", "fairshare.transport_calls",
            "sched.warmup_grants_per_slot"} <= names


def test_chunk_accounting_matches_trace():
    rec, res = _record_round()
    moved = rec.metrics["net.chunks_moved"]["value"]
    assert moved == len(res.log)
    assert rec.metrics["net.bytes_moved"]["value"] == \
        moved * CFG.chunk_bytes


def test_tracker_control_plane_audit():
    """The recorded control-plane seconds equal RoundMetrics.control_s
    EXACTLY: the counter accumulates the same float sequence the
    tracker's own control_s does."""
    rec, res = _record_round()
    assert rec.metrics["tracker.control_s"]["value"] == \
        res.metrics.control_s
    # ... and the per-cycle events carry the same total
    costs = sum(r.get("cost_s", 0.0) for r in rec.rows
                if r.get("name", "").startswith("tracker."))
    assert costs == pytest.approx(res.metrics.control_s)


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def test_perfetto_trace_loads_and_covers_all_tracks(tmp_path):
    rec, res = _record_round()
    out = tmp_path / "trace.json"
    n = obs.write_perfetto(rec, out)
    trace = json.loads(out.read_text())      # valid chrome-tracing JSON
    ev = trace["traceEvents"]
    assert len(ev) == n and trace["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in ev}
    assert {"X", "M"} <= phases
    # phase spans on pid 0, peer flows on pid 1, tracker on pid 2
    assert any(e["pid"] == 0 and e["ph"] == "X"
               and e["name"] == "round.warmup" for e in ev)
    flow_cats = {e["cat"] for e in ev
                 if e["pid"] == 1 and e["ph"] == "X"}
    assert {"spray", "warmup", "bt"} <= flow_cats
    assert any(e["pid"] == 2 and e["ph"] == "X" for e in ev)
    # every ts/dur is finite and non-negative
    for e in ev:
        if e["ph"] == "X":
            assert np.isfinite(e["ts"]) and e["dur"] >= 0.0
    names = {e["args"]["name"] for e in ev if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert names == {"round phases", "peers (sender tracks)",
                     "tracker control plane"}


# ---------------------------------------------------------------------------
# report: metrics reproduced from the recording alone
# ---------------------------------------------------------------------------

def test_report_reproduces_round_metrics():
    rec, res = _record_round()
    s = obs.summarize(obs.to_jsonl_rows(rec))
    (r0,) = s["rounds"].values()
    m = res.metrics
    assert r0["t_warm_s"] == pytest.approx(m.t_warm_s, abs=1e-9)
    assert r0["t_round_s"] == pytest.approx(m.t_round_s, abs=1e-9)
    assert r0["warmup_share_s"] == pytest.approx(m.warmup_share_s,
                                                 abs=1e-9)
    assert s["totals"]["control_s"] == m.control_s
    assert s["slowest_peers"], "flow batches must yield peer activity"
    text = obs.format_report(s)
    assert "warmup_share" in text and "slowest peers" in text


def test_session_recording_spans_rounds_on_one_wall_clock():
    """Multi-round session: per-round rows carry the round index and
    land at the session offsets; the report reproduces every round's
    wall-clock metrics."""
    with obs.recording() as rec:
        ses = SwarmSession(CFG, churn_rate=0.15, time_engine="event",
                           net=NET)
        ses.run(3)
    rows = obs.to_jsonl_rows(rec)
    assert obs.validate_rows(rows) == []
    starts = [r for r in rows if r.get("name") == "session.round_start"]
    ends = [r for r in rows if r.get("name") == "session.round_end"]
    assert [r["round"] for r in starts] == [0, 1, 2]
    # round r's rows start at the session offset of round r
    assert [r["t"] for r in starts] == pytest.approx(ses.offsets[:3])
    assert [r["t"] for r in ends] == pytest.approx(ses.offsets[1:])
    s = obs.summarize(rows)
    wc = ses.wall_clock()
    for r in range(3):
        assert s["rounds"][r]["t_warm_s"] == pytest.approx(
            wc["t_warm_s"][r], abs=1e-9)
        assert s["rounds"][r]["t_round_s"] == pytest.approx(
            wc["t_round_s"][r], abs=1e-9)
    assert s["counters"]["session.rounds"] == 3.0


def test_async_experiment_records_merges_and_staleness():
    """The async runner's merge instants, staleness histogram, and drop
    counter in the recording mirror AsyncResult exactly."""
    from repro.fl.asyncfl import AsyncConfig, run_async_experiment
    from repro.fl.client import LocalSpec
    from repro.fl.runner import FLConfig
    tiny = FLConfig(dataset="synth-mnist", n_clients=6, rounds=3,
                    n_train=600, n_test=200, min_degree=3, seed=3,
                    local=LocalSpec(epochs=1, batch_size=32, lr=0.05))
    acfg = AsyncConfig(buffer_k=2, max_staleness=2, overlap=True,
                       round_slots=2, time_engine="event", net=NET,
                       evolve_overlay=True)
    with obs.recording() as rec:
        out = run_async_experiment(tiny, acfg)
    merges = [r for r in rec.rows if r.get("name") == "async.merge"]
    assert [e["merged"] for e in merges] == \
        [m for m in out.merged if m > 0]
    hist = rec.metrics.get("async.staleness", {"values": []})["values"]
    assert sorted(int(v) for v in hist) == sorted(
        s for s, c in out.staleness_hist.items() for _ in range(c))
    assert rec.metrics.get("async.dropped",
                           {"value": 0.0})["value"] == out.dropped


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_report_validate_perfetto(tmp_path):
    rec, _res = _record_round()
    path = tmp_path / "round.jsonl"
    obs.write_jsonl(rec, path)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))

    def run(*argv):
        return subprocess.run(
            [sys.executable, "-m", "repro.obs", *argv], env=env,
            capture_output=True, text=True, timeout=120)

    p = run("validate", str(path))
    assert p.returncode == 0 and "0 violation(s)" in p.stdout
    p = run("report", str(path))
    assert p.returncode == 0 and "warmup_share" in p.stdout
    p = run("report", str(path), "--json")
    assert "rounds" in json.loads(p.stdout)
    out = tmp_path / "trace.json"
    p = run("perfetto", str(path), str(out))
    assert p.returncode == 0
    assert json.loads(out.read_text())["traceEvents"]
    # validate flags a corrupt recording
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "event", "name": 3}\n')
    p = run("validate", str(bad))
    assert p.returncode == 1 and "violation" in p.stdout


# ---------------------------------------------------------------------------
# the zero-overhead-when-disabled bound
# ---------------------------------------------------------------------------

def test_disabled_recorder_overhead_under_two_percent():
    """An n=100 warm-up with the NullRecorder installed must not pay
    more than 2% for the instrumentation hooks.  The disabled path per
    site is one obs.get() + one ``enabled`` attribute check; time a
    generous multiple of the sites the run executes and compare to the
    measured warm-up wall time."""
    cfg = SwarmConfig(n=100, chunks_per_update=16, min_degree=6,
                      s_max=3000, seed=5)
    sim = RoundSimulator(cfg, time_engine="event", net=NET)
    t0 = time.perf_counter()
    res = sim.run(warmup_only=True)
    wall = time.perf_counter() - t0
    assert res.metrics.t_warm_s > 0

    # Generous upper bound on disabled-path hook executions: every
    # warm-up slot touches a handful of sites; 20x the slot budget
    # covers the per-cycle engine/tracker/fairshare hooks too.
    n_sites = 20 * int(res.metrics.t_warm)
    rec = obs.get()
    assert rec.enabled is False
    t0 = time.perf_counter()
    for _ in range(max(n_sites, 1000)):
        r = obs.get()
        if r.enabled:
            r.counter("x")          # never taken on the disabled path
    hook_s = time.perf_counter() - t0
    assert hook_s < 0.02 * wall, (
        f"disabled-recorder hooks cost {hook_s:.6f}s against a "
        f"{wall:.4f}s warm-up ({hook_s / wall:.2%})")
