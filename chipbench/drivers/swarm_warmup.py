"""Driver of the swarm warm-up cells.

The window drives the program's round simulator,
``RoundSimulator(...).run(warmup_only=True)``, on the event time engine
with the jitted slot engine, exactly as a user of the simulator or the
tracker runs it.  The peers are the benchmark's own
(``references/swarm.py::instance``): the overlay and link budgets go in
through the simulator's injection arguments, as a session's persistent
population does, and the lags and access latencies replace the ones the
program drew.  Two wrappers from this file sit on the two calls the
benchmark measures, and change nothing they pass through:

* a delegating ``SchedulerPolicy`` around the configured policy times
  each directive cycle's assignment solve (``bench.solve``), which ends
  in the blocking fetch of the grants;
* a subclass of the event engine times each cycle's fair-share
  transport (``bench.transport``).

Set-up builds the round from the seed and runs its warm-up once -- to
its end, or for the configuration's ``round_cycles`` where a run cannot
hold the whole of it -- so that every program the window will call is
compiled.  The window then runs the same round from its start.  The
schedule is a pure function of the seed, so the window meets only
shapes the rehearsal met: when the round's warm-up ends, or it reaches
``round_cycles``, the round is built again and starts over inside the
window, its construction and spray counted.

The check replays every cycle of the window against
``references/swarm.py`` on the benchmark's own inputs: the grants'
legality, and the per-transfer stamps of a sample of cycles against the
NumPy transport in float64.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from chipbench.harness import span
from chipbench.references import swarm as ref


class WindowClosed(Exception):
    """The window's time is up: raised at the start of a cycle."""


class SegmentEnd(Exception):
    """The round reached ``round_cycles``: build it again."""


def round_seed(seed: int) -> int:
    """A 31-bit round seed from the benchmark seed (any size)."""
    return int(np.random.SeedSequence(int(seed) & (2 ** 64 - 1))
               .generate_state(1)[0] & 0x7FFFFFFF)


class _Segment:
    """One run of the round from its start: the simulator, and every
    cycle's grants and transport as they were produced."""

    def __init__(self, sim):
        self.sim = sim
        self.cycles = []        # (snd, rcv, chk), one per cycle
        self.moves = []         # (t_before, snd, rcv, ts, te)


class Driver:
    def __init__(self, cell, seed: int, meter, trace: bool):
        from repro.core import SwarmConfig
        from repro.core.policy import SchedulerPolicy, get_policy
        from repro.net import NetConfig
        import repro.net as rnet

        self.cell = cell
        self.seed = int(seed)
        self.meter = meter
        self.trace = trace
        conf = cell.config
        dep = dict(conf["deployment"])
        self.cap_cycles = conf.get("round_cycles") or float("inf")
        self.check_cycles = int(cell.traffic["check_cycles"])
        self.limits = conf["limits"]
        self.inst = ref.instance(conf, self.seed)
        self.thr = ref.thresholds(dep)
        self.net = NetConfig(**conf["net"])
        self._rnet = rnet
        self._engine_cls = rnet.EventEngine
        self.segment: _Segment | None = None
        self.segments: list[_Segment] = []
        self.solve_s: list[float] = []
        self.transport_s: list[float] = []
        self.deadline = float("inf")
        drv = self

        class TimedPolicy(SchedulerPolicy):
            name = "bench_timed"
            visibility = "full"
            phases = ("warmup",)

            def __init__(self, inner):
                self.inner = inner
                self.visibility = inner.visibility

            def reset(self, cfg):
                self.inner.reset(cfg)

            def schedule(self, view):
                seg = drv.segment
                if time.perf_counter() >= drv.deadline:
                    raise WindowClosed
                if len(seg.cycles) >= drv.cap_cycles:
                    raise SegmentEnd
                t0 = time.perf_counter()
                with span("bench.solve", drv.trace):
                    out = self.inner.schedule(view)
                drv.solve_s.append(time.perf_counter() - t0)
                seg.cycles.append(tuple(out))
                return out

        class TimedEngine(self._engine_cls):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                self.lat = drv.inst["lat"].copy()

            def warmup_cycle(self, slot, snd, rcv, chk):
                t_before = self.t
                t0 = time.perf_counter()
                with span("bench.transport", drv.trace):
                    ts, te = super().warmup_cycle(slot, snd, rcv, chk)
                drv.transport_s.append(time.perf_counter() - t0)
                drv.segment.moves.append((t_before, snd, rcv, ts, te))
                return ts, te

        self._timed_engine = TimedEngine
        inner = get_policy(dep.pop("scheduler"))
        self.policy = TimedPolicy(inner)
        self.swarm_cfg = SwarmConfig(**dep, scheduler=self.policy,
                                     seed=round_seed(self.seed))

    # ------------------------------------------------------------------
    def _run_segment(self) -> _Segment:
        """Build the round on the benchmark's peers and run its warm-up
        until it ends, reaches ``round_cycles``, or the window closes
        (re-raised)."""
        from repro.core.simulator import RoundSimulator
        inst = self.inst
        seg = self.segment = _Segment(None)
        seg.sim = RoundSimulator(
            self.swarm_cfg, time_engine="event", net=self.net,
            overlay=inst["adj"].copy(), up=inst["up"], down=inst["down"],
            up_bps=inst["up_bps"], down_bps=inst["down_bps"])
        seg.sim.state.lag = inst["lag"].copy()
        self.segments.append(seg)
        try:
            seg.sim.run(warmup_only=True)
        except SegmentEnd:
            pass
        return seg

    def setup(self) -> None:
        self._rnet.EventEngine = self._timed_engine
        try:
            self._run_segment()
        finally:
            self._rnet.EventEngine = self._engine_cls
        self.segments.clear()
        self.solve_s.clear()
        self.transport_s.clear()

    def window(self, seconds: float) -> dict:
        from repro import obs
        self._rnet.EventEngine = self._timed_engine
        rec = obs.Recorder() if self.trace else None
        c0 = self.meter.snapshot()
        t0 = time.perf_counter()
        self.deadline = t0 + seconds
        try:
            with (obs.recording(rec) if rec is not None
                  else contextlib.nullcontext()):
                while True:
                    self._run_segment()
        except WindowClosed:
            pass
        finally:
            t1 = time.perf_counter()
            self._rnet.EventEngine = self._engine_cls
            self.deadline = float("inf")
        c1 = self.meter.snapshot()
        counters = ({k: m["value"] for k, m in rec.metrics.items()
                     if m["metric"] == "counter"} if rec is not None
                    else {})
        return {"elapsed_s": t1 - t0,
                "cycles": sum(len(s.cycles) for s in self.segments),
                "rounds": len(self.segments),
                "solve_s": list(self.solve_s),
                "transport_s": list(self.transport_s),
                "compiles": c1[0] - c0[0],
                "counters": counters}

    @staticmethod
    def end_to_end(stats: dict) -> dict:
        cyc = stats["cycles"]
        return {"cycle_ms": 1e3 * stats["elapsed_s"] / cyc,
                "solve_ms_p95": 1e3 * float(np.percentile(
                    stats["solve_s"], 95))}

    # ------------------------------------------------------------------
    def _legality(self):
        """Per segment: violations by rule, and the cycles at fault."""
        cfg = self.swarm_cfg
        inst = self.inst
        out = []
        for seg in self.segments:
            st = seg.sim.state
            log = st.log
            spray = [np.concatenate(x) if x else np.zeros(0, np.int64)
                     for x in ([a for a, p in zip(log.senders, log.phases)
                                if p[0] == 0],
                               [a for a, p in zip(log.receivers, log.phases)
                                if p[0] == 0],
                               [a for a, p in zip(log.chunks, log.phases)
                                if p[0] == 0])]
            bad, cyc_bad = ref.replay_warmup(
                n=cfg.n, K=cfg.chunks_per_update, kappa=cfg.owner_throttle,
                tau=cfg.tau_concurrent, adj=inst["adj"], up=inst["up"],
                down=inst["down"], lag=inst["lag"], spray=spray,
                cycles=seg.cycles, **self.thr)
            out.append((bad, cyc_bad))
        return out

    def _sample(self):
        """(segment, move index) pairs of the cycles whose stamps are
        compared: every transport of the window, or a sample drawn from
        the seed."""
        all_moves = [(i, j) for i, s in enumerate(self.segments)
                     for j in range(len(s.moves))]
        if len(all_moves) <= self.check_cycles:
            return all_moves
        rng = np.random.default_rng(round_seed(self.seed) ^ 0x5EED)
        pick = np.sort(rng.choice(len(all_moves), self.check_cycles,
                                  replace=False))
        return [all_moves[k] for k in pick]

    def _sampled_cycles(self):
        """Each sampled cycle's flows as the engine took them, the
        program's stamps, the instant its data could start, and the
        benchmark's links."""
        net, inst = self.cell.config["net"], self.inst
        links = dict(n=len(inst["lat"]),
                     chunk_bytes=float(self.swarm_cfg.chunk_bytes),
                     up=inst["up_bps"], down=inst["down_bps"],
                     lat=inst["lat"], quantum_frac=net["quantum_frac"])
        for i, j in self._sample():
            t_before, snd, rcv, ts, te = self.segments[i].moves[j]
            t0 = t_before + net["tracker_rtt_s"] + net["tracker_solve_s"]
            yield snd, rcv, np.asarray(ts), np.asarray(te), t0, links

    @staticmethod
    def _gap(ts, te, rs, re_, t0) -> float:
        """Widest gap between two sets of stamps, over the reference
        cycle's makespan."""
        span = max(float(np.max(re_, initial=t0)) - t0, 1e-12)
        return max(float(np.max(np.abs(te - re_), initial=0.0)),
                   float(np.max(np.abs(ts - rs), initial=0.0))) / span

    def stamp_gaps(self) -> list[float]:
        """Per sampled cycle, the program's stamps against the float64
        reference's."""
        return [self._gap(ts, te, *ref.cycle_stamps(snd, rcv, t0=t0, **links),
                          t0)
                for snd, rcv, ts, te, t0, links in self._sampled_cycles()]

    def check(self) -> tuple[dict, int, int]:
        legal = self._legality()
        violations = sum(sum(b.values()) for b, _ in legal)
        bad_cycles = sum(int(c.sum()) for _, c in legal)
        gaps = self.stamp_gaps()
        lim = self.limits["stamp_gap"]
        gap = max(gaps, default=0.0)
        attempted = sum(len(s.cycles) for s in self.segments)
        failed = bad_cycles + sum(g > lim for g in gaps)
        by_rule = {}
        for b, _ in legal:
            for k, v in b.items():
                by_rule[k] = by_rule.get(k, 0) + v
        self.violations_by_rule = by_rule
        checks = {"violations": {"value": int(violations), "limit": 0},
                  "stamp_gap": {"value": gap, "limit": lim}}
        return checks, attempted, min(failed, attempted)

    def control_readings(self) -> dict:
        """The control: the reference in float32 put in the program's
        place, read against the float64 reference on the same cycles.
        Its stamps run from the cycle's start: on the absolute clock
        float32 would round them to its resolution alone."""
        gaps = []
        for snd, rcv, _, _, t0, links in self._sampled_cycles():
            rs, re_ = ref.cycle_stamps(snd, rcv, t0=t0, **links)
            cs, ce = ref.cycle_stamps(snd, rcv, t0=0.0, dtype=np.float32,
                                      **links)
            gaps.append(self._gap(cs.astype(np.float64) + t0,
                                  ce.astype(np.float64) + t0, rs, re_, t0))
        return {"stamp_gap": max(gaps, default=0.0)}

    def calibrate(self, control: bool) -> dict:
        """Readings that set the limits: the program's, and with
        ``control`` the control's (the float32 transport in the
        program's place)."""
        checks, _, _ = self.check()
        out = {"program": {k: c["value"] for k, c in checks.items()},
               "by_rule": self.violations_by_rule}
        if control:
            out["control"] = self.control_readings()
        return out
