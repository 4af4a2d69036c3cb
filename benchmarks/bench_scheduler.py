"""Slot-engine smoke: one n=500/K=206 warm-up on the jit engine.

Runs a warm-up-only round at n=500 with K=206 (GoogLeNet chunking) and
a per-client warm-up goal of 1030 chunks, on the default ``"jit"`` slot
engine, and prints its wall clock with the per-phase breakdown taken
from the spans of a ``repro.obs`` recorder on the perf clock: bitplane
sync / matching / extraction on the engine side, spray / warm-up /
trace emit on the simulator side.  It exits non-zero if the round fails
open or misses a generous wall-clock bound (the CI perf smoke).  CPU
wall clock is not a device number.

Usage:  python benchmarks/bench_scheduler.py --smoke
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import banner  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import SwarmConfig  # noqa: E402
from repro.core import simulator as sim_mod  # noqa: E402

N_SMOKE = 500
K_SMOKE = 206          # Table III / GoogLeNet chunk count
CAP_SMOKE = 8192       # stratified candidate cap (state.candidate_columns)


def _smoke_cfg() -> SwarmConfig:
    # warmup_threshold_pct = 5/n sets k_term to 1030 chunks per client.
    return SwarmConfig(n=N_SMOKE, chunks_per_update=K_SMOKE,
                       s_max=100_000, seed=0,
                       scheduler="greedy_fastest_first",
                       scheduler_impl="jit",
                       warmup_threshold_pct=5.0 / N_SMOKE,
                       cand_cap=CAP_SMOKE)


def _wall(rec, name: str) -> float:
    """Host seconds of every ``name`` span the recorder holds."""
    return sum(r.get("wall_s", 0.0) for r in rec.rows
               if r["kind"] == "span" and r["name"] == name)


def engine_point() -> dict:
    """The warm-up-only smoke round with its per-phase breakdown."""
    # The recorder's perf clock times the simulator's round.* spans and
    # the jit engine's matching.* spans; recording() restores the null
    # recorder even if the run raises.
    clk = time.perf_counter
    with obs.recording(clock=clk) as rec:
        t0 = clk()
        sim = sim_mod.RoundSimulator(_smoke_cfg())
        setup_s = clk() - t0
        res = sim.run(warmup_only=True)
        total_s = clk() - t0
    tm = {name: _wall(rec, f"round.{name}")
          for name in ("spray", "warmup", "emit")}
    engine_ph = {"bitplane_s": _wall(rec, "matching.sync"),
                 "matching_s": _wall(rec, "matching.kernel"),
                 "extraction_s": _wall(rec, "matching.decode")}
    m = res.metrics
    row = {
        "n": N_SMOKE, "K": K_SMOKE, "cand_cap": CAP_SMOKE,
        "t_warm": m.t_warm,
        "failed_open": m.failed_open,
        "warmup_utilization": round(m.warmup_utilization, 4),
        "total_s": round(total_s, 2),
        "setup_s": round(setup_s, 2),      # state alloc + overlay
        "phases": {
            "spray_s": round(tm["spray"], 2),
            "warmup_s": round(tm["warmup"], 2),
            "trace_emit_s": round(tm["emit"], 2),
            # Engine-side split of warmup_s (host decode + rng +
            # candidate prep is the remainder).
            **{k: round(v, 2) for k, v in engine_ph.items()},
        },
    }
    print(f"  n={N_SMOKE} jit: warm-up {tm['warmup']:7.2f}s  "
          f"(total {total_s:6.1f}s, setup {setup_s:4.1f}s, "
          f"t_warm={m.t_warm}, failed_open={m.failed_open})", flush=True)
    return row


def smoke(bound_s: float = 300.0) -> int:
    """CI perf gate: one warm-up-only jit round at n=500/K=206 must
    finish inside a generous wall-clock bound on a cold CPU."""
    banner(f"Smoke: n={N_SMOKE}/K={K_SMOKE} jit warm-up under "
           f"{bound_s:.0f}s")
    row = engine_point()
    ok = (not row["failed_open"]) and row["total_s"] <= bound_s
    print(f"  smoke {'OK' if ok else 'MISS'}: total {row['total_s']}s "
          f"(bound {bound_s:.0f}s), failed_open={row['failed_open']}")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: the n=500 jit point (the only mode)")
    ap.parse_args()
    sys.exit(smoke())


if __name__ == "__main__":
    main()
