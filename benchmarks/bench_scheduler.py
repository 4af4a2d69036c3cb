"""Slot-engine benchmark: loop vs batched vs jit (PR 8 acceptance).

Measures

1. **Headline speedup** — one full default round (spray -> warm-up ->
   exact BT) at the paper's n=100 / K=64 stress point, batched engine
   vs the per-receiver loop engine.
2. **Scaling sweep** — warm-up wall clock at n in {500, 1000, 2000,
   5000} with K=206 (GoogLeNet chunking) and a constant per-client
   warm-up goal (warmup_threshold_pct = 5/n, i.e. k_term = 1030
   chunks/client at every n), jit vs batched (vs loop at n=500).  The
   jit rows carry the engine's per-phase breakdown — bitplane build /
   matching / extraction on the engine side, spray / warm-up / trace
   emit on the simulator side — from the spans of a ``repro.obs``
   recorder on the perf clock.
3. **scaling_bends** — a log-log power-law fit of the batched curve,
   extrapolated to n=5000, must sit far above the jit engine's
   measured point: the packed-bitplane kernel visibly bends the
   scaling curve.

Emits ``results/bench/BENCH_scheduler.json``.

Usage:  python benchmarks/bench_scheduler.py [--quick] [--smoke]

``--quick`` stops the sweep at n=1000; ``--smoke`` runs only the
n=500 jit point under a generous wall-clock gate and exits non-zero
on a miss (the CI perf smoke).
"""
from __future__ import annotations

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import banner, save  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import SwarmConfig, simulate_round  # noqa: E402
from repro.core import simulator as sim_mod  # noqa: E402

K_SWEEP = 206          # Table III / GoogLeNet chunk count
CAP_SWEEP = 8192       # stratified candidate cap (state.candidate_columns)


def _round(cfg: SwarmConfig, bt_mode: str = "auto"):
    t0 = time.time()
    res = simulate_round(cfg, bt_mode=bt_mode)
    dt = time.time() - t0
    m = res.metrics
    return dt, {
        "t_warm": m.t_warm,
        "t_round": m.t_round,
        "warmup_utilization": round(m.warmup_utilization, 4),
        "overall_utilization": round(m.overall_utilization, 4),
        "warmup_share": round(m.warmup_share, 4),
        "failed_open": m.failed_open,
    }


def headline(n: int = 100, k: int = 64, seed: int = 0, reps: int = 4):
    """Full exact-BT round, interleaved best-of-reps per engine.

    Interleaving the engines and taking per-engine minima makes the
    ratio robust to background load on shared boxes (single-run wall
    clock here swings ~±20%).
    """
    best = {"batched": None, "loop": None}
    met = {}
    for i in range(reps):
        for impl in ("batched", "loop"):
            if impl == "loop" and i >= max(2, reps - 2):
                continue               # loop is ~6x slower; 2 reps do
            cfg = SwarmConfig(n=n, chunks_per_update=k, s_max=100_000,
                              seed=seed, scheduler_impl=impl)
            dt, m = _round(cfg)
            if best[impl] is None or dt < best[impl]:
                best[impl], met[impl] = dt, m
    out = {}
    for impl in ("batched", "loop"):
        out[impl] = {"seconds": round(best[impl], 3), **met[impl]}
        print(f"  {impl:7s}: {best[impl]:6.2f}s  "
              f"t_warm={met[impl]['t_warm']} "
              f"t_round={met[impl]['t_round']} "
              f"util={met[impl]['warmup_utilization']}", flush=True)
    out["speedup"] = round(out["loop"]["seconds"]
                           / out["batched"]["seconds"], 2)
    print(f"  speedup: {out['speedup']}x", flush=True)
    return out


def _sweep_cfg(n: int, impl: str) -> SwarmConfig:
    # warmup_threshold_pct = 5/n keeps k_term at 1030 chunks per client
    # for every n, so sweep points differ only in swarm size.
    return SwarmConfig(n=n, chunks_per_update=K_SWEEP, s_max=100_000,
                       seed=0, scheduler="greedy_fastest_first",
                       scheduler_impl=impl,
                       warmup_threshold_pct=5.0 / n,
                       cand_cap=CAP_SWEEP)


def _wall(rec, name: str) -> float:
    """Host seconds of every ``name`` span the recorder holds."""
    return sum(r.get("wall_s", 0.0) for r in rec.rows
               if r["kind"] == "span" and r["name"] == name)


def engine_point(n: int, impl: str) -> dict:
    """One warm-up-only sweep point with per-phase breakdown."""
    # The recorder's perf clock times the simulator's round.* spans and
    # the jit engine's matching.* spans; recording() restores the null
    # recorder even if the run raises.
    clk = time.perf_counter
    with obs.recording(clock=clk) as rec:
        t0 = clk()
        sim = sim_mod.RoundSimulator(_sweep_cfg(n, impl))
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
        "n": n, "K": K_SWEEP, "impl": impl, "cand_cap": CAP_SWEEP,
        "t_warm": m.t_warm,
        "failed_open": m.failed_open,
        "warmup_utilization": round(m.warmup_utilization, 4),
        "total_s": round(total_s, 2),
        # state alloc + overlay.  Setup and spray used to dominate the
        # large-n sweep points via quadratic python-loop fills (n=5000:
        # setup 30.5s, spray 2.7s); the vectorized fill/spray paths
        # hold them near-flat (~3s / ~1.2s at the same point), so the
        # sweep now times the engines, not the harness.
        "setup_s": round(setup_s, 2),
        "phases": {
            "spray_s": round(tm["spray"], 2),
            "warmup_s": round(tm["warmup"], 2),
            "trace_emit_s": round(tm["emit"], 2),
        },
    }
    if impl == "jit":
        # Engine-side split of warmup_s (host decode + rng + candidate
        # prep is the remainder).
        row["phases"].update(
            {k: round(v, 2) for k, v in engine_ph.items()})
    print(f"  n={n:5d} {impl:7s}: warm-up {tm['warmup']:7.2f}s  "
          f"(total {total_s:6.1f}s, setup {setup_s:4.1f}s, "
          f"t_warm={m.t_warm}, failed_open={m.failed_open})", flush=True)
    return row


def _fit_power(rows) -> tuple[float, float]:
    """Least-squares log-log fit warmup_s ~ a * n^p -> (a, p)."""
    xs = [math.log(r["n"]) for r in rows]
    ys = [math.log(max(r["phases"]["warmup_s"], 1e-9)) for r in rows]
    mx = sum(xs) / len(xs)
    my = sum(ys) / len(ys)
    vxx = sum((x - mx) ** 2 for x in xs)
    p = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / vxx
    a = math.exp(my - p * mx)
    return a, p


def scaling_sweep(sizes):
    """jit vs batched warm-up scaling; loop joins at the smallest n."""
    rows = []
    for n in sizes:
        rows.append(engine_point(n, "jit"))
        if n <= 2000:                  # batched at n=5000 takes ~an hour
            rows.append(engine_point(n, "batched"))
        if n == sizes[0]:
            rows.append(engine_point(n, "loop"))
    return rows


def bend_check(rows) -> dict:
    """Extrapolate the batched power law to the largest jit point."""
    batched = [r for r in rows if r["impl"] == "batched"]
    jit = [r for r in rows if r["impl"] == "jit"]
    if len(batched) < 2 or not jit:
        return {"scaling_bends": "insufficient points"}
    a, p = _fit_power(batched)
    top = max(jit, key=lambda r: r["n"])
    pred = a * top["n"] ** p
    meas = top["phases"]["warmup_s"]
    out = {
        "batched_fit_exponent": round(p, 2),
        "batched_extrapolated_s_at_n%d" % top["n"]: round(pred, 1),
        "jit_measured_s_at_n%d" % top["n"]: round(meas, 1),
        "bend_factor": round(pred / max(meas, 1e-9), 1),
        "scaling_bends": bool(meas < 0.5 * pred),
    }
    print(f"  batched ~ n^{p:.2f}; extrapolated to n={top['n']}: "
          f"{pred:.0f}s vs jit measured {meas:.1f}s "
          f"(bend x{out['bend_factor']}, bends={out['scaling_bends']})",
          flush=True)
    return out


def smoke(bound_s: float = 300.0) -> int:
    """CI perf gate: one warm-up-only jit round at n=500/K=206 must
    finish inside a generous wall-clock bound on a cold CPU."""
    banner(f"Smoke: n=500/K={K_SWEEP} jit warm-up under {bound_s:.0f}s")
    row = engine_point(500, "jit")
    ok = (not row["failed_open"]) and row["total_s"] <= bound_s
    print(f"  smoke {'OK' if ok else 'MISS'}: total {row['total_s']}s "
          f"(bound {bound_s:.0f}s), failed_open={row['failed_open']}")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="stop the scaling sweep at n=1000")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: only the n=500 jit point")
    args = ap.parse_args()
    if args.smoke:
        sys.exit(smoke())

    payload = {"bench": "scheduler",
               "date": time.strftime("%Y-%m-%d %H:%M:%S")}

    banner("Headline: n=100/K=64 full round, batched vs loop")
    payload["headline_n100_k64"] = headline()

    banner("Warm-up scaling sweep: jit vs batched, K=206, k_term=1030")
    sizes = [500, 1000] if args.quick else [500, 1000, 2000, 5000]
    payload["scaling_sweep"] = scaling_sweep(sizes)
    payload.update(bend_check(payload["scaling_sweep"]))

    top_jit = [r for r in payload["scaling_sweep"]
               if r["impl"] == "jit" and r["n"] == 5000]
    payload["n5000_warmup_under_60s"] = (
        bool(top_jit and not top_jit[0]["failed_open"]
             and top_jit[0]["phases"]["warmup_s"] < 60.0)
        if top_jit else "skipped (--quick)")
    ok = payload["headline_n100_k64"]["speedup"] >= 5.0
    payload["speedup_target_met"] = ok

    path = save("BENCH_scheduler", payload)
    print(f"\nwrote {path}")
    print(f"speedup {payload['headline_n100_k64']['speedup']}x "
          f"(target >=5x: {'OK' if ok else 'MISS'}); "
          f"n=5000 jit warm-up < 60s: "
          f"{payload.get('n5000_warmup_under_60s')}")


if __name__ == "__main__":
    main()
