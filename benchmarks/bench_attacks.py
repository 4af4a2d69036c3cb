"""Attack-scorer vectorization benchmark -> BENCH_attacks.json.

Measures the three §IV-C attack scorers — vectorized grouped-statistics
implementations over the typed :class:`TransferTrace` vs the historical
per-observation dict-loop references — on warm-up traces from n=100..500
swarms, asserting decision-for-decision equality while timing both.

The paper's privacy sweeps (Figs. 6-7: ablations x density x volume x
collusion x seeds) re-score the same traces dozens of times, so scorer
cost is the sweep bottleneck once simulation is vectorized; the vectorized
path removes the Python loop over observations (hundreds of thousands
of events at n=500).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import SwarmConfig, simulate_round
from repro.core.attacks import ATTACKS, ATTACKS_REFERENCE

from .common import banner, save


def _time(fn, reps=3):
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def run(fast: bool = False, sizes=None):
    banner("BENCH attacks — vectorized vs dict-loop ASR scoring")
    if sizes is None:
        sizes = (100, 200) if fast else (100, 300, 500)
    K = 16
    results = {}
    for n in sizes:
        cfg = SwarmConfig(n=n, chunks_per_update=K, s_max=50_000, seed=0)
        res = simulate_round(cfg, bt_mode="fluid")
        obs = np.arange(max(n // 10, 3))
        warm_events = int((res.log.phase == 1).sum())
        row = {"events": len(res.log), "warmup_events": warm_events,
               "observers": int(obs.size), "attacks": {}}
        for name in ATTACKS:
            t_vec, r_vec = _time(lambda: ATTACKS[name](res.log, obs, K))
            t_ref, r_ref = _time(
                lambda: ATTACKS_REFERENCE[name](res.log, obs, K))
            assert r_vec.asr_per_observer == r_ref.asr_per_observer, name
            assert r_vec.n_decisions == r_ref.n_decisions, name
            row["attacks"][name] = {
                "t_vectorized_s": t_vec, "t_loop_s": t_ref,
                "speedup": t_ref / max(t_vec, 1e-12),
                "max_asr": r_vec.max_asr,
                "n_decisions": r_vec.n_decisions,
            }
        tot_vec = sum(a["t_vectorized_s"] for a in row["attacks"].values())
        tot_ref = sum(a["t_loop_s"] for a in row["attacks"].values())
        row["speedup_combined"] = tot_ref / max(tot_vec, 1e-12)
        results[n] = row
        print(f"  n={n}: {warm_events} warm-up events, combined speedup "
              f"{row['speedup_combined']:.1f}x "
              + " ".join(f"{a}={v['speedup']:.1f}x"
                         for a, v in row["attacks"].items()))
    save("BENCH_attacks", {"K": K, "sizes": list(sizes),
                           "results": results})
    return results


if __name__ == "__main__":
    run()
