"""Regenerate tests/golden_schedules.json.

The golden file pins the byte-exact transfer schedules of all six
scheduler policies (x both slot engines, x seeds) and the exact ASR
numbers of the three observation attacks, as produced by the historical
string-dispatch code path.  The SchedulerPolicy / TransferTrace API must
reproduce them bit-for-bit (tests/test_policy_api.py,
tests/test_trace.py).

    PYTHONPATH=src python tests/capture_golden.py
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from repro.core import SwarmConfig, simulate_round
from repro.core.attacks import run_all_attacks

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "golden_schedules.json")

MODES = ["random_fifo", "random_fastest_first", "greedy_fastest_first",
         "distributed", "flooding"]
IMPLS = ("loop", "jit")
SEEDS = [1, 9]

LOG_KEYS = ("slot", "sender", "receiver", "chunk", "owner",
            "b_size", "o_size", "phase")


def log_digest(log) -> str:
    h = hashlib.sha256()
    for key in LOG_KEYS:
        arr = np.ascontiguousarray(np.asarray(log[key], dtype=np.int64))
        h.update(key.encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def main():
    golden = {"schedules": {}, "attacks": {}}
    for mode in MODES:
        for impl in IMPLS:
            for seed in SEEDS:
                cfg = SwarmConfig(n=16, chunks_per_update=24, s_max=5000,
                                  seed=seed, scheduler=mode,
                                  scheduler_impl=impl)
                res = simulate_round(cfg)
                key = f"{mode}/{impl}/{seed}"
                golden["schedules"][key] = log_digest(res.log)
                print(key, golden["schedules"][key][:16])

    # Exact attack numbers (Figs. 6-7 path): loop engine, two ablations.
    for name, kw in {
        "full": {},
        "none": dict(enable_preround=False, enable_timelag=False,
                     enable_gating=False, enable_nonowner_first=False),
    }.items():
        for seed in (0, 1):
            cfg = SwarmConfig(n=24, chunks_per_update=24, s_max=5000,
                              seed=seed, scheduler_impl="loop", **kw)
            res = simulate_round(cfg)
            reps = run_all_attacks(res.log, np.arange(6), 24)
            pooled = run_all_attacks(res.log, np.arange(12), 24,
                                     pooled=True)
            key = f"{name}/{seed}"
            golden["attacks"][key] = {
                a: {"max": reps[a].max_asr, "mean": reps[a].mean_asr,
                    "n": reps[a].n_decisions,
                    "pooled_max": pooled[a].max_asr,
                    "pooled_any": pooled[a].any_correct_rate}
                for a in reps
            }
            print(key, {a: round(v["max"], 4)
                        for a, v in golden["attacks"][key].items()})

    with open(OUT, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
