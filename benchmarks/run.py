"""Benchmark harness entry point: one benchmark per paper table/figure
plus the §Roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run            # fast defaults
    PYTHONPATH=src python -m benchmarks.run --full     # paper scale
    PYTHONPATH=src python -m benchmarks.run --check    # regression gate

``--check`` snapshots the committed ``results/bench/BENCH_*.json``
baselines BEFORE running, re-runs the selected suites, and diffs the
fresh payloads against the snapshots under the per-suite tolerances in
``CHECKS`` (see ``common.compare_bench``) — exit non-zero on any
regression.
"""
from __future__ import annotations

import argparse
import sys
import time

# Per-suite regression tolerances for --check.  Directions state which
# way is BETTER: "lower" wall clocks may not rise past the slack,
# "higher" capability counts may not fall, "equal" contracts (exit
# codes, validity booleans) may not move at all.
CHECKS = {
    "analysis": ("BENCH_analysis", [
        {"path": "smoke_exit_code", "direction": "equal"},
        {"path": "smoke_clean", "direction": "equal"},
        {"path": "files_analyzed", "direction": "higher"},
        {"path": "jit_targets_ready", "direction": "higher"},
        {"path": "cli_wall_s", "direction": "lower", "rel": 2.0,
         "abs": 5.0},
        {"path": "analyze_wall_s", "direction": "lower", "rel": 2.0,
         "abs": 5.0},
    ]),
    "obs": ("BENCH_obs", [
        {"path": "export_valid", "direction": "equal"},
        {"path": "perfetto_valid", "direction": "equal"},
        {"path": "tracks_covered", "direction": "equal"},
        {"path": "report_matches_metrics", "direction": "equal"},
        {"path": "control_s_matches", "direction": "equal"},
        {"path": "overhead_frac", "direction": "lower", "abs": 0.02},
        {"path": "rows", "direction": "higher", "rel": 0.5},
        {"path": "trace_events", "direction": "higher", "rel": 0.5},
        {"path": "record_wall_s", "direction": "lower", "rel": 3.0,
         "abs": 10.0},
    ]),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale parameters (slow)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig3,table3")
    ap.add_argument("--check", action="store_true",
                    help="diff fresh results against the committed "
                         "results/bench baselines; exit non-zero on "
                         "regression")
    args = ap.parse_args(argv)
    fast = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_analysis, bench_async, bench_attacks,
                   bench_net, bench_obs, bench_session,
                   fig3_utilization, fig4_decomposition, fig5_threshold,
                   fig6_7_asr, fig8_llm_scale, roofline,
                   table2_learning, table3_scaling)
    from .common import compare_bench, load

    suite = {
        "analysis": lambda: bench_analysis.run(fast=fast),
        "obs": lambda: bench_obs.run(fast=fast),
        "table2": lambda: table2_learning.run(fast=fast),
        "async": lambda: bench_async.run(fast=fast),
        "session": lambda: bench_session.run(fast=fast),
        "attacks": lambda: bench_attacks.run(fast=fast),
        "net": lambda: bench_net.run(fast=fast),
        "fig3": lambda: fig3_utilization.run(fast=fast),
        "fig4": lambda: fig4_decomposition.run(fast=fast),
        "fig5": lambda: fig5_threshold.run(fast=fast),
        "table3": lambda: table3_scaling.run(
            fast=fast, sizes=(100, 200) if fast else (100, 200, 300)),
        "fig6_7": lambda: fig6_7_asr.run(fast=fast),
        "fig8": lambda: fig8_llm_scale.run(fast=fast),
        "roofline": lambda: roofline.run(fast=fast),
    }
    only = [s for s in args.only.split(",") if s]
    # Snapshot the committed baselines BEFORE running: the suites
    # overwrite their own results/bench artifacts as they go.
    baselines = {}
    if args.check:
        for name, (artifact, _specs) in CHECKS.items():
            if only and name not in only:
                continue
            baselines[name] = load(artifact)
    t0 = time.time()
    failures = []
    payloads = {}
    for name, fn in suite.items():
        if only and name not in only:
            continue
        try:
            payloads[name] = fn()
        except Exception as e:                       # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    if args.check:
        for name, (artifact, specs) in CHECKS.items():
            if only and name not in only:
                continue
            base, cur = baselines.get(name), payloads.get(name)
            if base is None:
                failures.append(
                    (name, f"no committed baseline {artifact}.json"))
                continue
            if not isinstance(cur, dict):
                continue                # suite already failed above
            diff = compare_bench(base, cur, specs)
            n_ok = sum(1 for c in diff["checked"] if c["ok"])
            print(f"\n--check {name}: {n_ok}/{len(diff['checked'])} "
                  f"metrics within tolerance of {artifact}.json")
            for r in diff["regressions"]:
                print(f"  REGRESSION {r['path']}: baseline "
                      f"{r['baseline']} -> current {r['current']}")
            for p in diff["unmatched"]:
                print(f"  MISSING baseline metric: {p}")
            if not diff["ok"]:
                failures.append((name, "regression gate"))
    print(f"\n=== benchmarks done in {time.time() - t0:.0f}s; "
          f"{len(failures)} failures ===")
    for name, err in failures:
        print(f"  FAILED {name}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
