"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (process start to window start: imports, inputs and weights from
the seed, compiling or loading every program the window will call) is
reported as ``setup_s``.  The window then runs for ``--seconds``.
With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
line carries the per-layer metrics, the device's busy and window
seconds, and a breakdown.  After the window the driver checks what the
timed path produced against the plain reference; the compared numbers
and their limits close stderr and the result line.

A run without a TPU, with fewer chips than the cell asks for, on a chip
the peaks table does not know, or without the program beside it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.trace_reduce import WINDOW, Trace  # noqa: E402


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(workload: str, root: Path = ROOT,
            check_device=harness.check_device):
    """The cell, the program on the path, JAX with the compile cache
    on, and the device checked against the peaks table."""
    spec = harness.load_spec(root)
    cell = harness.resolve_cell(spec, workload, root)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise harness.BenchError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax
    harness.enable_compile_cache()
    peaks = harness.load_peaks(root)
    device = check_device(cell.chips, peaks, jax.devices())
    return cell, device, peaks[device["kind"]]


def _memory_peak(n_chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_chips]]
    return int(max(peaks, default=0))


def _traced_window(drv, seconds: float):
    """The window under the profiler; its trace reduced and deleted."""
    import glob

    import jax
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    # Host spans from TraceAnnotation only: tracing every Python call
    # would slow the host many times over and flood the trace.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation(WINDOW):
                stats = drv.window(seconds)
        files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise harness.BenchError("the profiler wrote no trace")
        trace = Trace.from_xplane(files[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return stats, trace


def run(args, root: Path = ROOT, check_device=harness.check_device) -> dict:
    """One run; returns the result line it printed."""
    cell, device, peak = prepare(args.workload, root, check_device)
    meter = harness.CompileMeter()
    drv = harness.load_driver(cell.driver, root).Driver(
        cell, args.seed, meter, bool(args.trace))
    drv.setup()
    setup_s = time.perf_counter() - T_PROCESS
    trace = None
    if args.trace:
        stats, trace = _traced_window(drv, args.seconds)
    else:
        stats = drv.window(args.seconds)
    device["memory_peak_bytes"] = _memory_peak(cell.chips)

    metrics = {}
    breakdown = None
    if args.trace:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        ctx = SimpleNamespace(cell=cell, stats=stats, trace=trace,
                              peak=peak, device=device)
        for m in cell.per_layer:
            value = harness.load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = harness.metric_entry(value, m["unit"])
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}
    else:
        values = drv.end_to_end(stats)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = harness.metric_entry(values[m["name"]],
                                                      m["unit"])
    # The reference runs once the window's numbers are taken and the
    # program's state is freed.
    gc.collect()
    checks, attempted, failed = drv.check()
    correct = (attempted > 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    harness.emit(line, checks)
    return dict(line, checks=checks)


def main(argv=None) -> int:
    args = _parse(argv)
    try:
        run(args)
        return 0
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
