"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 5

For every seed this runs the cell as ``run.py`` does -- set-up, a
window of ``--seconds`` at the cell's own sizes, the check -- and
prints the program's readings of each compared number as one JSON line.
For the control seeds it also prints the control's readings (the
reference in the next lower precision put in the program's place) and,
where the driver has them, the readings of planted faults.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.run import prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        cell, device, _ = prepare(args.workload)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    meter = harness.CompileMeter()
    drv_mod = harness.load_driver(cell.driver)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = drv_mod.Driver(cell, s, meter, False)
        drv.setup()
        stats = drv.window(args.seconds)
        out = drv.calibrate(s in control)
        out.update(workload=cell.name, seed=s, device=device["kind"],
                   wall_s=time.perf_counter() - t0,
                   window={k: v for k, v in stats.items()
                           if isinstance(v, (int, float))})
        print(json.dumps(out), flush=True)
        del drv
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
