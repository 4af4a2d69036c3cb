import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import — jax locks the device count on init.
# The 512 placeholder host devices exist ONLY for the dry-run; smoke
# tests and benchmarks see the real single CPU device (they never import
# this module).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each non-skipped cell this driver

    1. builds the step function + ShapeDtypeStruct inputs (specs.py),
    2. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``
       under the production mesh — success proves the sharding config is
       coherent (no resharding errors, no unsupported collectives),
    3. records ``compiled.memory_analysis()`` (fits-per-device proof),
       ``compiled.cost_analysis()`` (XLA's numbers, scan-undercounted),
       and the loop-corrected structural costs (hlo_analysis.py), and
    4. derives the three roofline terms (§Roofline).

Usage:
    python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np


def _mem_fields(ma) -> dict:
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    out["total_per_device_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             rules=None, sp: bool = False,
             microbatch: int = 0, torrent_blocks: int = 4,
             compress: bool = False, verbose: bool = True,
             cfg_overrides: dict | None = None,
             save_hlo: str = "") -> dict:
    from repro.configs import SHAPES, cell_skip_reason, get_config
    from repro.launch import hlo_analysis
    from repro.launch.flops import model_flops
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell, to_shardings
    from repro.sharding.api import DEFAULT_RULES, axis_rules

    skip = cell_skip_reason(arch, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip", "reason": skip}

    # the blocked XLA attention, whose FLOPs cost_analysis can see
    cfg = get_config(arch).replace(attn_impl="xla")
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    use_rules = dict(DEFAULT_RULES if rules is None else rules)
    if sp:
        use_rules["seq"] = "model"   # Megatron-style sequence parallel
    with mesh, axis_rules(use_rules, mesh):
        cell = build_cell(cfg, shape, mesh, rules=use_rules,
                          microbatch=microbatch,
                          torrent_blocks=torrent_blocks,
                          compress=compress)
        jitted = jax.jit(
            cell["step"],
            in_shardings=to_shardings(mesh, cell["in_specs"]),
            out_shardings=to_shardings(mesh, cell["out_specs"]))
        lowered = jitted.lower(*cell["args"])
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = _mem_fields(compiled.memory_analysis())
    ca = hlo_analysis.xla_cost_analysis(compiled)
    txt = compiled.as_text()
    costs = hlo_analysis.analyze(txt)
    mf = model_flops(cfg, shape)
    terms = hlo_analysis.roofline_terms(costs, model_flops_global=mf,
                                        n_chips=n_chips)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(txt)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips, "status": "ok",
        "compile_seconds": round(compile_s, 1),
        "memory": mem,
        "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))},
        "roofline": terms,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "knobs": {"microbatch": microbatch,
                  "torrent_blocks": torrent_blocks,
                  "compress": compress,
                  "cache_dtype": cfg.cache_dtype or cfg.dtype,
                  "overrides": cfg_overrides or {}},
    }
    if verbose:
        gb = mem.get("total_per_device_bytes", 0) / 2**30
        print(f"[{rec['mesh']}] {arch} x {shape_name}: OK "
              f"({compile_s:.0f}s compile, {gb:.2f} GiB/device, "
              f"dominant={terms['dominant']}, "
              f"roofline_frac={terms['roofline_fraction']:.3f})",
              flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
        print(f"  cost_analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}", flush=True)
        print(f"  structural: flops/dev={costs.flops:.3e} "
              f"hbm/dev={costs.hbm_bytes:.3e} "
              f"coll/dev={costs.coll_bytes:.3e} "
              f"colls={costs.coll_counts}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--torrent-blocks", type=int, default=4)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--cache-dtype", default="")
    ap.add_argument("--sp", action="store_true",
                    help="sequence parallelism: shard the residual stream seq dim over model")
    args = ap.parse_args()

    from repro.configs import all_cells
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.all:
        cells = [(a, s) for a, s, _ in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    ok = skipped = failed = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            try:
                ov = ({"cache_dtype": args.cache_dtype}
                      if args.cache_dtype else None)
                rec = run_cell(arch, shape, multi, sp=args.sp,
                               microbatch=args.microbatch,
                               torrent_blocks=args.torrent_blocks,
                               compress=args.compress,
                               cfg_overrides=ov,
                               save_hlo=args.save_hlo)
            except Exception as e:   # record failures — they are bugs
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if multi else "single",
                       "status": "fail", "error": repr(e)}
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            st = rec["status"]
            ok += st == "ok"
            skipped += st == "skip"
            failed += st == "fail"
            if st == "skip":
                print(f"[{rec['mesh']}] {arch} x {shape}: SKIP "
                      f"({rec['reason']})", flush=True)
            elif st == "fail":
                print(f"[{rec['mesh']}] {arch} x {shape}: FAIL", flush=True)
    print(f"\ndry-run summary: {ok} ok / {skipped} skip / {failed} fail",
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
