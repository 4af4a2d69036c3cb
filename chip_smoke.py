#!/usr/bin/env python3
"""On-chip smoke test: the system's main path, once, on one TPU chip.

    python chip_smoke.py                 # phases 1 and 2, one chip
    python chip_smoke.py --four-chips    # the multi-pod FL step, four chips

Phase 1 runs one FLTorrent round at the paper's residential size (n=500
peers, K=206 chunks of 256 KiB, GreedyFastestFirst on the jitted slot
engine, full warm-up stack, event-driven fair-share transport) and
checks it: every update reconstructable, the Eq. (1) bound, the
tracker audit, no duplicate delivery, and a schedule byte-identical to
the same seed run on the host CPU in this process.

Phase 2 takes FL training steps of qwen3-1.7b at its published widths,
cut in depth only, through the trainer's own loop
(:func:`repro.launch.train.train`).

``--four-chips`` runs only the multi-pod path: four pods (FL clients),
one per chip, aggregating through the torrent ring, plain and int8
compressed, against the same global batch through the one-pod
data-parallel step over the same four chips.

Each phase prints what ran, its checks and its seconds, with compile
time apart from run time.  Any failed check raises, so the script exits
non-zero; only a run whose phases all passed prints the closing JSON
line.  The script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# qwen3-1.7b at its published widths keeps this many of its 28 layers:
# the v5e compile rehearsal puts this train step (bf16 weights, AdamW's
# f32 master and moments, 2 x 2048 tokens, 151936-wide logits) at 12.5
# GiB of the chip's 15.75, leaving room for the allocator.
ONE_CHIP_LAYERS = 8
ONE_CHIP_BATCH, SEQ = 2, 2048
# Each of four pods holds the whole model, its optimizer state, its own
# gradient and the ring's f32 buffers: the 311M-parameter embedding and
# one layer is the depth the v5e compile rehearsal fits most easily.
FOUR_CHIP_LAYERS = 1
FOUR_CHIP_BATCH = 4
STEPS = 3
# Relative tolerance on t_warm_s / t_round_s between the chip and the
# host CPU run of the same seed (the fair-share kernels run in float64
# on both).
TIME_RTOL = 1e-6
# Four-chip agreement with the data-parallel reference: the per-step
# loss, and two relative L2 distances after the last step — the f32
# master weights, over the size of the reference's own update from the
# initial weights, and AdamW's first moment (a running mean of the
# aggregated gradient), over its own size.  The int8 ring quantizes
# each ring block (D / n_blocks elements) against one scale, so every
# gradient element below amax/254 arrives as zero; AdamW turns a zero
# gradient into no step where the reference steps by ~lr, so for the
# compressed ring only the moment is held tight and the weights need
# only sit closer to the reference than the initial weights do.
LOSS_RTOL = 1e-2
RING_RTOL = {False: {"master": 0.05, "moment": 0.05},
             True: {"master": 1.0, "moment": 0.10}}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"    ok  {what}", flush=True)


class CompileMeter:
    """XLA compiles seen by this process, from JAX's monitoring events:
    a persistent-cache hit is counted as a compile whose seconds are
    the cache read."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def timed(self, label: str, fn):
        """Run ``fn()``; print its wall, compile and run seconds."""
        c0, s0, h0 = self.count, self.seconds, self.cache_hits
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.seconds - s0
        print(f"  {label}: wall {wall:.3f}s = compile {comp:.3f}s "
              f"({self.count - c0} compiles, {self.cache_hits - h0} "
              f"from cache) + run {wall - comp:.3f}s", flush=True)
        return out


# ----------------------------------------------------------------------
# phase 1: one swarm round
# ----------------------------------------------------------------------

def swarm_phase(meter, device, ref_device, *, n: int = 500,
                chunks: int = 206, chunk_bytes: int = 256 * 1024) -> dict:
    """One event-engine round on ``device``, checked, and compared with
    the same seed on ``ref_device``."""
    import jax
    import numpy as np

    from repro.core import SwarmConfig, simulate_round
    from repro.core.audit import directives_from_trace, verify_directives
    from repro.core.privacy import check_eq1
    from repro.net import RESIDENTIAL_NET

    cfg = SwarmConfig(n=n, chunks_per_update=chunks,
                      chunk_bytes=chunk_bytes,
                      scheduler="greedy_fastest_first",
                      scheduler_impl="jit")
    print(f"phase 1: swarm round n={n} K={chunks} x {chunk_bytes} B, "
          f"{cfg.scheduler} on the jit engine, event engine, "
          f"RESIDENTIAL_NET, full warm-up stack", flush=True)

    def run_on(dev):
        with jax.default_device(dev):
            return simulate_round(cfg, time_engine="event",
                                  net=RESIDENTIAL_NET)

    res = meter.timed(f"round on {device.platform}:{device.id}",
                      lambda: run_on(device))
    ref = meter.timed(f"reference round on "
                      f"{ref_device.platform}:{ref_device.id}",
                      lambda: run_on(ref_device))
    m, mr = res.metrics, ref.metrics
    print(f"  slots: warm-up {m.t_warm}, round {m.t_round} "
          f"(fluid BT: {res.fluid_bt}); t_warm_s {m.t_warm_s!r}, "
          f"t_round_s {m.t_round_s!r}, warmup_share_s "
          f"{m.warmup_share_s!r}", flush=True)

    log = res.log
    check(bool(res.reconstructable.all()),
          f"all {n} updates reconstructable at every peer")
    check(bool(check_eq1(log, cfg.owner_throttle, cfg.k_gate)),
          "Eq. (1) holds on every warm-up transfer")
    bad = verify_directives(res.adj, directives_from_trace(log),
                            res.up, res.down)
    check(not bad, f"tracker audit (adjacency, budgets) clean {bad[:1]}")
    pair = (np.asarray(log["receiver"], np.int64) * cfg.total_chunks
            + np.asarray(log["chunk"], np.int64))
    check(np.unique(pair).size == pair.size,
          f"no receiver got a chunk twice ({pair.size} deliveries)")
    for key in ("slot", "sender", "receiver", "chunk"):
        a = np.ascontiguousarray(np.asarray(log[key], np.int64))
        b = np.ascontiguousarray(np.asarray(ref.log[key], np.int64))
        check(a.tobytes() == b.tobytes(),
              f"schedule column {key!r} byte-identical to the "
              f"{ref_device.platform} run ({a.size} rows)")
    for name in ("t_warm_s", "t_round_s"):
        a, b = getattr(m, name), getattr(mr, name)
        rel = abs(a - b) / max(abs(b), 1e-300)
        check(rel <= TIME_RTOL,
              f"{name} {a!r} vs {ref_device.platform} {b!r}: "
              f"relative difference {rel:.3e} <= {TIME_RTOL:g}")
    return {"t_warm": m.t_warm, "t_round": m.t_round,
            "t_warm_s": m.t_warm_s, "t_round_s": m.t_round_s}


# ----------------------------------------------------------------------
# phase 2: FL train steps
# ----------------------------------------------------------------------

def train_phase(meter, cfg, *, published_layers: int, steps: int,
                batch: int, seq: int) -> dict:
    """``steps`` FL steps of ``cfg`` at one pod through the trainer."""
    import jax

    from repro.launch.train import train

    print(f"phase 2: FL train step, {cfg.name} d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv} KV, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.dtype}; {cfg.n_layers} of {published_layers} layers "
          f"(depth is the only change from the published config); "
          f"batch {batch} x {seq} tokens, {steps} steps", flush=True)
    run = meter.timed("train", lambda: train(
        cfg, steps=steps, batch=batch, seq=seq, pods=1, log_every=1))
    n = int(sum(x.size for x in jax.tree_util.tree_leaves(run.params)))
    print(f"  parameters: {n} ({n / 1e6:.1f}M)", flush=True)
    print(f"  losses: {run.losses}", flush=True)
    print(f"  step seconds: {run.step_s} (after the first: "
          f"{run.step_s[1:]})", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"  peak_bytes_in_use: {peak} (bytes_limit "
          f"{stats.get('bytes_limit')})", flush=True)
    check(len(run.losses) == steps, f"{steps} steps taken")
    check(all(math.isfinite(x) for x in run.losses), "every loss finite")
    check(run.losses[-1] <= run.losses[0] * 1.01,
          "loss falling or flat (last <= first + 1%)")
    return {"params": n, "losses": run.losses, "step_s": run.step_s,
            "peak_bytes_in_use": peak}


# ----------------------------------------------------------------------
# --four-chips: torrent ring vs data parallel
# ----------------------------------------------------------------------

def _host_f32(tree) -> list:
    import jax
    import numpy as np
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(tree)]


def _rel_l2(a: list, b: list, base: list) -> float:
    import numpy as np
    num = sum(float(np.sum((x - y) ** 2)) for x, y in zip(a, b))
    den = sum(float(np.sum((y - z) ** 2)) for y, z in zip(b, base))
    return float(np.sqrt(num / max(den, 1e-30)))


def four_chip_phase(meter, cfg, *, pods: int, steps: int, batch: int,
                    seq: int) -> dict:
    """The ``pods``-pod FL step through the torrent ring (plain and
    compressed) against the one-pod data-parallel step on the same
    devices and the same global batch."""
    import jax

    from repro.launch.train import train
    from repro.models import init_params

    print(f"four-chip phase: {cfg.name} at {cfg.n_layers} layers, "
          f"{pods} pods over {jax.device_count()} devices, global batch "
          f"{batch} x {seq}, {steps} steps", flush=True)
    # train() initializes from PRNGKey(0); AdamW's master starts as the
    # f32 cast of these weights.
    base = _host_f32(init_params(cfg, jax.random.PRNGKey(0)))
    ref = meter.timed("data-parallel reference (1 pod)", lambda: train(
        cfg, steps=steps, batch=batch, seq=seq, pods=1, log_every=1))
    ref_devs = sorted(d.id for d in ref.mesh.devices.flat)
    print(f"  reference mesh devices: {ref_devs}; losses {ref.losses}",
          flush=True)
    ref_losses, ref_master = ref.losses, _host_f32(ref.opt.master)
    ref_m = _host_f32(ref.opt.m)
    del ref
    out = {}
    for compress in (False, True):
        run = meter.timed(f"torrent ring, compress={compress}",
                          lambda c=compress: train(
                              cfg, steps=steps, batch=batch, seq=seq,
                              pods=pods, log_every=1, compress=c))
        per_pod = [sorted(d.id for d in run.mesh.devices[p].flat)
                   for p in range(run.mesh.devices.shape[0])]
        print(f"  pod device ids: {per_pod}; losses {run.losses}",
              flush=True)
        check(len({i for ids in per_pod for i in ids}) == pods,
              f"{pods} pods on {pods} distinct devices")
        dl = max(abs(a - b) / abs(b) for a, b in zip(run.losses,
                                                        ref_losses))
        check(dl <= LOSS_RTOL, f"per-step loss vs data parallel: "
              f"relative difference {dl:.3e} <= {LOSS_RTOL}")
        tol = RING_RTOL[compress]
        rel = _rel_l2(_host_f32(run.opt.master), ref_master, base)
        check(rel <= tol["master"],
              f"master weights vs data parallel: relative L2 {rel:.3e} "
              f"<= {tol['master']}")
        rel_m = _rel_l2(_host_f32(run.opt.m), ref_m,
                        [x * 0 for x in ref_m])
        check(rel_m <= tol["moment"],
              f"first moment vs data parallel: relative L2 {rel_m:.3e} "
              f"<= {tol['moment']}")
        out[f"compress={compress}"] = {"losses": run.losses,
                                       "loss_rel": dl,
                                       "master_rel_l2": rel,
                                       "moment_rel_l2": rel_m}
        del run
    return out


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-pod ring vs data-parallel "
                         "comparison over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # Phase 1's reference runs on the host CPU: keep that backend
    # available next to the accelerator when the platforms are pinned.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"devices only", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if jax.device_count() < need:
        print(f"chip_smoke: needs {need} chips, found "
              f"{jax.device_count()}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{jax.device_count()}; compile cache {enable_compile_cache()}",
          flush=True)
    meter = CompileMeter()
    qwen = get_config("qwen3-1.7b", reduced=False)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(meter, qwen.replace(n_layers=FOUR_CHIP_LAYERS),
                        pods=4, steps=STEPS, batch=FOUR_CHIP_BATCH,
                        seq=SEQ)
    else:
        swarm_phase(meter, dev, jax.devices("cpu")[0])
        train_phase(meter, qwen.replace(n_layers=ONE_CHIP_LAYERS),
                    published_layers=qwen.n_layers, steps=STEPS,
                    batch=ONE_CHIP_BATCH, seq=SEQ)
    print(f"total: {time.perf_counter() - t0:.3f}s, {meter.count} "
          f"compiles, {meter.seconds:.3f}s compiling, "
          f"{meter.cache_hits} from cache", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
