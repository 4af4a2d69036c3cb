"""Dist-collective microbenchmark: torrent ring vs one-shot all-reduce.

Measures, on an 8-fake-device host mesh (pod axis = 8):

1. ``torrent_fedavg`` wall time across ``n_blocks`` in {1, 2, 4, 8},
   plus the int8 wire-compression path, with the structural
   collective-permute count from the lowered HLO ((P-1) x n_blocks
   [+ P-1 scale sends when compressed] — the paper's chunked
   dissemination schedule made visible to the XLA scheduler).
2. The ``psum`` comparator: the same masked FedAvg as a single fused
   all-reduce (what a datacenter job would run) — the latency budget
   the chunked ring trades against for overlap and per-chunk
   compression.

Emits ``results/bench/BENCH_dist.json``.

Usage:  python benchmarks/bench_dist.py [--d ELEMS] [--reps N]
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import argparse      # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from common import banner, save  # noqa: E402
from repro.dist.torrent import masked_weights, torrent_fedavg  # noqa: E402

PODS = 8


def _time(fn, args, reps: int) -> float:
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        best = min(best, time.perf_counter() - t0)
    return best


def bench_ring(mesh, ups, w, a, n_blocks: int, compress: bool, reps: int):
    fn = jax.jit(lambda u, ww, aa: torrent_fedavg(
        u, ww, aa, mesh=mesh, n_blocks=n_blocks, compress=compress))
    with mesh:
        txt = fn.lower(ups, w, a).as_text()
        dt = _time(fn, (ups, w, a), reps)
    n_cp = len(re.findall(r"collective.permute", txt))
    return {"n_blocks": n_blocks, "compress": compress,
            "ms": round(dt * 1e3, 3), "collective_permutes": n_cp}


def bench_psum(mesh, ups, w, a, reps: int):
    """Masked FedAvg as one fused all-reduce (the datacenter baseline)."""
    def body(x, wn):
        idx = jax.lax.axis_index("pod")
        return jax.lax.psum(x[0] * wn[idx], "pod")

    def agg(u, ww, aa):
        wn = masked_weights(ww, aa)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("pod", None), P(None)),
                             out_specs=P(None), check_vma=False)(u["w"], wn)

    fn = jax.jit(agg)
    with mesh:
        txt = fn.lower(ups, w, a).as_text()
        dt = _time(fn, (ups, w, a), reps)
    n_ar = len(re.findall(r"all.reduce", txt))
    return {"ms": round(dt * 1e3, 3), "all_reduces": n_ar}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=1 << 20,
                    help="update elements per pod (default 1Mi = 4 MiB)")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    assert jax.device_count() >= PODS, jax.device_count()
    mesh = jax.make_mesh((PODS, 1), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    ups = {"w": jax.random.normal(key, (PODS, args.d), jnp.float32)}
    w = jnp.arange(1.0, PODS + 1.0)
    a = jnp.ones(PODS)

    payload = {"bench": "dist", "pods": PODS, "d": args.d,
               "bytes_per_pod": args.d * 4,
               "date": time.strftime("%Y-%m-%d %H:%M:%S")}

    banner(f"torrent ring, P={PODS}, D={args.d} f32, n_blocks sweep")
    ring = []
    for nb in (1, 2, 4, 8):
        r = bench_ring(mesh, ups, w, a, nb, False, args.reps)
        print(f"  n_blocks={nb:2d}  {r['ms']:8.2f} ms  "
              f"{r['collective_permutes']} collective-permutes")
        ring.append(r)
    rc = bench_ring(mesh, ups, w, a, 4, True, args.reps)
    print(f"  n_blocks= 4  {rc['ms']:8.2f} ms  "
          f"{rc['collective_permutes']} collective-permutes  [int8 wire]")
    payload["ring"] = ring
    payload["ring_compressed"] = rc

    banner("psum all-reduce comparator")
    ps = bench_psum(mesh, ups, w, a, args.reps)
    print(f"  fused all-reduce  {ps['ms']:8.2f} ms")
    payload["psum"] = ps

    # structural acceptance: (P-1) x n_blocks explicit sends
    payload["schedule_ok"] = all(
        r["collective_permutes"] >= (PODS - 1) * r["n_blocks"]
        for r in ring)

    path = save("BENCH_dist", payload)
    print(f"\nwrote {path}")
    print(f"schedule_ok (>= (P-1)*n_blocks permutes): "
          f"{payload['schedule_ok']}")


if __name__ == "__main__":
    main()
