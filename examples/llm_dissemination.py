"""LLM-scale dissemination stress test (paper §V-E): swarm a 7B-class
bf16 update over datacenter links with and without unlinkability
hardening, and demonstrate the int8 chunk-compression wire format used
by the on-pod torrent collective.

    PYTHONPATH=src python examples/llm_dissemination.py
"""
import jax
import jax.numpy as jnp

from repro.core import SwarmConfig, simulate_round
from repro.core.capacities import DATACENTER
from repro.kernels import ops


def main():
    # --- swarm-level: 7B bf16 update, 4 MiB pieces, 24 peers ---
    nbytes = 7e9 * 2
    chunk = 4 * 2**20
    K = int(-(-nbytes // chunk))
    common = dict(n=24, chunks_per_update=K, chunk_bytes=chunk,
                  s_max=10**7, seed=0, min_degree=10)
    base = simulate_round(
        SwarmConfig(**common, enable_gating=False, enable_preround=False,
                    enable_timelag=False, enable_nonowner_first=False,
                    warmup_threshold_pct=0.0),
        link_model=DATACENTER, bt_mode="fluid").metrics
    full = simulate_round(SwarmConfig(**common), link_model=DATACENTER,
                          bt_mode="fluid").metrics
    ovh = (full.t_round - base.t_round) / base.t_round
    print(f"7B update, {K} pieces, 24 peers @ 7-10 Gbps:")
    print(f"  BitTorrent-only round: {base.t_round}s")
    print(f"  FLTorrent (hardened):  {full.t_round}s  ({ovh:+.1%})")

    # --- chunk-level: int8 wire compression (the dissemination
    #     collective quantizes ONCE at source, hops carry int8) ---
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 65536)) * 0.02
    # The Pallas kernel: compiled on a TPU, interpreted elsewhere.
    impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    q, scales = ops.quantize(x, impl=impl)
    deq = ops.dequantize(q, scales, impl=impl)
    rel = float(jnp.abs(deq - x).max() / jnp.abs(x).max())
    ratio = x.nbytes / (q.nbytes + scales.nbytes)
    print(f"\nint8 chunk compression: {ratio:.2f}x fewer wire bytes, "
          f"max rel err {rel:.4f}")


if __name__ == "__main__":
    main()
