"""Compile rehearsal of the main path's device kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret
mode cannot (block shapes the tiling refuses, VMEM overuse, dtypes the
chip lacks) at real widths and without a chip.  Nothing runs: each test
lowers a kernel for one described v5e chip and compiles it.

The topology is described inside a module fixture only: describing it
loads the TPU library, which one process at a time may hold, so it must
happen in the process that runs these tests and never while a module
is imported.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import jit_engine
from repro.kernels.attention import flash_attention
from repro.kernels.flash_causal import block_for, causal_attention
from repro.kernels.fedavg import fedavg_reduce
from repro.kernels.quantize import chunk_dequantize, chunk_quantize
from repro.kernels.rglru import rglru_scan
from repro.net import fairshare

# One FLTorrent update at the paper's size: 206 chunks x 256 KiB of f32.
K_CHUNKS = 206
CHUNK_ELEMS = 256 * 1024 // 4
UPDATE_ELEMS = K_CHUNKS * CHUNK_ELEMS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *specs, sharding):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_fedavg_reduce_compiles_at_update_size(one_chip):
    c = _compile(fedavg_reduce, ((8, UPDATE_ELEMS), jnp.float32),
                 ((8,), jnp.float32), ((8,), jnp.float32),
                 sharding=one_chip)
    assert _has_kernel(c)


def test_chunk_quantize_roundtrip_compiles_at_update_size(one_chip):
    def roundtrip(x):
        q, s = chunk_quantize(x)
        return chunk_dequantize(q, s)

    c = _compile(roundtrip, ((K_CHUNKS, CHUNK_ELEMS), jnp.float32),
                 sharding=one_chip)
    assert _has_kernel(c)


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    # qwen3-1.7b: 16 query heads over 8 KV heads, head_dim 128.
    c = _compile(flash_attention, ((1, 16, 2048, 128), jnp.bfloat16),
                 ((1, 8, 2048, 128), jnp.bfloat16),
                 ((1, 8, 2048, 128), jnp.bfloat16), sharding=one_chip)
    assert _has_kernel(c)


@pytest.mark.parametrize("hq, hkv, t, dqk, dv", [
    (16, 16, 8192, 192, 128),   # moonlight-train: MLA widths at 8k
    (16, 8, 2048, 128, 128),    # qwen3-train: GQA 16/8
], ids=["mla-8k", "gqa-2k"])
def test_causal_flash_forward_and_backward_compile(one_chip, hq, hkv, t,
                                                   dqk, dv):
    block = block_for(t, dqk, dv)

    def loss(q, k, v):
        o = causal_attention(q, k, v, scale=dqk ** -0.5, block=block)
        return jnp.sum(o.astype(jnp.float32))

    specs = (((2, hq, t, dqk), jnp.bfloat16), ((2, hkv, t, dqk), jnp.bfloat16),
             ((2, hkv, t, dv), jnp.bfloat16))
    c = _compile(jax.grad(loss, (0, 1, 2)), *specs, sharding=one_chip)
    txt = c.as_text()
    for name in ("flash_causal_fwd", "flash_causal_dq", "flash_causal_dkv"):
        assert name in txt


def test_rglru_compiles_at_recurrentgemma_width(one_chip):
    # recurrentgemma-2b's recurrence width (d_rnn 2560) at 2k context.
    shape = (1, 2048, 2560)
    c = _compile(lambda x, a, g: rglru_scan(x, a, g),
                 (shape, jnp.bfloat16), (shape, jnp.bfloat16),
                 (shape, jnp.bfloat16), sharding=one_chip)
    assert _has_kernel(c)


def test_slot_rounds_compiles_at_paper_residential_size(one_chip):
    # n=500 peers, K=206: the (500, ceil(500*206/32)) packed inventory,
    # the 2**15 candidate bucket and the GFF non-owner-first gated
    # variant a warm-up slot dispatches.
    n, m_pad = 500, 1 << 15
    w_full = -(-n * K_CHUNKS // 32)
    kern = functools.partial(jit_engine._slot_rounds, 2, True, False, 4, 32)
    i32, u32 = jnp.int32, jnp.uint32
    c = _compile(kern, ((n, w_full), u32), ((m_pad,), i32),
                 ((m_pad,), i32), ((m_pad,), jnp.bool_), ((), i32),
                 ((n,), jnp.bool_), ((n, 16), i32), ((n,), i32),
                 ((n,), i32), ((), i32), ((), i32), ((), i32),
                 sharding=one_chip)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("kernel", ["maxmin", "transport"])
def test_fairshare_kernels_compile_at_paper_residential_size(one_chip,
                                                             kernel):
    # n=500 peers, 2048 flows and 8192 chunks: the warm-up cycle bucket
    # of the n=500 / K=206 residential round.
    n, f_pad, m_pad = 500, 2048, 8192
    ft, it = jnp.float64, jnp.int64
    with jax.enable_x64(True):
        if kernel == "maxmin":
            fn = fairshare._maxmin_compiled(n, f_pad, 16)
            specs = (((f_pad,), it), ((f_pad,), it), ((n,), ft),
                     ((n,), ft), ((f_pad,), jnp.bool_))
        else:
            fn = fairshare._transport_compiled(n, f_pad, m_pad, 16)
            specs = (((f_pad,), it), ((f_pad,), it), ((f_pad,), ft),
                     ((f_pad,), ft), ((), ft), ((n,), ft), ((n,), ft),
                     ((), ft), ((m_pad,), it), ((m_pad,), it))
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        c = fn.lower(*args).compile()
    assert c.memory_analysis() is not None
