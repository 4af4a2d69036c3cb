"""Pallas TPU kernels: per-chunk int8 symmetric quantize / dequantize.

FLTorrent disseminates updates as fixed 256 KiB chunks (§II-B); int8
chunk compression is our gradient-compression hook for the dissemination
collective (4x fewer bytes over ICI/DCN per chunk, one f32 scale per
chunk).  Per-chunk scales keep the quantization error local: a single
outlier layer only degrades its own chunks.

Each 256 KiB f32 chunk is 65 536 elements = a (512, 128) lane-aligned
tile; the quant kernel does one amax reduction + one scaled round per
tile (memory-bound, one HBM pass), grid = (n_chunks,).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (1, rows, lanes)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.full_like(s_ref, scale)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]
                  ).astype(x_ref.dtype)


def _tile(e: int) -> tuple[int, int]:
    """(rows, lanes) view of one E-element chunk: lane-dense 128-wide
    rows when E allows, else one full-width row.  Either way the block
    spans the whole chunk, so its last two dims equal the array's."""
    return (e // 128, 128) if e % 128 == 0 else (1, e)


def chunk_quantize(x: jnp.ndarray, *, interpret: bool = False
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (n_chunks, E) f32 -> (int8 (n,E), f32 scales (n,1))."""
    n, e = x.shape
    r, c = _tile(e)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, r, c), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, r, c), jnp.int8),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(n, r, c))
    return q.reshape(n, e), s.reshape(n, 1)


def chunk_dequantize(q: jnp.ndarray, scale: jnp.ndarray, *,
                     dtype=jnp.float32,
                     interpret: bool = False) -> jnp.ndarray:
    """(n, E) int8 + (n, 1) scales -> (n, E) dtype."""
    n, e = q.shape
    r, c = _tile(e)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r, c), dtype),
        interpret=interpret,
    )(q.reshape(n, r, c), scale.reshape(n, 1, 1))
    return x.reshape(n, e)
