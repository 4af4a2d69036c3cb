"""Pallas TPU kernel for the RG-LRU gated diagonal linear recurrence.

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (gx_t * x_t)

(De et al., "Griffin/RecurrentGemma", 2024.)  This is recurrentgemma's
hot loop at long context: elementwise (VPU) work that is purely
memory-bound, so the kernel's job on TPU is to stream each (T, D) slab
HBM->VMEM exactly once and keep the carry ``h`` resident in VMEM.

Blocking: grid = (B, D/block_d, T/block_t) with **time innermost** so the
(1, block_d) carry persists in VMEM scratch across time blocks.  Inside
a block a ``fori_loop`` walks tile-aligned 16-row slabs, and the rows of
each slab run unrolled — the recurrence is inherently sequential in t,
but each step is a (1, block_d) vector op on the VPU.  VMEM per step =
4 slabs * block_t * block_d * 4 B (x, a, gx in + y out) + carry;
defaults (block_t=256, block_d=512) give ~2 MiB, well under budget
with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Rows per fori_loop step: one packed sublane tile of a 16-bit dtype
# (two of f32), so every dynamic row offset is tile-aligned.
_SUB = 16


def _rglru_kernel(x_ref, a_ref, g_ref, h0_ref, y_ref, hT_ref, h_scr, *,
                  block_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    row_id = jax.lax.broadcasted_iota(jnp.int32, (_SUB, h_scr.shape[1]), 0)

    def step(i, h):
        # Load a tile-aligned (_SUB, block_d) slab, run the recurrence
        # over its rows in registers, store the slab back in one write.
        rows = pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)
        x = x_ref[0, rows, :].astype(jnp.float32)
        a = a_ref[0, rows, :].astype(jnp.float32)
        g = g_ref[0, rows, :].astype(jnp.float32)
        inp = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * (g * x)
        y = jnp.zeros_like(x)
        for j in range(_SUB):
            h = a[j:j + 1] * h + inp[j:j + 1]
            y = jnp.where(row_id == j, h, y)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_t // _SUB, step, h_scr[...])

    @pl.when(ti == nt - 1)
    def _final():
        hT_ref[...] = h_scr[...].astype(hT_ref.dtype)


def rglru_scan(x: jnp.ndarray, a: jnp.ndarray, gate_x: jnp.ndarray,
               h0: jnp.ndarray | None = None, *,
               block_t: int = 256, block_d: int = 512,
               interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x, a, gate_x: (B, T, D).  Returns (y (B,T,D), h_T (B,D))."""
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), jnp.float32)
    # Whole _SUB-row slabs; the padded rows below carry a = 1 (inert).
    block_t = -(-min(block_t, t) // _SUB) * _SUB
    block_d = min(block_d, d)
    pad_t = (-t) % block_t
    pad_d = (-d) % block_d
    if pad_t or pad_d:
        pad = ((0, 0), (0, pad_t), (0, pad_d))
        # Pad a with 1 (h_t = 1*h + sqrt(1-1)*... = h): carry stays inert
        # through padded time rows, so h_T is the true final state.
        x = jnp.pad(x, pad)
        a = jnp.pad(a, pad, constant_values=1.0)
        gate_x = jnp.pad(gate_x, pad)
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d)))
    tt, dd = x.shape[1], x.shape[2]
    nt, nd = tt // block_t, dd // block_d

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    y, hT = pl.pallas_call(
        kernel,
        grid=(b, nd, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda b_, di, ti: (b_, ti, di)),
            pl.BlockSpec((1, block_t, block_d),
                         lambda b_, di, ti: (b_, ti, di)),
            pl.BlockSpec((1, block_t, block_d),
                         lambda b_, di, ti: (b_, ti, di)),
            pl.BlockSpec((1, block_d), lambda b_, di, ti: (b_, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda b_, di, ti: (b_, ti, di)),
            pl.BlockSpec((1, block_d), lambda b_, di, ti: (b_, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tt, dd), x.dtype),
            jax.ShapeDtypeStruct((b, dd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(x, a, gate_x, h0)
    if pad_t or pad_d:
        y = y[:, :t, :d]
        hT = hT[:, :d]
    return y, hT
