"""Pallas TPU flash attention for a full-sequence causal pass, forward
and backward.

q: (B, Hq, T, Dqk); k: (B, Hkv, T, Dqk); v: (B, Hkv, T, Dv), with Hq a
multiple of Hkv and Dv free of Dqk (MLA: q/k 192, v 128).  Every kernel
tiles the sequence in square ``block`` x ``block`` tiles and walks a
rectangular grid whose tiles above the diagonal do nothing: their K/V
(or Q/dO) index is clamped to the last live tile's, so the pipeline
fetches nothing for them, and ``pl.when`` skips their compute.  Only
the diagonal tiles apply the causal mask.

* forward: grid (B, Hq, q block, kv block); the running max, denominator
  and output accumulate in f32 VMEM scratch; it writes the output and
  the f32 log-sum-exp per row, nothing else.
* dQ: grid (B, Hq, q block, kv block); recomputes each tile's P from the
  log-sum-exp and accumulates dS K in f32.
* dK/dV: grid (B, Hkv, kv block, group, q block), the group's query
  heads and the q blocks innermost, so a KV head's gradients accumulate
  in f32 VMEM scratch over all the query heads that read it.

q, k, v, P, dO and dS enter the MXU in the inputs' dtype, products
accumulate in f32, and the softmax statistics and the dO.O row sums
stay f32.  The (block, block) score tile never leaves VMEM.  The
log-sum-exp and dO.O rows travel as (B, H, 1, T) rows: the dK/dV kernel
reads them as rows of its transposed tiles, the dQ kernel turns its
rows into columns once per q block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
BLOCKS = (512, 256, 128)
# A kernel's working set under the default scoped VMEM of a TPU core.
VMEM_BUDGET = 12 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def block_for(t: int, dqk: int, dv: int) -> int | None:
    """The tile edge for sequence length ``t`` at widths ``dqk``/``dv``:
    the largest of ``BLOCKS`` that divides ``t`` and whose heaviest
    kernel (dK/dV: four f32 score tiles, double-buffered inputs, f32
    accumulators) fits ``VMEM_BUDGET``; None if none does."""
    width = sum(-(-d // LANES) * LANES for d in (dqk, dv))
    for b in BLOCKS:
        need = (4 * b * b * 4                    # S, P, dP, dS in f32
                + 2 * 2 * 2 * b * width          # bf16 q, k, v, dO, 2 buffers
                + b * width * 4)                 # dK, dV accumulators
        if t % b == 0 and need <= VMEM_BUDGET:
            return b
    return None


def _causal(s, masked: bool, q_axis: int):
    """Mask a diagonal tile (query index along ``q_axis``)."""
    if not masked:
        return s
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = col <= row if q_axis == 0 else row <= col
    return jnp.where(keep, s, NEG_INF)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        s = _causal(s, masked, 0)
        m_prev = m_scr[...]                               # (b, 128)
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_next
        pv = jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + pv

    pl.when(j < i)(lambda: tile(False))
    pl.when(j == i)(lambda: tile(True))

    @pl.when(j == i)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T[:1, :]


def _fwd(q, k, v, scale, block, interpret):
    b, hq, t, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g, n = hq // hkv, t // block

    def kv_map(b_, h, i, j):
        return b_, h // g, jnp.minimum(j, i), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b, hq, n, n),
        in_specs=[
            pl.BlockSpec((None, None, block, dqk),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((None, None, block, dqk), kv_map),
            pl.BlockSpec((None, None, block, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block, dv),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((None, None, 1, block),
                         lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, hq, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_causal_fwd",
    )(q, k, v)


# ----------------------------------------------------------------------
# backward: dQ
# ----------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_scr, di_scr, acc_scr, *, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)
    block = acc_scr.shape[0]

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the rows' statistics as columns, once per q block
        lse_scr[...] = jnp.broadcast_to(lse_ref[...], (LANES, block)).T
        di_scr[...] = jnp.broadcast_to(di_ref[...], (LANES, block)).T

    def tile(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(_causal(s, masked, 0) - lse_scr[:, :1])
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_scr[:, :1])
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    pl.when(j < i)(lambda: tile(False))
    pl.when(j == i)(lambda: tile(True))

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dq(q, k, v, do, lse, di, scale, block, interpret):
    b, hq, t, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g, n = hq // hkv, t // block

    def q_map(b_, h, i, j):
        return b_, h, i, 0

    def kv_map(b_, h, i, j):
        return b_, h // g, jnp.minimum(j, i), 0

    def row_map(b_, h, i, j):
        return b_, h, 0, i

    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(b, hq, n, n),
        in_specs=[
            pl.BlockSpec((None, None, block, dqk), q_map),
            pl.BlockSpec((None, None, block, dqk), kv_map),
            pl.BlockSpec((None, None, block, dv), kv_map),
            pl.BlockSpec((None, None, block, dv), q_map),
            pl.BlockSpec((None, None, 1, block), row_map),
            pl.BlockSpec((None, None, 1, block), row_map),
        ],
        out_specs=pl.BlockSpec((None, None, block, dqk), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, dqk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_causal_dq",
    )(q, k, v, do, lse, di)


# ----------------------------------------------------------------------
# backward: dK, dV
# ----------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale: float):
    j, gi, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    last_g, last_i = pl.num_programs(3) - 1, pl.num_programs(4) - 1

    @pl.when((gi == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        # transposed tiles: kv positions down, q positions across
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(_causal(st, masked, 1) - lse_ref[...])
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_scr[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    pl.when(i > j)(lambda: tile(False))
    pl.when(i == j)(lambda: tile(True))

    @pl.when((gi == last_g) & (i == last_i))
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dkv(q, k, v, do, lse, di, scale, block, interpret):
    b, hq, t, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g, n = hq // hkv, t // block

    def q_map(b_, h, j, gi, i):
        return b_, h * g + gi, jnp.maximum(i, j), 0

    def row_map(b_, h, j, gi, i):
        return b_, h * g + gi, 0, jnp.maximum(i, j)

    def kv_map(b_, h, j, gi, i):
        return b_, h, j, 0

    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(b, hkv, n, g, n),
        in_specs=[
            pl.BlockSpec((None, None, block, dqk), q_map),
            pl.BlockSpec((None, None, block, dqk), kv_map),
            pl.BlockSpec((None, None, block, dv), kv_map),
            pl.BlockSpec((None, None, block, dv), q_map),
            pl.BlockSpec((None, None, 1, block), row_map),
            pl.BlockSpec((None, None, 1, block), row_map),
        ],
        out_specs=[pl.BlockSpec((None, None, block, dqk), kv_map),
                   pl.BlockSpec((None, None, block, dv), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, dqk), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_causal_dkv",
    )(q, k, v, do, lse, di)


# ----------------------------------------------------------------------
# differentiable entry point
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, block, interpret):
    return _fwd(q, k, v, scale, block, interpret)[0]


def _attention_fwd(q, k, v, scale, block, interpret):
    o, lse = _fwd(q, k, v, scale, block, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, block, interpret, res, do):
    q, k, v, o, lse = res
    do = do.astype(o.dtype)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    dq = _dq(q, k, v, do, lse, di, scale, block, interpret)
    dk, dv = _dkv(q, k, v, do, lse, di, scale, block, interpret)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     scale: float, block: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Causal softmax(scale q k^T) v over the whole sequence; returns
    (B, Hq, T, Dv) in q's dtype.  ``block`` must divide T (see
    :func:`block_for`)."""
    b, hq, t, dqk = q.shape
    _, hkv, tk, dk_ = k.shape
    if (tk, dk_) != (t, dqk) or v.shape[:3] != k.shape[:3] or hq % hkv:
        raise ValueError(f"shapes q {q.shape}, k {k.shape}, v {v.shape} "
                         "are no full-sequence attention")
    if t % block:
        raise ValueError(f"block {block} does not divide T = {t}")
    return _attention(q, k, v, float(scale), int(block), interpret)
