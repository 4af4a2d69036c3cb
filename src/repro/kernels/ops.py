"""Jit'd wrappers + implementation dispatch for the kernel package.

Every op has interchangeable implementations:

* ``impl="pallas"``     — the Pallas TPU kernel (production target).
* ``impl="interpret"``  — same kernel body, interpret=True (CPU tests).
* ``impl="xla"``        — pure-jnp *blocked* implementation: memory-
                          bounded like the kernel (chunked q / two-block
                          sliding window), differentiable, and what the
                          multi-pod dry-run lowers so cost_analysis sees
                          the real FLOPs.  NOT the O(T^2)-memory oracle
                          (that's ref.py, used only as a test oracle).

Attention adds ``impl="auto"``, the models' default: :func:`flash_block`
decides from the shapes, flags and backend.  A full-sequence causal
pass (train, prefill) on a TPU runs the causal flash kernels of
``flash_causal.py``, whose forward and backward are both Pallas;
everything else (decode, sliding windows, softcap, offsets, the CPU)
runs the blocked XLA path.  ``"pallas"`` and ``"interpret"`` take the
causal flash kernels wherever the shapes allow and otherwise the
forward-only kernel of ``attention.py`` (decode, windows, softcap).
Kernel tests sweep "interpret" vs ref.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash_causal, ref
from .attention import flash_attention
from .fedavg import fedavg_reduce as _fedavg_pallas
from .quantize import chunk_dequantize as _dq_pallas
from .quantize import chunk_quantize as _q_pallas
from .rglru import rglru_scan as _rglru_pallas

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Attention: XLA blocked path (chunked-q online softmax / two-block SWA)
# ----------------------------------------------------------------------

def _xla_attention_qchunk(q, k, v, *, causal, window, softcap, q_offset,
                          kv_offset, scale, block_q):
    """Chunked-over-q attention; peak memory O(block_q * Tk) per head.
    Values may be narrower than queries and keys (MLA)."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    dv = v.shape[-1]
    group = hq // hkv
    sc = (d ** -0.5) if scale is None else scale
    block_q = max(1, min(block_q, tq))
    pad_q = (-tq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    nq = q.shape[2] // block_q
    qb = q.reshape(b, hq, nq, block_q, d).transpose(2, 0, 1, 3, 4)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_block(args):
        qi, qblk = args
        qf = qblk.astype(jnp.float32)              # (b, hq, block_q, d)
        qg = qf.reshape(b, hkv, group, block_q, d)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kf) * sc
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = (q_offset + qi * block_q
                 + jnp.arange(block_q))[:, None]
        k_pos = kv_offset + jnp.arange(tk)[None, :]
        mask = jnp.broadcast_to(k_pos >= 0, (block_q, tk))
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(mask.any(-1)[None, None, None, :, None], p, 0.0)
        o = jnp.einsum("bkgqt,bktd->bkgqd", p, vf)
        return o.reshape(b, hq, block_q, dv)

    # Flash-style backward: without this, lax.map stores every block's
    # f32 softmax matrix as a residual — (nq, b, g, block_q, Tk) f32
    # per layer per microbatch dominated chameleon train_4k's HBM
    # traffic (§Perf cell-3 iter-2).  Recompute P inside the block.
    one_block = jax.checkpoint(
        one_block, policy=jax.checkpoint_policies.nothing_saveable)

    out = jax.lax.map(one_block, (jnp.arange(nq), qb))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, nq * block_q, dv)
    return out[:, :, :tq].astype(q.dtype)


def _xla_attention_swa(q, k, v, *, softcap, q_offset, scale, window):
    """Two-block sliding-window attention: q block i attends to k blocks
    (i-1, i) with block size = window, so compute/memory are O(T*window)
    instead of O(T^2).  Exact for causal SWA with width <= window."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert q_offset == 0 and tq == tk, "SWA fast path is for full-seq fwd"
    group = hq // hkv
    sc = (d ** -0.5) if scale is None else scale
    bs = window
    pad = (-tq) % bs
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    t = q.shape[2]
    nb = t // bs
    qb = q.reshape(b, hq, nb, bs, d).astype(jnp.float32)
    kb = k.reshape(b, hkv, nb, bs, d).astype(jnp.float32)
    vb = v.reshape(b, hkv, nb, bs, d).astype(jnp.float32)
    # Previous k/v block (zeros for block 0).
    kprev = jnp.pad(kb[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vb[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([kprev, kb], axis=3)       # (b, hkv, nb, 2bs, d)
    v2 = jnp.concatenate([vprev, vb], axis=3)
    qg = qb.reshape(b, hkv, group, nb, bs, d)
    s = jnp.einsum("bkgnqd,bkntd->bkgnqt", qg, k2) * sc
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    # Positions within the 2-block strip: q at bs + i, k at j.
    q_pos = bs + jnp.arange(bs)[:, None]
    k_pos = jnp.arange(2 * bs)[None, :]
    mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
    # First block has no previous block (its strip's left half is pad).
    blk = jnp.arange(nb)[:, None, None]
    valid = (k_pos[None] >= bs) | (blk > 0)
    mask = mask[None] & valid
    # Padded tail keys.
    if pad:
        abs_k = blk * bs + (k_pos[None] - bs)
        mask &= abs_k < tk
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1)[None, None, None, ..., None], p, 0.0)
    o = jnp.einsum("bkgnqt,bkntd->bkgnqd", p, v2)
    o = o.reshape(b, hq, t, d)
    return o[:, :, :tq].astype(q.dtype)


def _static_zero(offset) -> bool:
    return isinstance(offset, int) and offset == 0


def flash_block(tq: int, tk: int, dqk: int, dv: int, *, causal: bool,
                window: int | None, softcap: float | None, q_offset,
                kv_offset, impl: str, backend: str,
                devices: int = 1) -> int | None:
    """The tile edge of the causal flash kernels when :func:`attention`
    runs a call of these shapes and flags on them, else None.

    They take a full-sequence causal pass (static zero offsets, as many
    keys as queries, no window, no softcap) whose length a tile from
    ``flash_causal.block_for`` divides, traced for one device (XLA
    cannot partition a Pallas kernel over ``devices`` > 1): under
    ``impl="auto"`` on a TPU backend, under ``"pallas"``/``"interpret"``
    on any."""
    if impl == "auto":
        if backend != "tpu":
            return None
    elif impl not in ("pallas", "interpret"):
        return None
    if devices != 1:
        return None
    if not (causal and window is None and softcap is None and tq == tk
            and _static_zero(q_offset) and _static_zero(kv_offset)):
        return None
    return flash_causal.block_for(tq, dqk, dv)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: int | None = None,
              softcap: float | None = None, q_offset: int = 0,
              kv_offset: int = 0,
              scale: float | None = None, impl: str = "auto",
              block_q: int = 512, block_k: int = 512,
              devices: int = 1) -> jnp.ndarray:
    """Dispatching multi-head attention; see module docstring.  q:
    (B, Hq, Tq, D); k: (B, Hkv, Tk, D); v: (B, Hkv, Tk, Dv).  The flash
    path picks its own tile; ``block_q``/``block_k`` size the others.
    ``devices``: how many devices the computation is traced for."""
    block = flash_block(q.shape[2], k.shape[2], q.shape[-1], v.shape[-1],
                        causal=causal, window=window, softcap=softcap,
                        q_offset=q_offset, kv_offset=kv_offset, impl=impl,
                        backend=jax.default_backend(), devices=devices)
    if block is not None:
        sc = (q.shape[-1] ** -0.5) if scale is None else scale
        return flash_causal.causal_attention(
            q, k, v, scale=sc, block=block, interpret=impl == "interpret")
    if impl in ("pallas", "interpret"):
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset,
                               kv_offset=kv_offset,
                               scale=scale, block_q=block_q,
                               block_k=block_k,
                               interpret=impl == "interpret")
    if impl in ("xla", "auto"):
        tq, tk = q.shape[2], k.shape[2]
        static_offsets = _static_zero(q_offset) and _static_zero(kv_offset)
        if (window is not None and causal and static_offsets
                and tq == tk and tq > 2 * window):
            return _xla_attention_swa(q, k, v, softcap=softcap,
                                      q_offset=0, scale=scale,
                                      window=window)
        return _xla_attention_qchunk(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset,
                                     kv_offset=kv_offset,
                                     scale=scale, block_q=block_q)
    if impl == "ref":
        return ref.mha(q, k, v, causal=causal, window=window,
                       softcap=softcap, q_offset=q_offset,
                       kv_offset=kv_offset, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")


# ----------------------------------------------------------------------
# RG-LRU
# ----------------------------------------------------------------------

def _xla_rglru(x, a, gate_x, h0):
    """Associative-scan RG-LRU — O(log T) depth, differentiable."""
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)
    gx = gate_x.astype(jnp.float32)
    inp = jnp.sqrt(jnp.maximum(1.0 - af * af, 0.0)) * (gx * xf)
    if h0 is not None:
        # Fold h0 into the first step: h_1 = a_1 h_0 + i_1.
        inp = inp.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, hs = jax.lax.associative_scan(combine, (af, inp), axis=1)
    return hs.astype(x.dtype), hs[:, -1].astype(jnp.float32)


def rglru(x: jnp.ndarray, a: jnp.ndarray, gate_x: jnp.ndarray,
          h0: jnp.ndarray | None = None, *, impl: str = "xla",
          block_t: int = 256, block_d: int = 512):
    """Gated diagonal linear recurrence; returns (y (B,T,D), h_T (B,D))."""
    if impl == "pallas":
        return _rglru_pallas(x, a, gate_x, h0, block_t=block_t,
                             block_d=block_d)
    if impl == "interpret":
        return _rglru_pallas(x, a, gate_x, h0, block_t=block_t,
                             block_d=block_d, interpret=True)
    if impl == "xla":
        return _xla_rglru(x, a, gate_x, h0)
    if impl == "ref":
        return ref.rglru(x, a, gate_x, h0)
    raise ValueError(f"unknown rglru impl {impl!r}")


# ----------------------------------------------------------------------
# FedAvg reduction
# ----------------------------------------------------------------------

def fedavg(updates: jnp.ndarray, weights: jnp.ndarray,
           active: jnp.ndarray, *, impl: str = "xla",
           block_d: int = 2048) -> jnp.ndarray:
    if impl == "pallas":
        return _fedavg_pallas(updates, weights, active, block_d=block_d)
    if impl == "interpret":
        return _fedavg_pallas(updates, weights, active, block_d=block_d,
                              interpret=True)
    if impl in ("xla", "ref"):
        return ref.fedavg_reduce(updates, weights, active)
    raise ValueError(f"unknown fedavg impl {impl!r}")


# ----------------------------------------------------------------------
# Chunk quantization
# ----------------------------------------------------------------------

def quantize(x: jnp.ndarray, *, impl: str = "xla"):
    if impl == "pallas":
        return _q_pallas(x)
    if impl == "interpret":
        return _q_pallas(x, interpret=True)
    if impl in ("xla", "ref"):
        return ref.chunk_quantize(x)
    raise ValueError(impl)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, *, impl: str = "xla",
               dtype=jnp.float32):
    if impl == "pallas":
        return _dq_pallas(q, scale, dtype=dtype)
    if impl == "interpret":
        return _dq_pallas(q, scale, dtype=dtype, interpret=True)
    if impl in ("xla", "ref"):
        return ref.chunk_dequantize(q, scale).astype(dtype)
    raise ValueError(impl)


# ----------------------------------------------------------------------
# Chunkwise mLSTM
# ----------------------------------------------------------------------

def mlstm(q, k, v, i_pre, f_pre, *, chunk: int = 128,
          impl: str = "xla"):
    """Chunkwise-parallel mLSTM from zero state.

    q,k,v: (B, H, T, dh) (q,k pre-scaled); i_pre,f_pre: (B, H, T).
    Returns (h (B,H,T,dh), C, n, m).  impl="pallas"/"interpret" uses the
    fused kernel (state resident in VMEM); impl="xla" the scan form.
    """
    from repro.models.layers import _mlstm_chunkwise
    from .mlstm import mlstm_chunkwise as _k

    if impl == "pallas":
        return _k(q, k, v, i_pre, f_pre, chunk=chunk)
    if impl == "interpret":
        return _k(q, k, v, i_pre, f_pre, chunk=chunk, interpret=True)
    if impl in ("xla", "ref"):
        b, h, t, dh = q.shape
        init = (jnp.zeros((b, h, dh, dh)), jnp.zeros((b, h, dh)),
                jnp.full((b, h), -1e30))
        (C, n, m), hs = _mlstm_chunkwise(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), i_pre.transpose(0, 2, 1),
            f_pre.transpose(0, 2, 1), init, chunk=chunk, remat=False)
        return (hs.reshape(b, t, h, dh).transpose(0, 2, 1, 3), C, n, m)
    raise ValueError(impl)
