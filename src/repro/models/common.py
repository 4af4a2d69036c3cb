"""Shared model components: norms, RoPE, embeddings, chunked CE loss."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.sharding.api import logical_constraint


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float) -> jnp.ndarray:
    """x: (B, H, T, D); positions: (T,) or (B, T) absolute positions."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
        ang = ang[None, None]                       # (1, 1, T, half)
    else:
        ang = positions.astype(jnp.float32)[:, None, :, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def embed_tokens(embed: jnp.ndarray, tokens: jnp.ndarray,
                 d_model: int, scale: bool = True) -> jnp.ndarray:
    """Embedding rows of ``tokens``, times ``sqrt(d_model)`` if
    ``scale``."""
    x = jnp.take(embed, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(math.sqrt(d_model), x.dtype)
    return logical_constraint(x, "batch", "seq", None)


def unembed_logits(x: jnp.ndarray, embed_t: jnp.ndarray,
                   softcap: float | None) -> jnp.ndarray:
    """x: (..., D) @ embed_t (D, V) with optional final softcap."""
    logits = jnp.einsum("...d,dv->...v", x, embed_t,
                        preferred_element_type=jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def chunked_ce_loss(x: jnp.ndarray, embed_t: jnp.ndarray,
                    labels: jnp.ndarray, mask: jnp.ndarray, *,
                    softcap: float | None, chunk: int = 512
                    ) -> jnp.ndarray:
    """Cross-entropy without materializing full (B, T, V) logits.

    x: (B, T, D) final hidden states; embed_t: (D, V); labels: (B, T)
    int32; mask: (B, T) float (0 = ignore).  Logits are computed one
    T-chunk at a time (lax.map) with the vocab axis sharding-constrained,
    so peak memory is (B, chunk, V/model_parallel) per device.
    """
    b, t, d = x.shape
    chunk = max(1, min(chunk, t))
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xc = x.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

    def one(args):
        xb, lb, mb = args
        logits = unembed_logits(xb, embed_t, softcap)
        logits = logical_constraint(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return ((lse - gold) * mb).sum(), mb.sum()

    losses, counts = jax.lax.map(one, (xc, lc, mc))
    return losses.sum() / jnp.maximum(counts.sum(), 1.0)


# ----------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0):
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  state: jnp.ndarray | None = None):
    """Depthwise causal conv.  x: (B, T, D); w: (W, D).

    Returns (y (B,T,D), new_state (B, W-1, D)) — state carries the last
    W-1 inputs for decode continuation.
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width))
    new_state = xp[:, xp.shape[1] - (width - 1):]
    return y.astype(x.dtype), new_state
