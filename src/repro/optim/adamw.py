"""AdamW with fp32 master weights + global-norm clipping (pure JAX).

Mixed-precision layout (MaxText-style): model params live in the model
dtype (bf16); the optimizer keeps fp32 master weights and fp32 (m, v)
moments.  Under the sharding rules all four trees share the same
PartitionSpecs, so optimizer state is ZeRO-sharded wherever params are.

Leaves under a ``buffers`` key (an MoE router's correction bias) are
state the model reads but does not train: the update leaves them, their
master copy and their moments as they are, weight decay included.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jnp.ndarray          # () int32
    master: dict               # fp32 copy of params
    m: dict
    v: dict


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree_util.tree_leaves(tree)))


def adamw_init(params) -> OptState:
    def f32(t):
        # Always a fresh buffer, even for f32 params: the train step
        # donates params and optimizer state, and a master that aliased
        # its param would be donated twice.
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32, copy=True), t)
    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)
    return OptState(step=jnp.zeros((), jnp.int32), master=f32(params),
                    m=zeros, v=jax.tree_util.tree_map(jnp.copy, zeros))


def adamw_update(grads, state: OptState, params, *, lr,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, clip_norm: float = 1.0):
    """Returns (new_params, new_state).  ``lr`` may be a scalar or a
    schedule value computed from ``state.step`` by the caller."""
    step = state.step + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    return _repack(params, grads, state, lr, b1, b2, eps, weight_decay,
                   scale, bc1, bc2, step)


def _is_buffer(path) -> bool:
    return any(getattr(k, "key", None) == "buffers" for k in path)


def _repack(params, grads, state, lr, b1, b2, eps, weight_decay, scale,
            bc1, bc2, step):
    paths_g, treedef = jax.tree_util.tree_flatten_with_path(grads)
    leaves_g = [g for _, g in paths_g]
    leaves_m = treedef.flatten_up_to(state.m)
    leaves_v = treedef.flatten_up_to(state.v)
    leaves_w = treedef.flatten_up_to(state.master)
    leaves_p = treedef.flatten_up_to(params)
    new_m, new_v, new_w, new_p = [], [], [], []
    for (path, g), m, v, w, pp in zip(paths_g, leaves_m, leaves_v,
                                      leaves_w, leaves_p):
        if _is_buffer(path):
            new_m.append(m)
            new_v.append(v)
            new_w.append(w)
            new_p.append(pp)
            continue
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        w = w - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * w)
        new_m.append(m)
        new_v.append(v)
        new_w.append(w)
        new_p.append(w.astype(pp.dtype))
    unf = treedef.unflatten
    return unf(new_p), OptState(step=step, master=unf(new_w),
                                m=unf(new_m), v=unf(new_v))
