"""FedAvg over the reconstructable active set (paper §II-B).

    g_v^agg,r = sum_{u in A_v^r} w_u / (sum_{j in A_v^r} w_j) * g_u^r

with A_v^r = {u : C_u^r subset of C_v^r[s_max]} and |A_v^r| >= 1.  When
every update is reconstructable by the deadline, all clients compute the
*identical* aggregate — the same value as server-based FedAvg — which is
the paper's core aggregation-semantics claim.

The computation is a masked weighted reduction over stacked flat
updates.  These jnp forms serve the simulated FL runners; on a pod mesh
the torrent ring aggregates with the Pallas ``fedavg_reduce`` kernel
(``repro.dist.torrent._aggregate``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def fedavg_weights(weights: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Normalized FedAvg weights restricted to the active set."""
    w = jnp.asarray(weights, jnp.float32) * jnp.asarray(active, jnp.float32)
    total = jnp.sum(w)
    return jnp.where(total > 0, w / jnp.maximum(total, 1e-12), w)


def fedavg_flat(updates: jnp.ndarray, weights: jnp.ndarray,
                active: jnp.ndarray) -> jnp.ndarray:
    """Masked weighted average of stacked flat updates (n, D) -> (D,)."""
    wn = fedavg_weights(weights, active)
    return jnp.einsum("n,nd->d", wn, updates.astype(jnp.float32))


def fedavg_pytree(updates: list, weights, active):
    """FedAvg over a list of update pytrees (same treedef)."""
    weights = jnp.asarray(weights, jnp.float32)
    active = jnp.asarray(active, jnp.float32)
    wn = fedavg_weights(weights, active)

    def combine(*leaves):
        stacked = jnp.stack([l.astype(jnp.float32) for l in leaves])
        return jnp.einsum("n,n...->...", wn, stacked)

    return jax.tree_util.tree_map(combine, *updates)


def per_client_aggregates(updates: jnp.ndarray, weights: np.ndarray,
                          reconstructable: np.ndarray) -> jnp.ndarray:
    """Each client v aggregates over its own A_v^r: (n, D) -> (n, D).

    ``reconstructable[v, u]`` says update u is reconstructable at v by
    the deadline.  Rows with an empty active set return zeros (the
    protocol requires |A_v^r| >= 1; callers treat such clients as
    dropped for the round)."""
    recon = jnp.asarray(reconstructable, jnp.float32)        # (n, n)
    w = jnp.asarray(weights, jnp.float32)[None, :] * recon   # (n, n)
    denom = jnp.sum(w, axis=1, keepdims=True)
    wn = jnp.where(denom > 0, w / jnp.maximum(denom, 1e-12), 0.0)
    return wn @ updates.astype(jnp.float32)


def agreement_check(aggregates, atol: float = 1e-6) -> bool:
    """True when all per-client aggregates agree (full dissemination).

    Accepts a stacked (n, D) array or a list of same-treedef pytrees."""
    if isinstance(aggregates, (list, tuple)):
        flats = [jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                  for l in jax.tree_util.tree_leaves(a)])
                 for a in aggregates]
        aggregates = jnp.stack(flats)
    ref = aggregates[0]
    return bool(jnp.max(jnp.abs(aggregates - ref[None])) <= atol)
