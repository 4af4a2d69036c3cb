"""Torrent collective: chunked ring dissemination + masked FedAvg.

``torrent_fedavg`` is the multi-device form of the paper's round
pipeline (§II-B): every client ships its *full* update to every other
client as fixed-size chunks, then each client aggregates over the
active set it reconstructed.  On the ``pod`` mesh axis that becomes

    stage s in 1..P-1:   pod p sends its circulating update copy to
                         pod (p+1) mod P, one ``ppermute`` per block
                         (the chunk; ``n_blocks`` explicit sends)
    after P-1 stages:    every pod holds all P updates (the paper's
                         "reconstructable set" with a generous
                         deadline = the full swarm)
    on-pod aggregate:    masked FedAvg  sum_u m_u w_u x_u / sum_u m_u w_u
                         over each block's stacked (P, D/n_blocks)
                         buffer as soon as the block has gone round —
                         the ``kernels.fedavg.fedavg_reduce`` hot path.

Mapping to the paper's dissemination schedule: a ring stage is one
round-trip slot of the BT schedule with a full-rate pipe — each pod
*seeds* its own update and *relays* the one it received last stage, so
after P-1 stages chunk ownership is all-ones, exactly the terminal
state of the simulator's ``SwarmState``.  Splitting each stage into
``n_blocks`` independent ``ppermute`` sends is the chunking: the lowered
HLO contains (P-1) x n_blocks (x2 with the scales, when compressed)
``collective-permute`` ops, so the XLA scheduler can overlap block k's
sends with block k-1's aggregate the same way the BT pipeline overlaps
chunk transfers, and a pod never holds more than one block of all P
updates at once.

Wire compression (``compress=True``): each block is quantized int8 +
one f32 scale per block *once at its source* and the codes circulate
losslessly — receivers dequantize to accumulate, so quantization error
is one rounding per element (<2% relative), not per-hop.  Every pod
dequantizes its own blocks through the same path, keeping the aggregate
bit-identical across pods.

Zero active mass (``sum_u m_u w_u == 0``, e.g. every pod failed the
deadline) returns zeros, never NaN — the caller's apply step then
leaves params unchanged.

Single-device fallback: when ``mesh`` is None or has no ``pod`` axis of
matching size, ``torrent_fedavg`` aggregates the (optionally
quantize-roundtripped) blocks directly — the ring's provable terminal
state.  ``ring_allgather_emulated`` implements the full stage/roll
arithmetic on one device so tier-1 tests can check that terminal state
(every dest reconstructs every source, all dests agree) without the
multi-device harness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.fedavg import fedavg_reduce, masked_normalized_weights
from repro.kernels.ref import chunk_dequantize, chunk_quantize
from repro.kernels.ref import fedavg_reduce as fedavg_reduce_ref

# Normalized FedAvg weights; all-zero (not NaN) when no active mass.
masked_weights = masked_normalized_weights


def _flatten_updates(updates, n_blocks: int):
    """Pytree of (P, ...) leaves -> ((P, n_blocks, db) f32, meta)."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    p = leaves[0].shape[0]
    for l in leaves:
        if l.shape[0] != p:
            raise ValueError("all update leaves need the same leading "
                             f"(client) axis; got {l.shape[0]} vs {p}")
    shapes = [l.shape[1:] for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(p, -1).astype(jnp.float32) for l in leaves], axis=1)
    d = flat.shape[1]
    db = -(-d // n_blocks)
    pad = n_blocks * db - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    blocks = flat.reshape(p, n_blocks, db)
    return blocks, (treedef, shapes, dtypes, d)


def _unflatten(vec: jnp.ndarray, meta):
    treedef, shapes, dtypes, d = meta
    vec = vec.reshape(-1)[:d]
    out, off = [], 0
    for shp, dt in zip(shapes, dtypes):
        size = int(np.prod(shp, dtype=np.int64)) if shp else 1
        out.append(vec[off:off + size].reshape(shp).astype(dt))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _pod_size(mesh) -> int:
    return 1 if mesh is None else int(mesh.shape.get("pod", 1))


def _aggregate(flat: jnp.ndarray, weights: jnp.ndarray,
               active: jnp.ndarray) -> jnp.ndarray:
    """On-pod masked FedAvg over the gathered (P, D) buffer.

    Zero-weight rows are selected out (not multiplied) so a pod that
    was masked *because* it diverged (NaN update) cannot poison the
    aggregate via 0 * NaN.
    """
    if jax.default_backend() == "tpu":
        return fedavg_reduce(flat, weights, active)
    return fedavg_reduce_ref(flat, weights, active)


def ring_allgather_emulated(blocks: jnp.ndarray, *, compress: bool = False
                            ) -> jnp.ndarray:
    """Single-device emulation of the P-1 stage ring.

    blocks: (P, n_blocks, db).  Returns gathered[dest, src, block, e] —
    exactly the buffer each pod holds after the ring, so tests can
    assert all-dest agreement without the subprocess harness.
    """
    p, n_blocks, db = blocks.shape
    if compress:
        q, s = chunk_quantize(blocks.reshape(p * n_blocks, db))
        buf_q = q.reshape(p, n_blocks, db)
        buf_s = s.reshape(p, n_blocks, 1)
    else:
        buf = blocks
    gathered = jnp.zeros((p,) + blocks.shape, jnp.float32)
    dest = jnp.arange(p)
    for stage in range(p):
        if compress:
            payload = chunk_dequantize(
                buf_q.reshape(p * n_blocks, db),
                buf_s.reshape(p * n_blocks, 1)).reshape(p, n_blocks, db)
        else:
            payload = buf
        gathered = gathered.at[dest, (dest - stage) % p].set(payload)
        if stage < p - 1:
            # every pod forwards to pod+1 == roll by +1 on the pod axis
            if compress:
                buf_q = jnp.roll(buf_q, 1, axis=0)
                buf_s = jnp.roll(buf_s, 1, axis=0)
            else:
                buf = jnp.roll(buf, 1, axis=0)
    return gathered


def _ring_device_body(p: int, n_blocks: int, compress: bool):
    """shard_map body: local (1, n_blocks, db) -> gathered aggregate.

    The ring runs block by block: each block circulates through its
    P-1 stages and is aggregated before the next one starts, so a pod
    holds one gathered (P, db) block at a time instead of all P full
    updates.  Every output element is the same P-row reduction either
    way, so the aggregate does not depend on the blocking.
    """
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(xb, weights, active):
        my = xb[0]                                   # (n_blocks, db)
        idx = jax.lax.axis_index("pod")
        if compress:
            codes, scales = chunk_quantize(my)       # int8 codes + scales
        out = []
        for b in range(n_blocks):
            if compress:
                buf_q, buf_s = codes[b:b + 1], scales[b:b + 1]
            else:
                buf = my[b:b + 1]
            gathered = jnp.zeros((p, my.shape[1]), jnp.float32)
            src = idx
            for stage in range(p):
                payload = (chunk_dequantize(buf_q, buf_s) if compress
                           else buf)
                gathered = jax.lax.dynamic_update_slice(
                    gathered, payload.astype(jnp.float32), (src, 0))
                if stage < p - 1:
                    # one explicit collective-permute per block per
                    # stage = the paper's chunked sends
                    # ((P-1) x n_blocks total)
                    if compress:
                        buf_q = jax.lax.ppermute(buf_q, "pod", perm)
                        buf_s = jax.lax.ppermute(buf_s, "pod", perm)
                    else:
                        buf = jax.lax.ppermute(buf, "pod", perm)
                    src = (src - 1) % p
            out.append(_aggregate(gathered, weights, active))
        return jnp.concatenate(out)

    return body


def take_pods(tree, keep):
    """Slice the leading (pod) axis of every leaf to the surviving pods.

    The elastic re-mesh companion (§III-E): when the active pod count
    changes between rounds, the new P'-ring runs over
    ``take_pods(updates, keep)`` with (P',) weights/active — and its
    aggregate equals the old P-ring with the departed pods masked
    (``active=0``), because masked FedAvg weights renormalize over the
    same surviving mass.  Asserted in tests/test_session.py.
    """
    keep = jnp.asarray(keep, dtype=jnp.int32)
    return jax.tree_util.tree_map(
        lambda l: jnp.take(l, keep, axis=0), tree)


def torrent_fedavg(updates, weights: jnp.ndarray, active: jnp.ndarray, *,
                   mesh=None, n_blocks: int = 4, compress: bool = False):
    """Masked FedAvg of per-pod updates via the torrent ring.

    updates: pytree whose leaves have leading axis P (stacked per-pod
    updates); weights, active: (P,).  Returns the aggregate pytree with
    the leading axis removed — identical on every pod.
    """
    blocks, meta = _flatten_updates(updates, n_blocks)
    p = blocks.shape[0]
    pod = _pod_size(mesh)
    if pod > 1 and pod != p:
        raise ValueError(f"updates leading axis {p} != pod axis size {pod}")
    if pod > 1:
        ring = jax.shard_map(
            _ring_device_body(p, n_blocks, compress), mesh=mesh,
            in_specs=(P("pod", None, None), P(None), P(None)),
            out_specs=P(None),
            check_vma=False)
        agg = ring(blocks, jnp.asarray(weights), jnp.asarray(active))
    else:
        # Single-device path: after the ring every dest holds exactly
        # the (optionally quantize-roundtripped) source blocks — see
        # test_ring_emulation_every_dest_reconstructs_all — so skip the
        # O(P^2) stage unroll and aggregate the blocks directly.
        if compress:
            nb, db = blocks.shape[1:]
            q, s = chunk_quantize(blocks.reshape(p * nb, db))
            blocks = chunk_dequantize(q, s).reshape(p, nb, db)
        agg = _aggregate(blocks.reshape(p, -1), weights, active)
    return _unflatten(agg, meta)
