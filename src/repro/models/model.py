"""Unified LM: init / forward / train loss / prefill / decode.

Layers are grouped into ``n_cycles`` repetitions of the config's
``pattern`` plus an unrolled tail; the cycle params are *stacked* on a
leading axis and applied with ``lax.scan`` so HLO size is O(pattern),
not O(n_layers) — this is what keeps 512-way SPMD compiles of the 34B
configs tractable.  ``cfg.remat`` wraps the cycle body in
``jax.checkpoint`` (layer-boundary activation checkpointing).

Params tree:
    embed / adapter_in+head (hubert)   — input/output embeddings
    lead   = [per-layer params]        — the first_dense leading layers
                                         (only when first_dense > 0)
    cycles = {"slot<i>": stacked params (leading dim n_cycles)}
    tail   = [per-layer params]        — the rest of the pattern
    final_norm

Caches mirror the same structure (lead list + stacked per slot + tail
list).  In train mode the layers' routing counts (MoE) come out beside
the loss: ``train_loss(..., with_stats=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.sharding.api import logical_constraint

from .common import chunked_ce_loss, embed_tokens, rms_norm, unembed_logits
from .config import ArchConfig
from .layers import apply_layer, init_cache, init_layer, merge_stats


def init_params(cfg: ArchConfig, key) -> dict:
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 4)
    lead_keys = keys[cfg.n_layers - cfg.first_dense + 2:]
    p: dict = {}
    if cfg.has_embedding:
        p["embed"] = (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                        jnp.float32)
                      * (cfg.d_model ** -0.5)).astype(dt)
        if not cfg.tie_embeddings:
            p["head"] = (jax.random.normal(keys[1],
                                           (cfg.d_model, cfg.vocab),
                                           jnp.float32)
                         * (cfg.d_model ** -0.5)).astype(dt)
    else:
        p["adapter_in"] = (jax.random.normal(
            keys[0], (cfg.d_model, cfg.d_model), jnp.float32)
            * (cfg.d_model ** -0.5)).astype(dt)
        p["head"] = (jax.random.normal(keys[1], (cfg.d_model, cfg.vocab),
                                       jnp.float32)
                     * (cfg.d_model ** -0.5)).astype(dt)
    p["final_norm"] = jnp.zeros((cfg.d_model,), dt)

    nc = cfg.n_cycles
    plen = len(cfg.pattern)
    cycles: dict = {}
    for i, kind in enumerate(cfg.pattern):
        per = [init_layer(cfg, kind, keys[2 + c * plen + i])
               for c in range(nc)]
        cycles[f"slot{i}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per) if nc > 1 else \
            jax.tree_util.tree_map(lambda x: x[None], per[0])
    p["cycles"] = cycles
    tail = []
    base = 2 + nc * plen
    for j, kind in enumerate(cfg.tail_kinds):
        tail.append(init_layer(cfg, kind, keys[base + j]))
    p["tail"] = tail
    if cfg.first_dense:
        p["lead"] = [init_layer(cfg, kind, lead_keys[j])
                     for j, kind in enumerate(cfg.lead_kinds)]
    return p


def _embed_inputs(cfg: ArchConfig, p: dict, inputs) -> jnp.ndarray:
    if cfg.has_embedding:
        return embed_tokens(p["embed"], inputs, cfg.d_model,
                            cfg.embed_scale)
    x = inputs.astype(jnp.dtype(cfg.dtype)) @ p["adapter_in"]
    return logical_constraint(x, "batch", "seq", None)


def _run_layers(cfg: ArchConfig, p: dict, x: jnp.ndarray, mode: str,
                caches: dict | None, pos):
    """Leading layers, scan over cycles, unrolled tail.

    Returns (x, new_caches, stats): in train mode ``stats`` holds the
    routing counts of every MoE layer, combined (``merge_stats``)."""
    remat = cfg.remat and mode == "train"
    new_caches = None if mode == "train" else {"cycles": None, "tail": []}
    stats = []

    def unrolled(group, kinds, x, remat_each):
        for j, kind in enumerate(kinds):
            c_in = None if caches is None else caches[group][j]
            fn = functools.partial(apply_layer, cfg, kind, mode=mode,
                                   cache=c_in, pos=pos)
            if remat_each:
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.nothing_saveable)
            x, c_out, st = fn(p[group][j], x)
            stats.append(st)
            if new_caches is not None:
                new_caches.setdefault(group, []).append(c_out)
        return x

    if cfg.first_dense:
        x = unrolled("lead", cfg.lead_kinds, x, remat)

    def cycle_body(carry, xs):
        h = carry
        cyc_params, cyc_caches = xs
        new_cyc, cyc_stats = [], []
        for i, kind in enumerate(cfg.pattern):
            c_in = None if cyc_caches is None else cyc_caches[f"slot{i}"]
            h, c_out, st = apply_layer(cfg, kind, cyc_params[f"slot{i}"], h,
                                       mode, c_in, pos)
            new_cyc.append(c_out)
            cyc_stats.append(st)
        if mode == "train":
            return h, merge_stats(cyc_stats)
        return h, {f"slot{i}": c for i, c in enumerate(new_cyc)}

    body = cycle_body
    if remat:
        body = jax.checkpoint(cycle_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if mode == "train":
        # scan needs a matching-None xs pytree; pass params only.
        x, cyc_stats = jax.lax.scan(lambda c, cp: body(c, (cp, None)),
                                    x, p["cycles"])
        stats.append(cyc_stats)
    else:
        xs = (p["cycles"], None if caches is None else caches["cycles"])
        x, new_caches["cycles"] = jax.lax.scan(body, x, xs)

    x = unrolled("tail", cfg.tail_kinds, x, False)
    return x, new_caches, merge_stats(stats)


def _head_matrix(cfg: ArchConfig, p: dict) -> jnp.ndarray:
    if cfg.has_embedding and cfg.tie_embeddings:
        return p["embed"].T
    return p["head"]


def forward(cfg: ArchConfig, p: dict, inputs) -> jnp.ndarray:
    """Full-sequence logits (small-vocab / test use; see train_loss)."""
    x = _embed_inputs(cfg, p, inputs)
    x, _, _ = _run_layers(cfg, p, x, "train", None, None)
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    return unembed_logits(x, _head_matrix(cfg, p), cfg.final_softcap)


def train_loss(cfg: ArchConfig, p: dict, inputs, labels,
               mask=None, ce_chunk: int = 512, with_stats: bool = False):
    """Mean next-token (or masked-prediction) CE loss.

    inputs: (B, T) int tokens, or (B, T, D) frame embeddings when
    ``cfg.has_embedding`` is False.  labels: (B, T) int.  With
    ``with_stats`` returns (loss, stats): the MoE layers' routing
    counts (``layers.MOE_STATS``), empty for a model without them.
    """
    x = _embed_inputs(cfg, p, inputs)
    x, _, stats = _run_layers(cfg, p, x, "train", None, None)
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    loss = chunked_ce_loss(x, _head_matrix(cfg, p), labels, mask,
                           softcap=cfg.final_softcap, chunk=ce_chunk)
    return (loss, stats) if with_stats else loss


def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    nc = cfg.n_cycles
    cycles = {}
    for i, kind in enumerate(cfg.pattern):
        one = init_cache(cfg, kind, batch, max_len)
        cycles[f"slot{i}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (nc,) + x.shape), one)
    tail = [init_cache(cfg, kind, batch, max_len)
            for kind in cfg.tail_kinds]
    out = {"cycles": cycles, "tail": tail}
    if cfg.first_dense:
        out["lead"] = [init_cache(cfg, kind, batch, max_len)
                       for kind in cfg.lead_kinds]
    return out


def prefill(cfg: ArchConfig, p: dict, inputs, max_len: int):
    """Run the prompt, return (logits_last (B, V), caches).

    Attention caches are allocated at ``max_len`` and the first T
    entries populated; recurrent caches carry (h, conv) state.
    """
    assert cfg.causal, "prefill/decode only for causal LMs"
    b, t = inputs.shape[:2]
    x = _embed_inputs(cfg, p, inputs)
    x, caches, _ = _run_layers(cfg, p, x, "prefill", None, None)
    if max_len > t:
        # Grow global-attention KV caches from T to max_len entries.
        caches = _grow_caches(cfg, caches, t, max_len)
    x = rms_norm(x[:, -1:], p["final_norm"], cfg.norm_eps)
    logits = unembed_logits(x[:, 0], _head_matrix(cfg, p),
                            cfg.final_softcap)
    return logits, caches


def _grow_caches(cfg: ArchConfig, caches: dict, t: int, max_len: int):
    """Pad global-attention KV caches from t to max_len entries."""
    def fix(kind, cache, stacked):
        if kind in ("global", "moe") and cache is not None:
            ax = 3 if stacked else 2

            def pad_leaf(x):
                pw = [(0, 0)] * x.ndim
                pw[ax] = (0, max_len - t)
                return jnp.pad(x, pw)

            return jax.tree_util.tree_map(pad_leaf, cache)
        return cache
    out = {"cycles": {}, "tail": []}
    for i, kind in enumerate(cfg.pattern):
        out["cycles"][f"slot{i}"] = fix(kind, caches["cycles"][f"slot{i}"],
                                        True)
    for j, kind in enumerate(cfg.tail_kinds):
        out["tail"].append(fix(kind, caches["tail"][j], False))
    if cfg.first_dense:
        out["lead"] = [fix(kind, caches["lead"][j], False)
                       for j, kind in enumerate(cfg.lead_kinds)]
    return out


def decode_step(cfg: ArchConfig, p: dict, caches: dict, tokens, pos):
    """One decode step.  tokens: (B,) int; pos: scalar int32 (traced).

    Returns (logits (B, V), new_caches).
    """
    assert cfg.causal
    x = _embed_inputs(cfg, p, tokens[:, None])
    x, new_caches, _ = _run_layers(cfg, p, x, "decode", caches, pos)
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    logits = unembed_logits(x[:, 0], _head_matrix(cfg, p),
                            cfg.final_softcap)
    return logits, new_caches


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
