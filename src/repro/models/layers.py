"""Layer kinds: attention (global/local/moe), RG-LRU, mLSTM, sLSTM.

Every kind implements

    init_layer(cfg, kind, key)                      -> params
    apply_layer(cfg, kind, p, x, mode, cache, pos)  -> (x, new_cache)
    init_cache(cfg, kind, batch, max_len)           -> cache pytree

``mode`` in {"train", "prefill", "decode"}: train = full-sequence, no
cache; prefill = full-sequence, returns a populated decode cache;
decode = single-token step against the cache (``pos`` = traced scalar
absolute position).  Caches for "local" layers are rolling buffers of
``window`` entries (newest last), so decode attention uses a traced
``kv_offset = pos - window + 1`` and negative key positions are masked.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.sharding.api import logical_constraint, mesh_devices

from .common import causal_conv1d, dense_init, rms_norm, rope
from .config import ArchConfig

ATTN_KINDS = ("global", "local", "moe")
RGLRU_C = 8.0          # Griffin's fixed recurrence constant


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def _chunked_scan(step, init, xs, *, chunk: int, remat: bool):
    """lax.scan over time in rematerialized chunks.

    Plain scan-of-step saves every per-step residual for backward — for
    mLSTM that is a (B, H, dh, dh) matrix PER TIMESTEP (44 GiB/device at
    4k).  Chunking the scan and checkpointing each chunk stores only the
    chunk-boundary carries and recomputes inside, the standard
    linear-RNN training memory fix.  Falls back to one chunk when the
    sequence length isn't divisible (tiny smoke shapes).
    """
    t = xs[0].shape[0]
    chunk = min(chunk, t)
    while t % chunk:
        chunk -= 1
    nb = t // chunk
    if nb <= 1:
        return jax.lax.scan(step, init, xs)
    xs_b = jax.tree_util.tree_map(
        lambda x: x.reshape((nb, chunk) + x.shape[1:]), xs)

    def outer(carry, xc):
        return jax.lax.scan(step, carry, xc)

    if remat:
        outer = jax.checkpoint(
            outer, policy=jax.checkpoint_policies.nothing_saveable)
    carry, ys = jax.lax.scan(outer, init, xs_b)
    ys = jax.tree_util.tree_map(
        lambda y: y.reshape((t,) + y.shape[2:]), ys)
    return carry, ys


# ======================================================================
# Attention layers (global / local / moe)
# ======================================================================

def _init_attn(cfg: ArchConfig, kind: str, key) -> dict:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 12)
    p = {
        "ln1": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "wq": dense_init(ks[0], (d, qd), dt),
    }
    if cfg.mla:
        r = cfg.kv_lora_rank
        p["wkv_a"] = dense_init(ks[1], (d, r + cfg.qk_rope_dim), dt)
        p["kv_norm"] = jnp.zeros((r,), dt)
        p["wkv_b"] = dense_init(
            ks[2], (r, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)), dt)
    else:
        p["wk"] = dense_init(ks[1], (d, kvd), dt)
        p["wv"] = dense_init(ks[2], (d, kvd), dt)
    p["wo"] = dense_init(ks[3], (cfg.o_dim, d), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dt)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dt)
    if cfg.post_norm:
        p["post_ln1"] = jnp.zeros((d,), dt)
        p["post_ln2"] = jnp.zeros((d,), dt)
    if kind == "moe":
        e, fe = cfg.n_held, cfg.d_expert
        p["router"] = dense_init(ks[4], (d, cfg.n_experts), jnp.float32)
        if cfg.router == "sigmoid":
            # The correction bias steers the choice of experts only; it
            # is a buffer, which the optimizer leaves as it is.
            p["buffers"] = {"router_bias": jnp.zeros((cfg.n_experts,),
                                                     jnp.float32)}
        p["moe_gate"] = dense_init(ks[5], (e, d, fe), dt, in_axis=1)
        p["moe_up"] = dense_init(ks[6], (e, d, fe), dt, in_axis=1)
        p["moe_down"] = dense_init(ks[7], (e, fe, d), dt, in_axis=1)
        if cfg.d_shared:
            fs = cfg.d_shared
            p["shared_gate"] = dense_init(ks[8], (d, fs), dt)
            p["shared_up"] = dense_init(ks[9], (d, fs), dt)
            p["shared_down"] = dense_init(ks[10], (fs, d), dt)
    else:
        f = cfg.d_ff
        p["w_gate"] = dense_init(ks[4], (d, f), dt)
        p["w_up"] = dense_init(ks[5], (d, f), dt)
        p["w_down"] = dense_init(ks[6], (f, d), dt)
    return p


def _qkv(cfg: ArchConfig, p: dict, h: jnp.ndarray, positions):
    """Queries, keys and values (B, H, T, ·), rotary embedding applied."""
    b, t, _ = h.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = (h @ p["wq"]).reshape(b, t, hq, dh).transpose(0, 2, 1, 3)
    if cfg.mla:
        # Latent attention (DeepSeek-V2 sec. 2.1), training form: keys
        # and values are expanded per head from the normed latent c_kv;
        # one rope key k_pe is shared by every head.
        nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
        kv = h @ p["wkv_a"]
        c_kv = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
        k_pe = rope(kv[..., None, r:].transpose(0, 2, 1, 3), positions,
                    cfg.rope_theta)
        kvb = (c_kv @ p["wkv_b"]).reshape(
            b, t, hq, nope + cfg.v_head_dim).transpose(0, 2, 1, 3)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_pe, (b, hq, t, cfg.qk_rope_dim))], axis=-1)
        return q, k, kvb[..., nope:]
    k = (h @ p["wk"]).reshape(b, t, hkv, dh).transpose(0, 2, 1, 3)
    v = (h @ p["wv"]).reshape(b, t, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return rope(q, positions, cfg.rope_theta), rope(k, positions,
                                                     cfg.rope_theta), v


def _attention_mix(cfg: ArchConfig, kind: str, p: dict, h: jnp.ndarray,
                   mode: str, cache: dict | None, pos):
    """Returns (attn_out (B,T,o_dim), new_cache)."""
    b, t, d = h.shape
    window = cfg.window if kind == "local" else None
    # MLA gives its scale explicitly: 1/sqrt of the q/k width.
    scale = cfg.head_dim ** -0.5 if cfg.mla else None

    if mode == "decode":
        positions = jnp.full((t,), pos, jnp.int32)
    else:
        positions = jnp.arange(t, dtype=jnp.int32)
    q, k, v = _qkv(cfg, p, h, positions)
    q = logical_constraint(q, "batch", "heads", None, None)
    k = logical_constraint(k, "batch", "kv", None, None)
    v = logical_constraint(v, "batch", "kv", None, None)

    new_cache = None
    if mode == "decode":
        assert t == 1
        if window is not None:                       # rolling buffer
            ck = jnp.concatenate([cache["k"][:, :, 1:],
                                  k.astype(cache["k"].dtype)], axis=2)
            cv = jnp.concatenate([cache["v"][:, :, 1:],
                                  v.astype(cache["v"].dtype)], axis=2)
            new_cache = {"k": ck, "v": cv}
            out = ops.attention(
                q, ck, cv, causal=True, window=window,
                softcap=cfg.attn_softcap, q_offset=pos,
                kv_offset=pos - window + 1, scale=scale,
                impl=cfg.attn_impl,
                block_q=cfg.block_q, block_k=cfg.block_k)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
            new_cache = {"k": ck, "v": cv}
            out = ops.attention(
                q, ck, cv, causal=True, window=None,
                softcap=cfg.attn_softcap, q_offset=pos, scale=scale,
                impl=cfg.attn_impl, block_q=cfg.block_q,
                block_k=cfg.block_k)
    else:
        out = ops.attention(
            q, k, v, causal=cfg.causal, window=window,
            softcap=cfg.attn_softcap, scale=scale, impl=cfg.attn_impl,
            block_q=cfg.block_q, block_k=cfg.block_k,
            devices=mesh_devices())
        if mode == "prefill":
            cdt = jnp.dtype(cfg.cache_dtype or cfg.dtype)
            if window is not None:
                w = window
                if t >= w:
                    ck, cv = k[:, :, t - w:], v[:, :, t - w:]
                else:
                    padw = ((0, 0), (0, 0), (w - t, 0), (0, 0))
                    ck, cv = jnp.pad(k, padw), jnp.pad(v, padw)
                new_cache = {"k": ck.astype(cdt), "v": cv.astype(cdt)}
            else:
                new_cache = {"k": k.astype(cdt), "v": v.astype(cdt)}

    out = out.transpose(0, 2, 1, 3).reshape(b, t, cfg.o_dim)
    return out @ p["wo"], new_cache


def flash_attention_layers(cfg: ArchConfig, t: int, backend: str,
                           devices: int = 1) -> tuple[int, int]:
    """(attention layers whose full-sequence pass over ``t`` tokens on
    a mesh of ``devices`` ``ops.attention`` runs on the causal flash
    kernels, attention layers): ``ops.flash_block`` on the arguments
    ``_attention_mix`` gives it in train and prefill mode."""
    dv = cfg.v_head_dim if cfg.mla else cfg.head_dim
    kinds = [k for k in cfg.layer_kinds if k in ATTN_KINDS]
    flash = sum(ops.flash_block(
        t, t, cfg.head_dim, dv, causal=cfg.causal,
        window=cfg.window if k == "local" else None,
        softcap=cfg.attn_softcap, q_offset=0, kv_offset=0,
        impl=cfg.attn_impl, backend=backend, devices=devices) is not None
        for k in kinds)
    return flash, len(kinds)


def _glu(cfg: ArchConfig, h: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
    act = _act(cfg.act)
    g = act(h @ wg) * (h @ wu)
    g = logical_constraint(g, "batch", None, "ffn")
    return g @ wd


def _dense_ffn(cfg: ArchConfig, p: dict, h: jnp.ndarray) -> jnp.ndarray:
    return _glu(cfg, h, p["w_gate"], p["w_up"], p["w_down"])


MOE_TOKEN_BLOCK = 8192
# Routing counts a layer returns in train mode, with how the counts of
# several layers, token blocks or microbatches combine.
MOE_STATS = {"moe_pairs": jnp.sum, "moe_max_load": jnp.max}


def merge_stats(parts) -> dict:
    """Combine routing counts: each of ``parts`` a dict of arrays."""
    parts = [q for q in parts if q]
    if not parts:
        return {}
    return {k: MOE_STATS[k](jnp.stack([jnp.ravel(q[k]) for q in parts]
                                      ).ravel()) for k in parts[0]}


def _route(cfg: ArchConfig, p: dict, x: jnp.ndarray):
    """Top-k over all ``n_experts``: (weights (n, k) f32, ids (n, k)).

    ``softmax``: the top-k probabilities.  ``sigmoid`` (DeepSeek-V3's
    ``noaux_tc`` with one group): experts chosen on the scores plus the
    correction bias, weighted by the scores alone.  Either way the
    weights are normalised and scaled by ``routed_scale``.
    """
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(p["buffers"]["router_bias"])
        _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w * cfg.routed_scale, idx


def moe_held(cfg: ArchConfig, p: dict, x: jnp.ndarray, first):
    """The part of the MoE output that the experts held here give.

    x: (n, d) tokens; ``p["moe_*"]`` hold experts ``first`` to
    ``first + e`` (``first`` may be traced).  Routing is over all
    ``n_experts``; every (token, expert) pair whose expert is held is
    computed, none is dropped.  The pairs are sorted by expert and run
    through grouped products over the held experts (``ragged_dot``).
    Their static count is bounded by ``n * min(top_k, e)``: a token
    picks an expert at most once.  Rows past the held pairs belong to
    no group and are held at zero.

    Returns (y (n, d), {"moe_pairs", "moe_max_load"}): the pairs
    computed here, and the busiest held expert's pairs over the mean.
    """
    n, d = x.shape
    k = cfg.top_k
    e = p["moe_gate"].shape[0]
    act = _act(cfg.act)
    with jax.named_scope("moe.route"):
        w, idx = _route(cfg, p, x)
    with jax.named_scope("moe.dispatch"):
        rel = idx - first
        held = (rel >= 0) & (rel < e)
        flat = jnp.where(held, rel, e).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(flat, stable=True)
        m = n * min(k, e)
        sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
        pairs = sizes.sum()
        # Rows past the held pairs are in no group.  The TPU's grouped
        # product skips their tiles and defines nothing there (a step
        # without these selects trained to NaN on the chip), so each
        # product's operand and result is selected to zero at them, in
        # the forward and the backward pass alike.
        live = (jnp.arange(m) < pairs)[:, None]

        def grouped(a, mat):
            return jnp.where(live, jax.lax.ragged_dot(a, mat, sizes), 0)

        xs = jnp.where(live, x[order[:m] // k], 0)
    with jax.named_scope("moe.experts"):
        h = act(grouped(xs, p["moe_gate"])) * grouped(xs, p["moe_up"])
        ys = grouped(h, p["moe_down"])
    with jax.named_scope("moe.combine"):
        # Each pair's row in the sorted list; pairs not held read the
        # zero row appended at m.
        pos = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
        pos = jnp.where(held.reshape(-1), pos, m)
        ys = jnp.concatenate([ys, jnp.zeros((1, d), ys.dtype)], axis=0)
        yk = ys[pos].reshape(n, k, d)
        y = jnp.einsum("nk,nkd->nd", (w * held).astype(x.dtype), yk)
    stats = {"moe_pairs": pairs,
             "moe_max_load": (sizes.max() * e
                              / jnp.maximum(pairs, 1)).astype(jnp.float32)}
    return y, stats


def _moe_ffn_shardmap(cfg: ArchConfig, p: dict, h: jnp.ndarray, mesh):
    """Expert-parallel MoE via shard_map (§Perf cell-2).

    The pjit scatter/gather dispatch has data-dependent indices, which
    XLA SPMD can only partition by replicating the token matrix (537 MB
    collective-permutes + 268 MB all-reduces per layer per microbatch at
    olmoe train_4k).  Inside shard_map the dispatch is LOCAL: every
    (data, model) shard routes its data-shard's tokens over the full
    router, computes its own expert slice's part with
    :func:`moe_held`, and one psum over ``model`` sums the parts.

    Returns None when the cell isn't divisible (falls back to the
    blocked pjit path — tiny smoke configs, odd meshes).
    """
    from jax.sharding import PartitionSpec as P

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ms = int(sizes.get("model", 1))
    b, t, d = h.shape
    n = b * t
    ds = int(sizes.get("data", 1))
    if ms <= 1 or cfg.n_held % ms or n % ds:
        return None
    if int(sizes.get("pod", 1)) > 1:
        # shard_map under the vmap-over-pods FL step trips an XLA
        # partitioner check ("invalid binary instruction opcode copy",
        # jax 0.8.2) -- multi-pod cells keep the pjit dispatch path.
        return None
    e_loc = cfg.n_held // ms
    routed = {k: p[k] for k in ("router", "buffers", "moe_gate", "moe_up",
                                "moe_down") if k in p}
    exp_spec = P("model", None, None)
    specs = {k: (exp_spec if k.startswith("moe_")
                 else jax.tree_util.tree_map(lambda _: P(), v))
             for k, v in routed.items()}

    def body(x_loc, q):
        first = cfg.expert_first + jax.lax.axis_index("model") * e_loc
        y, st = moe_held(cfg, q, x_loc, first)
        st = {"moe_pairs": jax.lax.psum(st["moe_pairs"], ("data", "model")),
              "moe_max_load": jax.lax.pmax(st["moe_max_load"],
                                           ("data", "model"))}
        return jax.lax.psum(y, "model"), st

    try:
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("data", None), specs),
            out_specs=(P("data", None), {"moe_pairs": P(),
                                         "moe_max_load": P()}),
            axis_names={"data", "model"},      # pod (if any) stays auto
            check_vma=False)
        out, stats = fn(h.reshape(n, d), routed)
    except (TypeError, NotImplementedError, ValueError):
        return None
    return out.reshape(b, t, d), stats


def _moe_ffn(cfg: ArchConfig, p: dict, h: jnp.ndarray):
    """MoE FFN: the held experts' part plus the shared experts.

    Returns (out (B, T, D), routing counts).  Dispatches to the
    shard_map expert-parallel path when a production mesh is active
    (§Perf cell-2); otherwise tokens are processed in blocks of
    ``MOE_TOKEN_BLOCK`` (lax.map + remat; routing is per token and
    nothing is dropped, so blocks are independent), which bounds the
    sorted pair buffers by the block — unblocked they were 134
    GiB/device at olmoe prefill_32k.
    """
    from repro.sharding.api import current_rules
    b, t, d = h.shape
    out = None
    state = current_rules()
    if state is not None and state[1] is not None:
        out = _moe_ffn_shardmap(cfg, p, h, state[1])
    if out is not None:
        routed, stats = out
    else:
        n = b * t
        xf = h.reshape(n, d)
        block = MOE_TOKEN_BLOCK
        while n % block:
            block //= 2
        if block >= n or block < 64:
            routed, stats = moe_held(cfg, p, xf, cfg.expert_first)
        else:
            fn = jax.checkpoint(
                lambda x_: moe_held(cfg, p, x_, cfg.expert_first),
                policy=jax.checkpoint_policies.nothing_saveable)
            routed, parts = jax.lax.map(fn, xf.reshape(n // block, block, d))
            stats = merge_stats([parts])
        routed = routed.reshape(b, t, d)
    if cfg.d_shared:
        with jax.named_scope("moe.shared"):
            routed = routed + _glu(cfg, h, p["shared_gate"], p["shared_up"],
                                   p["shared_down"])
    return routed, stats


def _apply_attn(cfg: ArchConfig, kind: str, p: dict, x: jnp.ndarray,
                mode: str, cache, pos):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("mla") if cfg.mla else contextlib.nullcontext():
        attn, new_cache = _attention_mix(cfg, kind, p, h, mode, cache, pos)
    if cfg.post_norm:
        attn = rms_norm(attn, p["post_ln1"], cfg.norm_eps)
    x = x + attn
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    stats = {}
    if kind == "moe":
        ff, stats = _moe_ffn(cfg, p, h)
    else:
        ff = _dense_ffn(cfg, p, h)
    if cfg.post_norm:
        ff = rms_norm(ff, p["post_ln2"], cfg.norm_eps)
    x = x + ff
    x = logical_constraint(x, "batch", "seq", None)
    return x, new_cache, stats


# ======================================================================
# RG-LRU (Griffin recurrent block + GeGLU FFN)
# ======================================================================

def _init_rglru(cfg: ArchConfig, key) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dr = cfg.d_rnn or d
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    # Lambda init so a = exp(-c * softplus(lam)) ~ U(0.9, 0.999) at r=1.
    u = jax.random.uniform(ks[0], (dr,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / RGLRU_C))
    return {
        "ln1": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "rg_in": dense_init(ks[1], (d, dr), dt),
        "rg_gate": dense_init(ks[2], (d, dr), dt),
        "conv_w": (jax.random.normal(ks[3], (cfg.conv_width, dr),
                                     jnp.float32)
                   * (cfg.conv_width ** -0.5)).astype(dt),
        "lam": lam,
        "a_gate_w": jnp.ones((dr,), jnp.float32),
        "i_gate_w": jnp.ones((dr,), jnp.float32),
        "rg_out": dense_init(ks[4], (dr, d), dt),
        "w_gate": dense_init(ks[5], (d, f), dt),
        "w_up": dense_init(ks[6], (d, f), dt),
        "w_down": dense_init(ks[7], (f, d), dt),
    }


def _apply_rglru(cfg: ArchConfig, p: dict, x: jnp.ndarray, mode: str,
                 cache, pos):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    xr = h @ p["rg_in"]
    xg = jax.nn.gelu(h @ p["rg_gate"])
    xr = logical_constraint(xr, "batch", None, "rnn")
    conv_state = cache["conv"] if mode == "decode" else None
    xc, new_conv = causal_conv1d(xr, p["conv_w"], conv_state)

    xcf = xc.astype(jnp.float32)
    r = jax.nn.sigmoid(xcf * p["a_gate_w"])
    i = jax.nn.sigmoid(xcf * p["i_gate_w"])
    log_a = -RGLRU_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    h0 = cache["h"] if mode == "decode" else None
    y, h_t = ops.rglru(xc, a.astype(xc.dtype), i.astype(xc.dtype), h0,
                       impl=cfg.rnn_impl)
    out = (xg * y) @ p["rg_out"]
    x = x + out
    hh = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _dense_ffn(cfg, p, hh)
    x = logical_constraint(x, "batch", "seq", None)
    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"h": h_t, "conv": new_conv}
    return x, new_cache


# ======================================================================
# mLSTM (xLSTM matrix-memory block)
# ======================================================================

def _init_mlstm(cfg: ArchConfig, key) -> dict:
    d = cfg.d_model
    di = 2 * d
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 10)
    return {
        "norm": jnp.zeros((d,), dt),
        "up_l": dense_init(ks[0], (d, di), dt),
        "up_r": dense_init(ks[1], (d, di), dt),
        "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, di),
                                     jnp.float32)
                   * (cfg.conv_width ** -0.5)).astype(dt),
        "wq_i": dense_init(ks[3], (di, di), dt),
        "wk_i": dense_init(ks[4], (di, di), dt),
        "wv_i": dense_init(ks[5], (di, di), dt),
        "wi": dense_init(ks[6], (di, cfg.rnn_heads), jnp.float32),
        "wf": dense_init(ks[7], (di, cfg.rnn_heads), jnp.float32),
        "wo_gate": dense_init(ks[8], (di, di), dt),
        "down": dense_init(ks[9], (di, d), dt),
    }


MLSTM_CHUNK = 128


def _mlstm_chunkwise(q, k, v, i_pre, f_pre, state, *, chunk: int,
                     remat: bool):
    """Chunkwise-parallel mLSTM (stabilized exponential gating).

    The sequential scan reads+writes the (B, H, dh, dh) matrix state
    every timestep — O(T * B * H * dh^2) HBM traffic that made xlstm
    train_4k ~7000x memory-bound (EXPERIMENTS.md §Perf).  The chunkwise
    form (the xLSTM paper's training mode) touches the state once per
    chunk and handles the intra-chunk part as an (L, L)-masked
    quadratic, trading a small FLOP increase for a ~chunk-factor
    reduction in state traffic.

    Derivation (per head, relative to chunk start; A = incl-cumsum f):
        m_j = A_j + M_j,          M_j = max(m0, cummax_j(i - A))
        h_j = e^{m0-M_j} C0 q_j + sum_{s<=j} W[j,s] (k_s.q_j) v_s
        W[j,s] = e^{(i_s - A_s) - M_j}
        n_j = e^{m0-M_j} n0 + sum_{s<=j} W[j,s] k_s
        den_j = max(|n_j . q_j|, 1)
    State update uses the same weights at j = L-1.  Verified against
    the per-step recurrence in tests/test_mlstm_chunkwise.py.

    q,k,v: (B, T, H, dh) (q,k pre-scaled); i_pre,f_pre: (B, T, H).
    state: (C (B,H,dh,dh), n (B,H,dh), m (B,H)).  Returns
    (state, h (B, T, H, dh)).
    """
    b, t, hh, dh = q.shape
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        # inert padding: f=0 keeps A flat, i=-inf contributes nothing
        f_pre = jnp.pad(f_pre, ((0, 0), (0, pad), (0, 0)))
        i_pre = jnp.pad(i_pre, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1e30)
    nc = (t + pad) // chunk

    def split(x):
        x = x.reshape((b, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)          # (nc, b, chunk, ...)

    qs, ks, vs = split(q), split(k), split(v)
    is_, fs = split(i_pre), split(f_pre)

    def chunk_body(state, xs):
        C0, n0, m0 = state                    # (b,h,dh,dh),(b,h,dh),(b,h)
        qc, kc, vc, ic, fc = xs               # (b,chunk,h,...)
        L = qc.shape[1]
        ic = ic.astype(jnp.float32).transpose(0, 2, 1)     # (b,h,L)
        fc = fc.astype(jnp.float32).transpose(0, 2, 1)
        qh = qc.astype(jnp.float32).transpose(0, 2, 1, 3)  # (b,h,L,dh)
        kh = kc.astype(jnp.float32).transpose(0, 2, 1, 3)
        vh = vc.astype(jnp.float32).transpose(0, 2, 1, 3)

        A = jnp.cumsum(fc, axis=-1)                      # (b,h,L)
        gia = ic - A                                      # i_s - A_s
        g = jax.lax.cummax(gia, axis=2)
        M = jnp.maximum(m0[..., None], g)                # (b,h,L)
        c_int = jnp.exp(m0[..., None] - M)               # (b,h,L)
        # W[j,s] = exp(gia_s - M_j), s <= j
        W = jnp.exp(gia[..., None, :] - M[..., :, None])
        mask = jnp.tril(jnp.ones((L, L), bool))
        W = jnp.where(mask, W, 0.0)

        scores = jnp.einsum("bhjd,bhsd->bhjs", qh, kh)
        inter_num = jnp.einsum("bhij,bhsj->bhsi", C0, qh)  # C0 q_j
        h_num = (c_int[..., None] * inter_num
                 + jnp.einsum("bhjs,bhsi->bhji", W * scores, vh))
        nj = (c_int[..., None] * n0[:, :, None, :]
              + jnp.einsum("bhjs,bhsd->bhjd", W, kh))
        den = jnp.abs(jnp.einsum("bhjd,bhjd->bhj", nj, qh))
        h = h_num / jnp.maximum(den, 1.0)[..., None]     # (b,h,L,dh)

        # end-of-chunk state
        AL = A[..., -1]
        MxL = jnp.maximum(m0, g[..., -1])                # (b,h)
        wL = jnp.exp(gia - MxL[..., None])               # (b,h,L)
        C = (jnp.exp(m0 - MxL)[..., None, None] * C0
             + jnp.einsum("bhs,bhsi,bhsj->bhij", wL, vh, kh))
        n = (jnp.exp(m0 - MxL)[..., None] * n0
             + jnp.einsum("bhs,bhsd->bhd", wL, kh))
        m = AL + MxL
        return (C, n, m), h.transpose(0, 2, 1, 3)        # (b,L,h,dh)

    body = chunk_body
    if remat:
        body = jax.checkpoint(
            chunk_body, policy=jax.checkpoint_policies.nothing_saveable)
    state, hs = jax.lax.scan(body, state, (qs, ks, vs, is_, fs))
    hs = jnp.moveaxis(hs, 0, 1).reshape(b, t + pad, hh, dh)
    if pad:
        hs = hs[:, :t]
    return state, hs


def _mlstm_step(state, inputs):
    """One mLSTM cell step (stabilized exponential gating)."""
    C, nrm, m = state
    q_t, k_t, v_t, i_pre, f_pre = inputs
    m_new = jnp.maximum(f_pre + m, i_pre)                  # (B, H)
    fi = jnp.exp(f_pre + m - m_new)
    ii = jnp.exp(i_pre - m_new)
    C = fi[..., None, None] * C + ii[..., None, None] * (
        v_t[..., :, None] * k_t[..., None, :])             # (B,H,dh,dh)
    nrm = fi[..., None] * nrm + ii[..., None] * k_t
    num = jnp.einsum("bhij,bhj->bhi", C, q_t)
    den = jnp.abs(jnp.einsum("bhj,bhj->bh", nrm, q_t))
    h = num / jnp.maximum(den, 1.0)[..., None]
    return (C, nrm, m_new), h


def _apply_mlstm(cfg: ArchConfig, p: dict, x: jnp.ndarray, mode: str,
                 cache, pos):
    b, t, d = x.shape
    di = 2 * d
    hh = cfg.rnn_heads
    dh = di // hh
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    xl = h @ p["up_l"]
    xr = jax.nn.silu(h @ p["up_r"])
    xl = logical_constraint(xl, "batch", None, "rnn")
    conv_state = cache["conv"] if mode == "decode" else None
    xc, new_conv = causal_conv1d(xl, p["conv_w"], conv_state)

    scale = dh ** -0.5
    q = (xc @ p["wq_i"]).reshape(b, t, hh, dh).astype(jnp.float32) * scale
    k = (xc @ p["wk_i"]).reshape(b, t, hh, dh).astype(jnp.float32) * scale
    v = (xl @ p["wv_i"]).reshape(b, t, hh, dh).astype(jnp.float32)
    i_pre = xc.astype(jnp.float32) @ p["wi"]               # (B,T,H)
    f_pre = xc.astype(jnp.float32) @ p["wf"] + 1.0
    o = jax.nn.sigmoid(xc @ p["wo_gate"])

    if mode == "decode":
        state = (cache["C"], cache["n"], cache["m"])
        state, hs = _mlstm_step(
            state, (q[:, 0], k[:, 0], v[:, 0], i_pre[:, 0], f_pre[:, 0]))
        hs = hs[:, None]                                   # (B,1,H,dh)->
        hs = hs.reshape(b, t, di)
    else:
        init = (jnp.zeros((b, hh, dh, dh), jnp.float32),
                jnp.zeros((b, hh, dh), jnp.float32),
                jnp.full((b, hh), -1e30, jnp.float32))
        state, hs = _mlstm_chunkwise(q, k, v, i_pre, f_pre, init,
                                     chunk=MLSTM_CHUNK,
                                     remat=(mode == "train"))
        hs = hs.reshape(b, t, di)

    y = (o * hs.astype(o.dtype)) @ p["down"]
    x = x + y
    x = logical_constraint(x, "batch", "seq", None)
    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"C": state[0], "n": state[1], "m": state[2],
                     "conv": new_conv}
    return x, new_cache


# ======================================================================
# sLSTM (xLSTM scalar-memory block)
# ======================================================================

def _init_slstm(cfg: ArchConfig, key) -> dict:
    d = cfg.d_model
    hh = cfg.rnn_heads
    dh = d // hh
    f = -(-4 * d // 3 // 128) * 128
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    return {
        "norm": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "w4": dense_init(ks[0], (d, 4 * d), jnp.float32),
        "r4": (jax.random.normal(ks[1], (hh, dh, 4 * dh), jnp.float32)
               * (dh ** -0.5)),
        "b4": jnp.zeros((hh, 4 * dh), jnp.float32),
        "w_gate": dense_init(ks[2], (d, f), dt),
        "w_up": dense_init(ks[3], (d, f), dt),
        "w_down": dense_init(ks[4], (f, d), dt),
    }


def _slstm_step(p, state, wx_t):
    """wx_t: (B, H, 4*dh) input pre-activations for one step."""
    c, n, hprev, m = state
    gates = wx_t + jnp.einsum("bhd,hde->bhe", hprev, p["r4"]) + p["b4"]
    dh = c.shape[-1]
    i_pre = gates[..., 0 * dh:1 * dh]
    f_pre = gates[..., 1 * dh:2 * dh] + 1.0
    z_pre = gates[..., 2 * dh:3 * dh]
    o_pre = gates[..., 3 * dh:4 * dh]
    m_new = jnp.maximum(f_pre + m, i_pre)
    ii = jnp.exp(i_pre - m_new)
    ff = jnp.exp(f_pre + m - m_new)
    c = ff * c + ii * jnp.tanh(z_pre)
    n = ff * n + ii
    h = jax.nn.sigmoid(o_pre) * c / jnp.maximum(n, 1.0)
    return (c, n, h, m_new), h


def _apply_slstm(cfg: ArchConfig, p: dict, x: jnp.ndarray, mode: str,
                 cache, pos):
    b, t, d = x.shape
    hh = cfg.rnn_heads
    dh = d // hh
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    wx = (h.astype(jnp.float32) @ p["w4"]).reshape(b, t, hh, 4 * dh)

    if mode == "decode":
        state = (cache["c"], cache["n"], cache["h"], cache["m"])
        state, hs = _slstm_step(p, state, wx[:, 0])
        hs = hs[:, None]
    else:
        zeros = jnp.zeros((b, hh, dh), jnp.float32)
        init = (zeros, zeros, zeros, jnp.full((b, hh, dh), -1e30,
                                              jnp.float32))
        state, hs = _chunked_scan(
            lambda s, w: _slstm_step(p, s, w[0]), init,
            (wx.swapaxes(0, 1),), chunk=256, remat=(mode == "train"))
        hs = hs.swapaxes(0, 1)
    y = hs.reshape(b, t, d).astype(x.dtype)
    x = x + y
    hh2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _dense_ffn(cfg, p, hh2)
    x = logical_constraint(x, "batch", "seq", None)
    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"c": state[0], "n": state[1], "h": state[2],
                     "m": state[3]}
    return x, new_cache


# ======================================================================
# Dispatch
# ======================================================================

def init_layer(cfg: ArchConfig, kind: str, key) -> dict:
    if kind in ATTN_KINDS:
        return _init_attn(cfg, kind, key)
    if kind == "rglru":
        return _init_rglru(cfg, key)
    if kind == "mlstm":
        return _init_mlstm(cfg, key)
    if kind == "slstm":
        return _init_slstm(cfg, key)
    raise ValueError(kind)


def apply_layer(cfg: ArchConfig, kind: str, p: dict, x: jnp.ndarray,
                mode: str = "train", cache=None, pos=None):
    """Returns (x, new_cache, stats); ``stats`` holds an MoE layer's
    routing counts (``MOE_STATS``) and is empty for other kinds."""
    if kind in ATTN_KINDS:
        return _apply_attn(cfg, kind, p, x, mode, cache, pos)
    if kind == "rglru":
        return _apply_rglru(cfg, p, x, mode, cache, pos) + ({},)
    if kind == "mlstm":
        return _apply_mlstm(cfg, p, x, mode, cache, pos) + ({},)
    if kind == "slstm":
        return _apply_slstm(cfg, p, x, mode, cache, pos) + ({},)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
               dtype=None) -> dict:
    dt = jnp.dtype(dtype or cfg.dtype)
    if kind in ATTN_KINDS:
        cdt = jnp.dtype(dtype or cfg.cache_dtype or cfg.dtype)
        size = cfg.window if kind == "local" else max_len
        shape = (batch, cfg.n_kv, size, cfg.head_dim)
        # MLA keeps each head's expanded keys and values, not the latent.
        dv = cfg.v_head_dim if cfg.mla else cfg.head_dim
        return {"k": jnp.zeros(shape, cdt),
                "v": jnp.zeros(shape[:-1] + (dv,), cdt)}
    if kind == "rglru":
        dr = cfg.d_rnn or cfg.d_model
        return {"h": jnp.zeros((batch, dr), jnp.float32),
                "conv": jnp.zeros((batch, cfg.conv_width - 1, dr), dt)}
    if kind == "mlstm":
        di = 2 * cfg.d_model
        hh = cfg.rnn_heads
        dh = di // hh
        return {"C": jnp.zeros((batch, hh, dh, dh), jnp.float32),
                "n": jnp.zeros((batch, hh, dh), jnp.float32),
                "m": jnp.full((batch, hh), -1e30, jnp.float32),
                "conv": jnp.zeros((batch, cfg.conv_width - 1, di), dt)}
    if kind == "slstm":
        hh = cfg.rnn_heads
        dh = cfg.d_model // hh
        z = jnp.zeros((batch, hh, dh), jnp.float32)
        return {"c": z, "n": z, "h": z,
                "m": jnp.full((batch, hh, dh), -1e30, jnp.float32)}
    raise ValueError(kind)
