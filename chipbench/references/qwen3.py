"""Plain float32 reference of a Qwen3 dense decoder and its AdamW steps.

Straight ``jax.numpy`` at ``precision=HIGHEST``, no kernels, no cache,
no sharding; it imports nothing of the program.  It follows the Qwen3
description (hf.co/Qwen/Qwen3-1.7B): RMSNorm before attention and MLP,
grouped-query attention with per-head RMSNorm on queries and keys
before rotary embedding (rotate-half form, base ``rope_theta``), a
SwiGLU MLP, a final RMSNorm and an output head tied to the embedding,
trained on mean next-token cross entropy.  Two departures, both to
match the system under test, which states them:

* every RMSNorm weight is held as ``1 + scale`` (the program stores the
  offset ``scale``; the function is the same, and so is the gradient);
* token embeddings are multiplied by ``sqrt(hidden_size)`` before the
  first layer, as the program does for every model; Qwen3 does not.

Weights come from :func:`init_weights`, a function of the seed alone,
so the program and this reference start from the same numbers without
either taking them from the other.  Memory is kept to one chip: each
layer is rematerialized, sequences are taken one at a time, the
logits 512 positions at a time, and AdamW's moments wait in host
memory while a gradient is taken.

The control computes the same in a lower precision: ``dtype`` rounds
both operands of every matrix product to that type first.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln2",
              "w_gate", "w_up", "w_down")
NORM_KEYS = ("ln1", "ln2", "q_norm", "k_norm")


def shapes(c: dict) -> dict:
    """Leaf shapes of one layer for config ``c`` (HF key names)."""
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    f = c["intermediate_size"]
    return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (q, d), "ln2": (d,),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence(int(seed) & (2 ** 64 - 1))
               .generate_state(1)[0] & 0x7FFFFFFF)


def weight_key(seed: int):
    return jax.random.key(seed32(seed))


def init_weights(c: dict, n_layers: int, key) -> dict:
    """Weights from ``weight_key(seed)``, as float32 values that
    bfloat16 holds exactly: matrices N(0, 1/fan_in), the embedding
    N(0, 1/d), norm offsets N(0, 0.1^2).  Jit it, the key an argument,
    for one device call that every seed shares."""
    d, V = c["hidden_size"], c["vocab_size"]
    k_emb, k_fin, k_lay = jax.random.split(key, 3)

    def rnd(k, shape, std):
        x = jax.random.normal(k, shape, jnp.float32) * std
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    layers = []
    for i, kl in enumerate(jax.random.split(k_lay, n_layers)):
        del i
        ks = dict(zip(LAYER_KEYS, jax.random.split(kl, len(LAYER_KEYS))))
        lay = {}
        for name, shp in shapes(c).items():
            std = 0.1 if name in NORM_KEYS else shp[0] ** -0.5
            lay[name] = rnd(ks[name], shp, std)
        layers.append(lay)
    return {"embed": rnd(k_emb, (V, d), d ** -0.5),
            "final_norm": rnd(k_fin, (d,), 0.1),
            "layers": layers}


def leaf_names(n_layers: int) -> list[str]:
    return (["embed", "final_norm"]
            + [f"layers.{i}.{k}" for i in range(n_layers) for k in LAYER_KEYS])


def leaf_norms(w: dict) -> jnp.ndarray:
    """L2 norm of every leaf, in :func:`leaf_names` order."""
    vals = [w["embed"], w["final_norm"]]
    for lay in w["layers"]:
        vals += [lay[k] for k in LAYER_KEYS]
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v))) for v in vals])


# ----------------------------------------------------------------------
# forward and loss
# ----------------------------------------------------------------------

def _mm(a, b, dtype):
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    t, dh = x.shape[-3], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(c, lay, x, dtype):
    """One decoder layer on one sequence ``x`` (T, d)."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    T = x.shape[0]
    h = _rms(x, lay["ln1"], eps)
    q = _mm(h, lay["wq"], dtype).reshape(T, H, dh)
    k = _mm(h, lay["wk"], dtype).reshape(T, KV, dh)
    v = _mm(h, lay["wv"], dtype).reshape(T, KV, dh)
    q = _rope(_rms(q, lay["q_norm"], eps), theta)
    k = _rope(_rms(k, lay["k_norm"], eps), theta)
    g = H // KV
    q = q.reshape(T, KV, g, dh)
    if dtype is not None:
        q, k, v = (a.astype(dtype).astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if dtype is not None:
        p = p.astype(dtype).astype(jnp.float32)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI).reshape(T, H * dh)
    x = x + _mm(o, lay["wo"], dtype)
    h = _rms(x, lay["ln2"], eps)
    a = jax.nn.silu(_mm(h, lay["w_gate"], dtype)) * _mm(h, lay["w_up"], dtype)
    return x + _mm(a, lay["w_down"], dtype)


def _seq_loss_sum(c, w, tokens, labels, dtype, chunk=512):
    """Summed next-token cross entropy of one sequence."""
    x = w["embed"][tokens] * math.sqrt(c["hidden_size"])
    for lay in w["layers"]:
        x = jax.checkpoint(functools.partial(_layer, c, dtype=dtype))(lay, x)
    x = _rms(x, w["final_norm"], c["rms_norm_eps"])
    T, d = x.shape
    chunk = min(chunk, T)

    @jax.checkpoint
    def piece(tot, xl):
        xb, lb = xl
        logits = _mm(xb, w["embed"].T, dtype)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(piece, jnp.float32(0.0),
                          (x.reshape(T // chunk, chunk, d),
                           labels.reshape(T // chunk, chunk)))
    return tot


def loss_fn(c, w, inputs, labels, dtype=None):
    """Mean next-token cross entropy over a (B, T) batch, one sequence
    at a time."""
    def one(tot, xy):
        return tot + _seq_loss_sum(c, w, xy[0], xy[1], dtype), None

    tot, _ = jax.lax.scan(jax.checkpoint(one), jnp.float32(0.0),
                          (inputs, labels))
    return tot / (inputs.shape[0] * inputs.shape[1])


# ----------------------------------------------------------------------
# AdamW steps
# ----------------------------------------------------------------------

def _global_norm(g):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(g)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(w, m, v, g, step, lr, b1, b2, eps, wd, clip):
    scale = jnp.minimum(1.0, clip / jnp.maximum(_global_norm(g), 1e-12))
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step

    lw, tree = jax.tree_util.tree_flatten(w)
    new = []
    for w_, m_, v_, g_ in zip(lw, *(jax.tree_util.tree_leaves(t)
                                   for t in (m, v, g))):
        g_ = g_ * scale
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        w_ = w_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * w_)
        new.append((w_, m_, v_))
    w, m, v = (jax.tree_util.tree_unflatten(tree, [x[i] for x in new])
               for i in range(3))
    return w, m, v, scale


_leaf_norms = jax.jit(leaf_norms)


@jax.jit
def _delta_norms(a, b):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b))


@functools.lru_cache(maxsize=8)
def _programs(c_json: str, n_layers: int, dtype):
    """The jitted weight generator and gradient of a config, built once
    per process."""
    c = json.loads(c_json)
    gen = jax.jit(functools.partial(init_weights, c, n_layers))
    vg = jax.jit(jax.value_and_grad(
        functools.partial(loss_fn, c, dtype=dtype), argnums=0))
    return gen, vg


def run_steps(c: dict, seed: int, n_layers: int, batches, opt: dict,
              dtype=None, steps: int = 3) -> dict:
    """``steps`` AdamW steps from the seed's weights on ``batches``
    (a list of ``(inputs, labels)`` device arrays).

    Returns, as host arrays: each step's loss; each leaf's norm of the
    first gradient as the optimizer takes it (clipped) and unclipped;
    and each leaf's norm of the weights' change after the last step.
    """
    with jax.default_matmul_precision("highest"):
        gen, vg = _programs(json.dumps(c, sort_keys=True), n_layers, dtype)
        w = gen(weight_key(seed))
        m = v = None
        losses, first = [], None
        for t in range(steps):
            inp, lab = batches[t]
            # The moments wait on the host while the gradient is taken:
            # weights, gradient and moments together do not fit a chip.
            held = None if m is None else jax.device_get((m, v))
            m = v = None
            loss, g = vg(w, inp, lab)
            losses.append(float(loss))
            if held is None:
                m = jax.tree_util.tree_map(jnp.zeros_like, w)
                v = jax.tree_util.tree_map(jnp.zeros_like, w)
            else:
                m, v = jax.device_put(held)
                del held
            w, m, v, scale = _adamw(
                w, m, v, g, jnp.float32(t + 1), jnp.float32(opt["lr"]),
                jnp.float32(opt["b1"]), jnp.float32(opt["b2"]),
                jnp.float32(opt["eps"]), jnp.float32(opt["weight_decay"]),
                jnp.float32(opt["clip_norm"]))
            if t == 0:
                raw = np.asarray(_leaf_norms(g), np.float64)
                first = (raw * float(scale), raw)
            del g
        del m, v
        w0 = gen(weight_key(seed))
        delta = np.asarray(_delta_norms(w, w0), np.float64)
        del w, w0
    return {"losses": np.asarray(losses), "grad": first[0],
            "grad_raw": first[1], "delta": delta}
