"""Plain float32 reference of a Moonlight-16B-A3B (DeepSeek-V3) stage
and its AdamW steps.

Straight ``jax.numpy`` at ``precision=HIGHEST``, no kernels, no cache,
no sharding, no grouped products; it imports nothing of the program.
It follows the model's config.json (hf.co/moonshotai/Moonlight-16B-A3B,
``model_type`` deepseek_v3) and the DeepSeek-V3 description:

* RMSNorm before attention and before the MLP, a final RMSNorm and an
  untied output head, mean next-token cross entropy;
* latent attention (MLA) without a query latent: ``q = h Wq`` per head
  (128 wide without rope, 64 with); ``h Wkv_a`` gives a 512-wide latent,
  RMS-normed, and one 64-wide rope key shared by the heads; the latent
  times ``Wkv_b`` gives each head's 128-wide key part and 128-wide
  value; softmax scale 1/sqrt(192), causal;
* the first ``first_k_dense_replace`` layers have a SwiGLU MLP of width
  ``intermediate_size``; the others a mixture of experts: router logits
  in float32 over all ``router_outputs`` experts, sigmoid scores, the
  top ``num_experts_per_tok`` chosen on the scores plus a correction
  bias, weighted by their scores divided by the chosen scores' sum,
  times ``routed_scaling_factor``; each chosen expert is a SwiGLU of
  width ``moe_intermediate_size``; ``n_shared_experts`` shared experts,
  one SwiGLU of their summed width, take every token.

The configuration holds this chip's share: experts ``experts_first`` to
``experts_first + n_routed_experts`` of the router's outputs, and the
vocabulary's first ``vocab_size`` ids.  Experts that are not held add
nothing here, as in the program.  Each held expert is computed for
every token and weighted by its (zero where unchosen) routing weight:
no sorting, no grouping, nothing dropped.

Departures, each as the system under test has them:

* every RMSNorm weight is held as ``1 + scale``;
* rope is half-split (rotate-half), not DeepSeek's interleaved form, a
  fixed permutation of the rope columns of ``wq`` and ``wkv_a``;
* the sequence-wise balance loss (``seq_aux``) is left out: the config
  gives it no coefficient;
* the correction bias is held fixed, not updated from expert load.

Weights come from :func:`init_weights`, a function of the seed alone.
Memory is kept to one chip: each layer and each block of 1024 queries
(against all keys, the later ones masked) is rematerialized, sequences
are taken one at a time, the logits 512
positions at a time, and AdamW's moments wait in host memory while a
gradient is taken.  The control computes the same in a lower
precision: ``dtype`` rounds both operands of every matrix product to
that type first.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ATTN_KEYS = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln2")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("router", "moe_gate", "moe_up", "moe_down",
                        "shared_gate", "shared_up", "shared_down")
NORM_KEYS = ("ln1", "ln2", "kv_norm")
Q_BLOCK = 1024


def layer_keys(c: dict, i: int) -> tuple[str, ...]:
    return DENSE_KEYS if i < c["first_k_dense_replace"] else MOE_KEYS


def shapes(c: dict, i: int) -> dict:
    """Leaf shapes of layer ``i`` (HF key names in ``c``)."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    out = {"ln1": (d,), "wq": (d, H * (nope + rope)),
           "wkv_a": (d, r + rope), "kv_norm": (r,),
           "wkv_b": (r, H * (nope + dv)), "wo": (H * dv, d), "ln2": (d,)}
    if i < c["first_k_dense_replace"]:
        f = c["intermediate_size"]
        out.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        e, fe = c["n_routed_experts"], c["moe_intermediate_size"]
        fs = c["n_shared_experts"] * fe
        out.update(router=(d, c["router_outputs"]), moe_gate=(e, d, fe),
                   moe_up=(e, d, fe), moe_down=(e, fe, d),
                   shared_gate=(d, fs), shared_up=(d, fs),
                   shared_down=(fs, d))
    return out


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence(int(seed) & (2 ** 64 - 1))
               .generate_state(1)[0] & 0x7FFFFFFF)


def weight_key(seed: int):
    return jax.random.key(seed32(seed))


def init_weights(c: dict, n_layers: int, key) -> dict:
    """Weights from ``weight_key(seed)``, as float32 values that
    bfloat16 holds exactly: matrices N(0, 1/fan_in), the embedding
    N(0, 1/d), norm offsets N(0, 0.1^2), and the routers' correction
    biases N(0, ``router_bias_std``^2), kept apart under
    ``router_bias``: they are not trained.  Jit it, the key an argument, for one device call
    that every seed shares."""
    d, V = c["hidden_size"], c["vocab_size"]
    k_emb, k_head, k_fin, k_lay, k_bias = jax.random.split(key, 5)

    def rnd(k, shape, std):
        x = jax.random.normal(k, shape, jnp.float32) * std
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    layers = []
    for i, kl in enumerate(jax.random.split(k_lay, n_layers)):
        names = layer_keys(c, i)
        ks = dict(zip(names, jax.random.split(kl, len(names))))
        layers.append({name: rnd(ks[name], shp,
                                 0.1 if name in NORM_KEYS
                                 else shp[-2] ** -0.5)
                       for name, shp in shapes(c, i).items()})
    n_moe = n_layers - c["first_k_dense_replace"]
    return {"embed": rnd(k_emb, (V, d), d ** -0.5),
            "head": rnd(k_head, (d, V), d ** -0.5),
            "final_norm": rnd(k_fin, (d,), 0.1),
            "layers": layers,
            "router_bias": [rnd(k, (c["router_outputs"],),
                                c["router_bias_std"])
                            for k in jax.random.split(k_bias, n_moe)]}


def trained(w: dict) -> dict:
    """The leaves AdamW moves: all but the correction biases."""
    return {k: v for k, v in w.items() if k != "router_bias"}


def leaf_names(c: dict, n_layers: int) -> list[str]:
    return (["embed", "head", "final_norm"]
            + [f"layers.{i}.{k}" for i in range(n_layers)
               for k in layer_keys(c, i)])


def leaf_norms(c: dict, w: dict) -> jnp.ndarray:
    """L2 norm of every trained leaf, in :func:`leaf_names` order."""
    vals = [w["embed"], w["head"], w["final_norm"]]
    for i, lay in enumerate(w["layers"]):
        vals += [lay[k] for k in layer_keys(c, i)]
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
    """Half-split rotary embedding of ``x`` (T, heads, dim)."""
    t, dh = x.shape[0], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, wg, wu, wd, dtype):
    return _mm(jax.nn.silu(_mm(h, wg, dtype)) * _mm(h, wu, dtype), wd, dtype)


def _causal_block(q, k, v, start, scale, dtype):
    """Queries ``start`` on against every key, later ones masked."""
    n = q.shape[0]
    if dtype is not None:
        q, k, v = (a.astype(dtype).astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("qhd,thd->hqt", q, k, precision=HI) * scale
    keep = (jnp.arange(k.shape[0])[None, :]
            <= start + jnp.arange(n)[:, None])
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    if dtype is not None:
        p = p.astype(dtype).astype(jnp.float32)
    return jnp.einsum("hqt,thd->qhd", p, v, precision=HI)


def _attention(c, lay, x, dtype):
    """Latent attention of one sequence ``x`` (T, d); returns x + out."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    H = c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    T = x.shape[0]
    h = _rms(x, lay["ln1"], eps)
    q = _mm(h, lay["wq"], dtype).reshape(T, H, nope + rope)
    kv = _mm(h, lay["wkv_a"], dtype)
    c_kv = _rms(kv[:, :r], lay["kv_norm"], eps)
    k_pe = _rope(kv[:, None, r:], theta)
    kvb = _mm(c_kv, lay["wkv_b"], dtype).reshape(T, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    v = kvb[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    blk = min(Q_BLOCK, T)
    block = jax.checkpoint(lambda a: _causal_block(
        a[1], k, v, a[0] * blk, scale, dtype))
    o = jax.lax.map(block, (jnp.arange(T // blk),
                            q.reshape(T // blk, blk, H, nope + rope)))
    return x + _mm(o.reshape(T, H * dv), lay["wo"], dtype)


def _experts(c, lay, bias, h, dtype):
    """Routed experts held here plus the shared experts, on (T, d)."""
    logits = _mm(h, lay["router"], dtype)
    scores = jax.nn.sigmoid(logits)
    k = c["num_experts_per_tok"]
    _, idx = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True) * c["routed_scaling_factor"]
    gate = jnp.sum(jax.nn.one_hot(idx, c["router_outputs"]) * w[..., None],
                   axis=1)                                  # (T, experts)
    first = c["experts_first"]
    held = gate[:, first: first + c["n_routed_experts"]]      # (T, e)
    y = _swiglu(h[None], lay["moe_gate"], lay["moe_up"], lay["moe_down"],
                dtype)                                      # (e, T, d)
    return (_swiglu(h, lay["shared_gate"], lay["shared_up"],
                    lay["shared_down"], dtype)
            + jnp.einsum("te,etd->td", held, y, precision=HI))


def _layer(c, i, lay, bias, x, dtype):
    """Decoder layer ``i`` on one sequence ``x`` (T, d)."""
    x = _attention(c, lay, x, dtype)
    h = _rms(x, lay["ln2"], c["rms_norm_eps"])
    if i < c["first_k_dense_replace"]:
        return x + _swiglu(h, lay["w_gate"], lay["w_up"], lay["w_down"],
                           dtype)
    return x + _experts(c, lay, bias, h, dtype)


def _seq_loss_sum(c, w, tokens, labels, dtype, chunk=512):
    """Summed next-token cross entropy of one sequence."""
    x = w["embed"][tokens]
    n_dense = c["first_k_dense_replace"]
    for i, lay in enumerate(w["layers"]):
        bias = (None if i < n_dense else
                jax.lax.stop_gradient(w["router_bias"][i - n_dense]))
        x = jax.checkpoint(functools.partial(_layer, c, i, dtype=dtype))(
            lay, bias, x)
    x = _rms(x, w["final_norm"], c["rms_norm_eps"])
    T, d = x.shape
    chunk = min(chunk, T)

    @jax.checkpoint
    def piece(tot, xl):
        xb, lb = xl
        logits = _mm(xb, w["head"], dtype)
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


@functools.lru_cache(maxsize=8)
def _programs(c_json: str, n_layers: int, dtype):
    """The jitted weight generator, gradient and leaf norms of a config,
    built once per process."""
    c = json.loads(c_json)
    gen = jax.jit(functools.partial(init_weights, c, n_layers))

    def loss_of_trained(tw, bias, inp, lab):
        return loss_fn(c, dict(tw, router_bias=bias), inp, lab, dtype)

    vg = jax.jit(jax.value_and_grad(loss_of_trained, argnums=0))
    norms = jax.jit(functools.partial(leaf_norms, c))
    delta = jax.jit(lambda a, b: leaf_norms(
        c, jax.tree_util.tree_map(jnp.subtract, a, b)))
    return gen, vg, norms, delta


def run_steps(c: dict, seed: int, n_layers: int, batches, opt: dict,
              dtype=None, steps: int = 3) -> dict:
    """``steps`` AdamW steps from the seed's weights on ``batches``
    (a list of ``(inputs, labels)`` device arrays).

    Returns, as host arrays: each step's loss; each trained leaf's norm
    of the first gradient as the optimizer takes it (clipped) and
    unclipped; and each trained leaf's norm of the weights' change
    after the last step.
    """
    with jax.default_matmul_precision("highest"):
        gen, vg, norms, delta_norms = _programs(
            json.dumps(c, sort_keys=True), n_layers, dtype)
        full = gen(weight_key(seed))
        bias = full["router_bias"]
        w = trained(full)
        del full
        m = v = None
        losses, first = [], None
        for t in range(steps):
            inp, lab = batches[t]
            # The moments wait on the host while the gradient is taken.
            held = None if m is None else jax.device_get((m, v))
            m = v = None
            loss, g = vg(w, bias, inp, lab)
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
                raw = np.asarray(norms(g), np.float64)
                first = (raw * float(scale), raw)
            del g
        del m, v
        w0 = trained(gen(weight_key(seed)))
        delta = np.asarray(delta_norms(w, w0), np.float64)
        del w, w0
    return {"losses": np.asarray(losses), "grad": first[0],
            "grad_raw": first[1], "delta": delta}
