"""Moonlight-16B-A3B (DeepSeek-V3 block) against its plain reference.

At a tiny size on the CPU, on seeded random weights: the program's
latent attention, its MoE layer of the experts held (drop-free, with
shared experts and a sigmoid router with a correction bias) and its
leading dense layer, through ``models.train_loss`` and AdamW, agree
with ``chipbench/references/moonlight.py``; routing forced onto a few
held experts drops nothing; the parts that shares of 8 experts give add
up to the uncut layer; the correction bias is a buffer the optimizer
leaves as it is; the analytic counts follow the published shape.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.drivers.fl_train_moe import (_program_leaves,  # noqa: E402
                                            arch_fields, to_program_layout)
from chipbench.references import moonlight as mref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import ArchConfig, layers, train_loss  # noqa: E402
from repro.optim import adamw_init, adamw_update  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
            num_hidden_layers=4, vocab_size=512, router_outputs=16,
            n_routed_experts=8, num_experts_per_tok=3)
HP = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
      "clip_norm": 1.0}


def _conf(**kw) -> dict:
    c = json.loads((ROOT / "chipbench" / "configs"
                    / "moonlight-16b-a3b-6L.json").read_text())
    c.update(TINY, **kw)
    return c


def _setup(c, seed=5):
    arch = ArchConfig(**dict(arch_fields(c), dtype="float32"))
    w = mref.init_weights(c, c["num_hidden_layers"], mref.weight_key(seed))
    return arch, w, to_program_layout(c, w, jnp.float32)


def _batch(c, b=2, t=64, seed=1):
    toks = jax.random.randint(jax.random.key(seed), (b, t + 1), 0,
                              c["vocab_size"])
    return toks[:, :-1], toks[:, 1:]


def _ref_leaves(c, tree):
    return [tree["embed"], tree["head"], tree["final_norm"]] + [
        lay[k] for i, lay in enumerate(tree["layers"])
        for k in mref.layer_keys(c, i)]


def _loss_and_grads(c, arch, w, p, inp, lab):
    with jax.default_matmul_precision("highest"):
        (lp, stats), gp = jax.value_and_grad(
            lambda q: train_loss(arch, q, inp, lab, with_stats=True),
            has_aux=True)(p)
        lr, gr = jax.value_and_grad(
            lambda q: mref.loss_fn(c, dict(q, router_bias=w["router_bias"]),
                                   inp, lab))(mref.trained(w))
    return (lp, stats, gp), (lr, gr)


def _assert_leaves_close(c, prog_tree, ref_tree, rtol):
    names = mref.leaf_names(c, c["num_hidden_layers"])
    for name, a, b in zip(names, _program_leaves(c, prog_tree,
                                                 c["num_hidden_layers"]),
                          _ref_leaves(c, ref_tree)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < rtol, (name, err)


# ----------------------------------------------------------------------
# the program against the reference
# ----------------------------------------------------------------------

def test_loss_gradients_and_adamw_step_agree_with_the_reference():
    c = _conf()
    arch, w, p = _setup(c)
    inp, lab = _batch(c)
    (lp, stats, gp), (lr, gr) = _loss_and_grads(c, arch, w, p, inp, lab)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    _assert_leaves_close(c, gp, gr, 1e-4)
    assert int(stats["moe_pairs"]) > 0

    # One AdamW step: the program's update of its tree, the reference's
    # of its own, from the same gradients' values.
    opt = adamw_init(p)
    new_p, new_opt = adamw_update(gp, opt, p, lr=HP["lr"])
    tw = mref.trained(w)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, tw)
    rw, _, _, _ = mref._adamw(
        tw, zeros, jax.tree_util.tree_map(jnp.zeros_like, tw), gr,
        jnp.float32(1), *(jnp.float32(HP[k]) for k in
                          ("lr", "b1", "b2", "eps", "weight_decay",
                           "clip_norm")))
    _assert_leaves_close(c, new_opt.master, rw, 1e-4)
    np.testing.assert_array_equal(
        np.asarray(new_p["cycles"]["slot0"]["buffers"]["router_bias"]),
        np.stack([np.asarray(b) for b in w["router_bias"]]))


def test_latent_attention_agrees_with_the_reference():
    c = _conf()
    arch, w, p = _setup(c)
    x = jax.random.normal(jax.random.key(2), (2, 48, c["hidden_size"]))
    lay = p["lead"][0]
    with jax.default_matmul_precision("highest"):
        h = layers.rms_norm(x, lay["ln1"], arch.norm_eps)
        out, _ = layers._attention_mix(arch, "global", lay, h, "train",
                                       None, None)
        want = jnp.stack([mref._attention(c, w["layers"][0], xi, None)
                          for xi in x])
    np.testing.assert_allclose(np.asarray(x + out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_routing_forced_onto_held_experts_drops_nothing():
    """A correction bias of 10 on experts 0-2 makes every token pick
    those three held experts in every MoE layer: each gets every token,
    eight times its share, and the program still agrees."""
    c = _conf()
    arch, w, p = _setup(c)
    k = c["num_experts_per_tok"]
    forced = [b.at[:k].add(10.0) for b in w["router_bias"]]
    w = dict(w, router_bias=forced)
    p = to_program_layout(c, w, jnp.float32)
    inp, lab = _batch(c)
    (lp, stats, gp), (lr, gr) = _loss_and_grads(c, arch, w, p, inp, lab)
    n_tok = inp.size
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    assert int(stats["moe_pairs"]) == n_tok * k * n_moe
    assert float(stats["moe_max_load"]) == pytest.approx(
        c["n_routed_experts"] / k)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    _assert_leaves_close(c, gp, gr, 1e-4)


def test_shares_of_eight_experts_sum_to_the_uncut_layer():
    """The uncut layer holds all 16 experts; two shares hold 0-7 and
    8-15.  The shares' routed parts, with the shared experts counted
    once, give the uncut layer's output, and so does the reference."""
    c = _conf(n_routed_experts=16)
    arch, w, p = _setup(c)
    lay = jax.tree_util.tree_map(lambda a: a[0], p["cycles"]["slot0"])
    x = jax.random.normal(jax.random.key(3), (2, 40, c["hidden_size"]))
    whole, _ = layers._moe_ffn(arch, lay, x)
    shared = layers._glu(arch, x, lay["shared_gate"], lay["shared_up"],
                         lay["shared_down"])
    total = shared
    for first in (0, 8):
        cut = arch.replace(expert_first=first, experts_held=8)
        part = dict(lay, **{k: lay[k][first:first + 8]
                            for k in ("moe_gate", "moe_up", "moe_down")})
        y, _ = layers._moe_ffn(cut, part, x)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    n_dense = c["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([mref._experts(c, w["layers"][n_dense],
                                        w["router_bias"][0], xi, None)
                          for xi in x])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fl_step_leaves_the_bias_and_reports_routing():
    from repro import obs
    from repro.dist.fl_step import ElasticFLStep
    from repro.models import init_params
    from repro.optim.schedules import constant_lr
    cfg = get_config("moonlight-16b-a3b", reduced=True)
    p = init_params(cfg, jax.random.key(0))
    p["cycles"]["slot0"]["buffers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(1), p["cycles"]["slot0"]["buffers"]["router_bias"].shape)
    bias0 = np.asarray(p["cycles"]["slot0"]["buffers"]["router_bias"])
    opt = adamw_init(p)
    step = ElasticFLStep(cfg, lr_schedule=constant_lr(1e-2),
                         mesh_factory=lambda n: None)
    toks = jax.random.randint(jax.random.key(2), (1, 2, 33), 0, cfg.vocab)
    batch = {"inputs": toks[..., :-1], "labels": toks[..., 1:]}
    with obs.recording() as rec:
        p, opt, m = step(p, opt, batch, jnp.ones((1,)), jnp.ones((1,)))
    np.testing.assert_array_equal(
        np.asarray(p["cycles"]["slot0"]["buffers"]["router_bias"]), bias0)
    np.testing.assert_array_equal(
        np.asarray(opt.master["cycles"]["slot0"]["buffers"]["router_bias"]),
        bias0)
    assert not np.any(np.asarray(
        opt.m["cycles"]["slot0"]["buffers"]["router_bias"]))
    assert set(m) == {"loss", "lr", "moe_pairs", "moe_max_load"}
    assert rec.metrics["moe.assignments"]["value"] == float(m["moe_pairs"])
    assert rec.metrics["moe.max_load"]["value"] == pytest.approx(
        float(m["moe_max_load"]))
    assert rec.metrics["attn.flash_layers"]["value"] == 0.0
    assert rec.metrics["attn.layers"]["value"] == float(cfg.n_layers)
    # every token reaches top_k of n_experts, all held here
    n_moe = cfg.layer_kinds.count("moe")
    assert int(m["moe_pairs"]) == 2 * 32 * cfg.top_k * n_moe


def test_dense_fl_step_reports_nothing_new():
    from repro import obs
    from repro.dist.fl_step import ElasticFLStep
    from repro.models import init_params
    from repro.optim.schedules import constant_lr
    cfg = get_config("qwen3-1.7b", reduced=True)
    p = init_params(cfg, jax.random.key(0))
    step = ElasticFLStep(cfg, lr_schedule=constant_lr(1e-3),
                         mesh_factory=lambda n: None)
    toks = jax.random.randint(jax.random.key(2), (1, 2, 17), 0, cfg.vocab)
    batch = {"inputs": toks[..., :-1], "labels": toks[..., 1:]}
    with obs.recording() as rec:
        _, _, m = step(p, adamw_init(p), batch, jnp.ones((1,)),
                       jnp.ones((1,)))
    assert set(m) == {"loss", "lr"}
    # no routing counters; the attention counters of a CPU step: every
    # attention layer on the blocked path
    assert set(rec.metrics) == {"attn.flash_layers", "attn.layers"}
    assert rec.metrics["attn.flash_layers"]["value"] == 0.0
    assert rec.metrics["attn.layers"]["value"] == float(cfg.n_layers)


@pytest.mark.parametrize("name, t, backend, want", [
    ("moonlight-16b-a3b", 8192, "tpu", (27, 27)),
    ("qwen3-1.7b", 2048, "tpu", (28, 28)),
    ("qwen3-1.7b", 2048, "cpu", (0, 28)),
    ("qwen3-1.7b", 100, "tpu", (0, 28)),
])
def test_flash_attention_layers_of_the_train_models(name, t, backend, want):
    cfg = get_config(name)
    assert layers.flash_attention_layers(cfg, t, backend) == want
    assert layers.flash_attention_layers(cfg, t, backend, 4) == (0, want[1])
    assert layers.flash_attention_layers(
        cfg.replace(attn_impl="xla"), t, backend) == (0, want[1])


# ----------------------------------------------------------------------
# analytic counts
# ----------------------------------------------------------------------

ATTN = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
EXPERT = 3 * 2048 * 1408
MOE_OUTSIDE = ATTN + 2 * 2048 + 2048 * 64 + 3 * 2048 * 2816
DENSE = ATTN + 2 * 2048 + 3 * 2048 * 11264


def test_parameter_counts_follow_the_published_shape():
    cfg = get_config("moonlight-16b-a3b")
    assert DENSE == pytest.approx(83.0e6, rel=2e-3)
    assert MOE_OUTSIDE == pytest.approx(31.2e6, rel=2e-3)
    head = 2 * 163840 * 2048 + 2048
    assert cfg.param_count() == DENSE + 26 * (MOE_OUTSIDE + 64 * EXPERT) + head
    assert cfg.param_count() == pytest.approx(15.96e9, rel=1e-3)
    assert cfg.active_param_count() == (DENSE + 26 * (MOE_OUTSIDE + 6 * EXPERT)
                                        + head)
    assert cfg.active_param_count() == pytest.approx(2.9e9, rel=0.01)
    cut = cfg.replace(n_layers=6, experts_held=8, vocab=20480)
    assert cut.param_count() == (DENSE + 5 * (MOE_OUTSIDE + 8 * EXPERT)
                                 + 2 * 20480 * 2048 + 2048)
    assert cut.param_count() == pytest.approx(669e6, rel=1e-3)
    # a token reaches 6 of 64 experts: 0.75 of the 8 held here
    assert cut.active_param_count() == round(
        cut.param_count() - 5 * 8 * EXPERT + 5 * 0.75 * EXPERT)


def test_model_flops_count_latent_attention():
    from repro.configs.base import ShapeSpec
    from repro.launch.flops import model_flops
    cfg = get_config("moonlight-16b-a3b")
    t, b = 4096, 2
    attn = 27 * 2.0 * (t * t / 2) * 16 * (192 + 128)
    assert model_flops(cfg, ShapeSpec("t", t, b, "train")) == pytest.approx(
        6.0 * cfg.active_param_count() * b * t + 3.0 * b * attn)
    dense = get_config("qwen3-1.7b")
    pairs = t * t / 2
    assert model_flops(dense, ShapeSpec("t", t, b, "train")) == (
        6.0 * dense.active_param_count() * b * t
        + 3.0 * b * 28 * 4.0 * pairs * 16 * 128)
