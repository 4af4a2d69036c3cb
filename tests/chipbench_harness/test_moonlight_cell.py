"""The Moonlight train cell's harness, on the CPU at tiny sizes.

As ``test_bench_harness.py`` does for the other cells: the look for a
chip is a stub, a checkout gains a tiny MoE cell as new files, and a
run is driven as on the chip.  Faults planted in the timed path (half
the batch left out, a state left unchanged, the routers' correction
bias ignored, pairs over a capacity dropped) and the control in its
place have to read ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench_harness
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import flops_moe, harness  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench.references import moonlight as mref  # noqa: E402

CONFIG = ROOT / "chipbench" / "configs" / "moonlight-16b-a3b-6L.json"
TINY_MOON = dict(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=4,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, moe_intermediate_size=32,
                 num_hidden_layers=4, vocab_size=512, router_outputs=16,
                 n_routed_experts=8, num_experts_per_tok=3)


def fake_tpu(chips, peaks, devices):
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with a tiny Moonlight cell added as new
    files and entries; no existing file is edited."""
    root = tmp_path_factory.mktemp("moon") / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", root / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = json.loads(CONFIG.read_text())
    c.update(name="tiny-moon", **TINY_MOON)
    (root / "chipbench" / "configs" / "tiny-moon.json").write_text(
        json.dumps(c))
    tdir = root / "chipbench" / "traffic"
    t = json.loads((tdir / "train_moe_8k.json").read_text())
    t.update(seq=64, pool=4)
    (tdir / "tiny-moe.json").write_text(json.dumps(t))
    spec["configs"].append({"name": "tiny-moon", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chipbench/configs/tiny-moon.json"})
    spec["workloads"].append({"name": "tiny-moon.train",
                              "config": "tiny-moon", "traffic": "tiny-moe",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "moonlight-train" in m.get("workloads", []):
            m["workloads"].append("tiny-moon.train")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, seed=1234567890123, trace=0):
    args = SimpleNamespace(workload="tiny-moon.train", seed=seed,
                           seconds=1.5, trace=trace)
    return bench_run.run(args, root, fake_tpu)


# ----------------------------------------------------------------------
# the cell and its yardsticks
# ----------------------------------------------------------------------

def test_cell_reports_the_moe_counters():
    spec = harness.load_spec()
    cell = harness.resolve_cell(spec, "moonlight-train")
    assert cell.driver == "fl_train_moe"
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                     "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"train.mfu", "train.step_device_ms", "device.peak_hbm_gb",
            "moe.assignments", "moe.max_load"} <= names
    qwen = harness.resolve_cell(spec, "qwen3-train")
    assert not {"moe.assignments", "moe.max_load"} & {
        m["name"] for m in qwen.per_layer}


@pytest.mark.parametrize("metric, counters, stats, want", [
    ("moe.assignments", {"moe.assignments": 6144.0},
     {"tokens": 1024, "moe_layers": 8, "steps": 2}, 0.75),
    ("moe.assignments", {}, {"tokens": 1024, "moe_layers": 8}, None),
    ("moe.max_load", {"moe.max_load": 5.0}, {"steps": 2}, 2.5),
    ("moe.max_load", {}, {"steps": 2}, None),
])
def test_moe_counter_readers(metric, counters, stats, want):
    ctx = SimpleNamespace(stats=dict(stats, counters=counters))
    assert harness.load_reader(metric)(ctx) == want


def test_config_keeps_the_published_widths():
    c = json.loads(CONFIG.read_text())
    spec = harness.load_spec()
    entry = next(x for x in spec["configs"]
                 if x["name"] == "moonlight-16b-a3b-6L")
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert c["published"] == {"num_hidden_layers": 27,
                              "n_routed_experts": 64,
                              "vocab_size": 163840}
    for k, v in dict(hidden_size=2048, intermediate_size=11264,
                     moe_intermediate_size=1408, kv_lora_rank=512,
                     qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, num_attention_heads=16,
                     num_experts_per_tok=6, n_shared_experts=2,
                     router_outputs=64).items():
        assert c[k] == v, k


def test_flops_follow_the_analytic_sums():
    """2.63 GFLOP a token: 313.3M parameters in matrix products a token
    (6 x 13.76M attention, 69.2M dense, 5 x 23.9M MoE at 0.75 held
    experts a token, 41.9M head), and the causal 192/128-wide
    attention."""
    c = json.loads(CONFIG.read_text())
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    moe = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    n = 6 * attn + 3 * 2048 * 11264 + 5 * moe + 2048 * 20480
    assert flops_moe.matmul_params_per_token(c) == pytest.approx(n)
    att = 6 * 2.0 * 8192 ** 2 / 2 * 16 * (192 + 128)
    assert flops_moe.train_step_flops(c, 2, 8192) == pytest.approx(
        3 * (2 * n * 2 * 8192 + 2 * att))
    assert flops_moe.train_step_flops(c, 1, 8192) / 8192 == pytest.approx(
        2.635e9, rel=1e-3)


def test_zipf_pool_is_uneven_and_seeded(checkout):
    drv_mod = harness.load_driver("fl_train_moe", checkout)
    cell = harness.resolve_cell(harness.load_spec(checkout),
                                "tiny-moon.train", checkout)
    a = drv_mod.Driver(cell, 7, harness.CompileMeter(), False)._pool()
    b = drv_mod.Driver(cell, 7, harness.CompileMeter(), False)._pool()
    c = drv_mod.Driver(cell, 8, harness.CompileMeter(), False)._pool()
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])
    ids, counts = np.unique(np.asarray(a[0]), return_counts=True)
    assert ids.min() >= 0 and ids.max() < TINY_MOON["vocab_size"]
    # the most frequent id takes far more than a uniform share, and it
    # is the same id for another seed: the ranks are fixed
    assert counts.max() > 20 * counts.sum() / TINY_MOON["vocab_size"]
    ids_c, counts_c = np.unique(np.asarray(c[0]), return_counts=True)
    assert ids[np.argmax(counts)] == ids_c[np.argmax(counts_c)]


# ----------------------------------------------------------------------
# whole runs: sound, faulty, control
# ----------------------------------------------------------------------

def test_cell_runs_and_is_correct(checkout):
    line = _run(checkout)
    assert line["correct"], line
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    traced = _run(checkout, trace=1)
    assert traced["correct"], traced
    m = traced["metrics"]
    assert m["train.mfu"]["value"] > 0
    assert 0 < m["moe.assignments"]["value"] <= 3
    assert m["moe.max_load"]["value"] >= 1


def _fault(monkeypatch, kind):
    import jax
    import jax.numpy as jnp

    import repro.dist.fl_step as fl
    from repro.models import layers
    if kind == "state_unchanged":
        monkeypatch.setattr(fl, "adamw_update",
                            lambda g, opt, params, lr: (params, opt))
    elif kind == "half_batch":
        orig = fl.train_loss

        def half(cfg, p, x, y, **kw):
            h = x.shape[0] // 2
            return orig(cfg, p, x[:h], y[:h], **kw)
        monkeypatch.setattr(fl, "train_loss", half)
    elif kind == "bias_ignored":
        orig = layers._route

        def no_bias(cfg, p, x):
            q = dict(p, buffers={"router_bias": jnp.zeros_like(
                p["buffers"]["router_bias"])})
            return orig(cfg, q, x)
        monkeypatch.setattr(layers, "_route", no_bias)
    elif kind == "capacity_drops":
        # A dropping layer's rule: each expert keeps the first
        # n * top_k / n_experts of its pairs, in token order.
        orig = layers._route

        def capped(cfg, p, x):
            w, idx = orig(cfg, p, x)
            onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.int32)
            rank = (jnp.cumsum(onehot.reshape(-1, cfg.n_experts), 0)
                    .reshape(onehot.shape) * onehot).sum(-1) - 1
            cap = -(-x.shape[0] * cfg.top_k // cfg.n_experts)
            return jnp.where(rank < cap, w, 0.0), idx
        monkeypatch.setattr(layers, "_route", capped)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "bias_ignored", "capacity_drops",
                                  "control"])
def test_fault_is_caught(checkout, monkeypatch, kind):
    if kind == "control":
        import jax.numpy as jnp
        drv_mod = harness.load_driver("fl_train_moe", checkout)
        orig_setup = drv_mod.Driver.setup

        def setup(self):
            orig_setup(self)
            r = mref.run_steps(self.c, self.seed, self.L,
                               [(self.inputs[t], self.labels[t])
                                for t in range(3)], self.opt_hp,
                               dtype=jnp.float8_e4m3fn)
            self.loss_p, self.grad_p, self.delta_p = (
                r["losses"], r["grad"], r["delta"])
        monkeypatch.setattr(drv_mod.Driver, "setup", setup)
    else:
        _fault(monkeypatch, kind)
    line = _run(checkout)
    assert not line["correct"], line
