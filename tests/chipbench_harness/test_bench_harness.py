"""The chip benchmark's harness, on the CPU at tiny sizes.

The harness's look for a chip is swapped for a stub that reports a
TPU v5e, and the rest of a run is driven as on the chip: set-up, the
window, the check against the plain reference.  With the timed path
broken underneath, and with the control in its place, ``correct`` has
to come out false.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench_harness
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import flops, harness  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench.drivers.fl_train import (arch_fields,  # noqa: E402
                                        program_leaf_norms,
                                        to_program_layout)
from chipbench.references import qwen3 as qref  # noqa: E402
from chipbench.references import swarm as sref  # noqa: E402
from chipbench.trace_reduce import Trace  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def fake_tpu(chips, peaks, devices):
    """Stands in for the harness's look for a chip."""
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}


# ----------------------------------------------------------------------
# a checkout with a tiny cell of each kind, added as new files only
# ----------------------------------------------------------------------

TINY_SWARM = dict(n=60, chunks_per_update=24)
TINY_QWEN = dict(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 num_hidden_layers=2, vocab_size=512)


def _checkout(tmp: Path) -> Path:
    """A copy of the benchmark with tiny cells added as new files: a
    configuration, a traffic mix and a per-layer metric of their own,
    and entries in ``BENCHMARK.json``; no existing file is edited."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", root / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cdir = root / "chipbench" / "configs"
    res = json.loads((cdir / "residential-n500.json").read_text())
    res.update(name="tiny-swarm", round_cycles=40)
    res["deployment"].update(TINY_SWARM)
    (cdir / "tiny-swarm.json").write_text(json.dumps(res))
    q = json.loads((cdir / "qwen3-1.7b-8L.json").read_text())
    q.update(name="tiny-qwen", **TINY_QWEN)
    (cdir / "tiny-qwen.json").write_text(json.dumps(q))
    tdir = root / "chipbench" / "traffic"
    t = json.loads((tdir / "train_2x2048.json").read_text())
    t.update(seq=64, pool=4)
    (tdir / "tiny-train.json").write_text(json.dumps(t))
    w = json.loads((tdir / "warmup_stream.json").read_text())
    w.update(check_cycles=12)
    (tdir / "tiny-warmup.json").write_text(json.dumps(w))
    (root / "chipbench" / "layer_metrics" / "tiny.cycles.py").write_text(
        "def read(ctx):\n    return float(ctx.stats['cycles'])\n")
    spec["configs"] += [
        {"name": "tiny-swarm", "source": "test",
         "reduced": ["n", "chunks_per_update", "round_cycles"],
         "file": "chipbench/configs/tiny-swarm.json", "why": "test"},
        {"name": "tiny-qwen", "source": "test", "reduced": [],
         "file": "chipbench/configs/tiny-qwen.json", "why": "test"}]
    spec["workloads"] += [
        {"name": "tiny-swarm.warmup", "config": "tiny-swarm",
         "traffic": "tiny-warmup", "chips": 1, "why": "test"},
        {"name": "tiny-qwen.train", "config": "tiny-qwen",
         "traffic": "tiny-train", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            if "cycle_ms" == m["name"] or "solve_ms_p95" == m["name"]:
                m["workloads"].append("tiny-swarm.warmup")
            if m["name"] == "tokens_per_s":
                m["workloads"].append("tiny-qwen.train")
    for m in spec["per_layer"]:
        ws = m.get("workloads", [])
        if any(x.endswith("-warmup") for x in ws):
            ws.append("tiny-swarm.warmup")
        if any(x.endswith("-train") for x in ws):
            ws.append("tiny-qwen.train")
    spec["per_layer"].append(
        {"name": "tiny.cycles", "unit": "cycles", "better": "higher",
         "source": "program_counter", "layer": "round driver",
         "moves": "cycle_ms", "workloads": ["tiny-swarm.warmup"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _checkout(tmp_path_factory.mktemp("bench"))


def _run(root, workload, seed=1234567890123, seconds=1.5, trace=0):
    args = SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace)
    return bench_run.run(args, root, fake_tpu)


# ----------------------------------------------------------------------
# the device check
# ----------------------------------------------------------------------

def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "residential-warmup", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=120,
        cwd=ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


@pytest.mark.parametrize("platform, kind, chips, ok", [
    ("cpu", "cpu", 1, False),
    ("tpu", "TPU v9 imaginary", 1, False),
    ("tpu", "TPU v5 lite", 4, False),
    ("tpu", "TPU v5 lite", 1, True),
])
def test_device_check(platform, kind, chips, ok):
    devs = [SimpleNamespace(platform=platform, device_kind=kind)]
    peaks = harness.load_peaks()
    if ok:
        assert harness.check_device(chips, peaks, devs)["kind"] == kind
    else:
        with pytest.raises(harness.BenchError):
            harness.check_device(chips, peaks, devs)


def test_bare_checkout_refuses(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no program, no
    result, a non-zero exit."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "residential-warmup", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert res.stdout.strip() == ""


# ----------------------------------------------------------------------
# data-driven: new cells are new files
# ----------------------------------------------------------------------

def test_every_cell_of_the_spec_resolves():
    spec = harness.load_spec()
    for wl in spec["workloads"]:
        cell = harness.resolve_cell(spec, wl["name"])
        harness.load_driver(cell.driver)
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))


def test_new_files_add_a_cell(checkout):
    spec = harness.load_spec(checkout)
    cell = harness.resolve_cell(spec, "tiny-swarm.warmup", checkout)
    assert cell.config["deployment"]["n"] == TINY_SWARM["n"]
    assert cell.config["round_cycles"] == 40
    assert cell.traffic["check_cycles"] == 12
    assert "tiny.cycles" in [m["name"] for m in cell.per_layer]
    assert harness.load_reader("tiny.cycles", checkout)(
        SimpleNamespace(stats={"cycles": 7})) == 7.0
    train = harness.resolve_cell(spec, "tiny-qwen.train", checkout)
    assert [m["name"] for m in train.end_to_end] == ["tokens_per_s",
                                                      "setup_s"]


# ----------------------------------------------------------------------
# yardsticks
# ----------------------------------------------------------------------

def test_flops_agree_with_the_program():
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.flops import model_flops
    from repro.models import ArchConfig
    c = json.loads((ROOT / "chipbench/configs/qwen3-1.7b-8L.json").read_text())
    arch = ArchConfig(**arch_fields(c))
    assert arch.n_layers == 8
    want = model_flops(arch, ShapeSpec("t", 2048, 2, "train"))
    assert flops.train_step_flops(arch, 2, 2048) == pytest.approx(want,
                                                                  rel=1e-12)
    assert flops.param_count(arch) == 713_854_976
    other = get_config("olmoe-1b-7b")
    assert flops.train_step_flops(other, 4, 512) == pytest.approx(
        model_flops(other, ShapeSpec("t", 512, 4, "train")), rel=1e-12)


def test_trace_reduction_on_the_synthetic_fixture():
    t = Trace.from_json(str(FIXTURES / "synthetic_trace.json"))
    assert t.window_s == pytest.approx(1e-6)
    assert t.busy_s() == pytest.approx(175e-9)
    assert t.device_in_span_s("bench.solve") == pytest.approx(55e-9)
    assert t.device_in_span_s("bench.transport") == pytest.approx(90e-9)
    assert t.span_s("bench.solve") == pytest.approx(300e-9)
    assert t.span_count("bench.solve") == 2
    top = t.top_ops(10)
    assert [n for n, _ in top] == ["kern", "fusion.1", "copy"]
    assert [s for _, s in top] == pytest.approx([140e-9, 70e-9, 10e-9])
    gaps = dict(t.idle_gaps(10))
    assert gaps == pytest.approx({"driver": 730e-9, "bench.solve": 70e-9})


def _naive(t: Trace, span: str | None):
    """Busy ns per chip, or its part inside ``span``, by brute force
    over the events' integer nanoseconds."""
    lo, hi = t.window
    out = []
    for _, iv in t.ops:
        busy = set()
        for s, e in iv:
            busy.update(range(int(max(s, lo)), int(min(e, hi))))
        if span is not None:
            inside = set()
            for s, e in t.spans[span]:
                inside.update(range(int(max(s, lo)), int(min(e, hi))))
            busy &= inside
        out.append(len(busy))
    return float(np.mean(out)) * 1e-9


def test_trace_reduction_on_a_recorded_chip_trace():
    path = FIXTURES / "chip_trace.json"
    t = Trace.from_json(str(path))
    assert t.chips >= 1
    assert t.busy_s() == pytest.approx(_naive(t, None), rel=1e-9)
    for span in t.spans:
        if span != "bench.window":
            assert t.device_in_span_s(span) == pytest.approx(
                _naive(t, span), rel=1e-9, abs=1e-12)


# ----------------------------------------------------------------------
# references
# ----------------------------------------------------------------------

def test_qwen3_reference_agrees_with_the_program():
    import jax
    import jax.numpy as jnp

    from repro.models import ArchConfig, train_loss
    c = json.loads((ROOT / "chipbench/configs/qwen3-1.7b-8L.json").read_text())
    c.update(TINY_QWEN)
    arch = ArchConfig(**dict(arch_fields(c), dtype="float32"))
    w = qref.init_weights(c, c["num_hidden_layers"], qref.weight_key(5))
    p = to_program_layout(w, jnp.float32)
    key = jax.random.key(1)
    toks = jax.random.randint(key, (2, 33), 0, c["vocab_size"])
    inp, lab = toks[:, :-1], toks[:, 1:]
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda q: train_loss(arch, q, inp, lab))(p)
        lr, gr = jax.value_and_grad(
            lambda q: qref.loss_fn(c, q, inp, lab))(w)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    g_prog = np.asarray(program_leaf_norms(gp, c["num_hidden_layers"]))
    g_ref = np.asarray(qref.leaf_norms(gr))
    np.testing.assert_allclose(g_prog, g_ref, rtol=1e-4)


def test_transport_reference_agrees_with_the_program_oracle():
    from repro.net import fairshare
    rng = np.random.default_rng(3)
    n = 40
    snd = rng.integers(0, n, 300)
    rcv = (snd + rng.integers(1, n, 300)) % n
    up = rng.uniform(1e6, 3e6, n)
    down = rng.uniform(4e6, 9e6, n)
    pair, counts = np.unique(snd * n + rcv, return_counts=True)
    fs, fd = pair // n, pair % n
    nbytes = counts * 262144.0
    lb = fairshare.congestion_bound(fs, fd, nbytes, up, down)
    tm = fairshare._transport_host(fs, fd, counts, nbytes, 262144.0, up,
                                   down, lb / 32)
    fin, cflow, cend = sref.transport(fs, fd, counts, 262144.0, up, down,
                                      1 / 32)
    np.testing.assert_array_equal(cflow, tm.chunk_flow)
    np.testing.assert_allclose(cend, tm.chunk_end, rtol=1e-13)
    np.testing.assert_allclose(fin, tm.finish, rtol=1e-13)


def _conf(name):
    return json.loads((ROOT / "chipbench" / "configs" / name).read_text())


@pytest.mark.parametrize("name", ["residential-n500.json",
                                  "crosssilo-ds14b.json"])
def test_instance_follows_the_deployment(name):
    """The benchmark's peers: minimum degree m, links in their ranges,
    budgets from the rates and the chunk size, lags in range."""
    conf = _conf(name)
    dep, links, net = conf["deployment"], conf["links"], conf["net"]
    inst = sref.instance(conf, 2 ** 31 + 11)
    adj = inst["adj"]
    assert (adj == adj.T).all() and not adj.diagonal().any()
    assert adj.sum(1).min() >= dep["min_degree"]
    assert links["up_lo"] <= inst["up_bps"].min()
    assert inst["up_bps"].max() <= links["up_hi"]
    assert links["down_lo"] <= inst["down_bps"].min()
    assert inst["down_bps"].max() <= links["down_hi"]
    want = np.floor(inst["up_bps"] * dep["slot_seconds"]
                    / dep["chunk_bytes"])
    np.testing.assert_array_equal(inst["up"], np.maximum(want, 1))
    assert set(np.unique(inst["lag"])) == set(range(dep["lag_slots"]))
    assert (inst["lat"] >= net["latency_lo_s"]).all()
    assert (inst["lat"] <= max(net["latency_hi_s"], 0.0)).all()


def test_seeds_relabel_one_instance():
    """Every seed gets the same peers under other labels: the same
    degrees, rates and lags as multisets; one seed, the same labels."""
    conf = _conf("crosssilo-ds14b.json")
    a, b = sref.instance(conf, 1), sref.instance(conf, 2)
    assert not np.array_equal(a["adj"], b["adj"])
    np.testing.assert_array_equal(np.sort(a["adj"].sum(1)),
                                  np.sort(b["adj"].sum(1)))
    for k in ("up_bps", "down_bps", "lag"):
        np.testing.assert_array_equal(np.sort(a[k]), np.sort(b[k]))
    again = sref.instance(conf, 1)
    for k in a:
        np.testing.assert_array_equal(a[k], again[k])


# ----------------------------------------------------------------------
# whole runs: sound, faulty, control
# ----------------------------------------------------------------------

def test_swarm_cell_runs_and_is_correct(checkout):
    line = _run(checkout, "tiny-swarm.warmup")
    assert line["correct"], line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"cycle_ms", "solve_ms_p95", "setup_s"}
    assert list(line)[-1] == "checks"


def test_swarm_cell_traced(checkout):
    line = _run(checkout, "tiny-swarm.warmup", trace=1)
    assert line["correct"], line
    m = line["metrics"]
    assert m["tiny.cycles"]["value"] > 0
    assert "transport.solves" in m and "swarm.window_compiles" in m
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _swarm_fault(monkeypatch, kind):
    from repro.core import jit_engine
    import repro.net.engine as eng
    orig = jit_engine.schedule_centralized_jit
    if kind == "state_unchanged":
        first = {}

        def sched(state, mode):
            out = orig(state, mode)
            if len(out[0]) and "x" not in first:
                first["x"] = out
            return first.get("x", out)
        monkeypatch.setattr(jit_engine, "schedule_centralized_jit", sched)
    elif kind == "grant_altered":
        def sched(state, mode):
            snd, rcv, chk = orig(state, mode)
            if len(chk):
                chk = chk.copy()
                chk[0] = rcv[0] * state.cfg.chunks_per_update
            return snd, rcv, chk
        monkeypatch.setattr(jit_engine, "schedule_centralized_jit", sched)
    elif kind == "budgets_doubled":
        import repro.core.simulator as simulator
        state_cls = simulator.SwarmState

        class Doubled(state_cls):
            def __init__(self, cfg, adj, up, down, rng):
                super().__init__(cfg, adj, 2 * up, 2 * down, rng)
        monkeypatch.setattr(simulator, "SwarmState", Doubled)
    elif kind == "lags_ignored":
        from repro.core.state import SwarmState
        monkeypatch.setattr(SwarmState, "senders_active",
                            lambda self: self.active.copy())
    elif kind in ("stamp_altered", "control"):
        orig_t = eng.transport

        def transport(src, dst, counts, chunk_bytes, up, down, *,
                      quantum_frac):
            tm = orig_t(src, dst, counts, chunk_bytes, up, down,
                        quantum_frac=quantum_frac)
            if kind == "stamp_altered":
                tm.chunk_end = tm.chunk_end * (1 + 1e-3)
            else:
                fin, cf, ce = sref.transport(src, dst, counts, chunk_bytes,
                                             up, down, quantum_frac,
                                             np.float32)
                tm.finish = fin.astype(np.float64)
                tm.chunk_flow = cf
                tm.chunk_end = ce.astype(np.float64)
            return tm
        monkeypatch.setattr(eng, "transport", transport)


@pytest.mark.parametrize("kind", ["state_unchanged", "grant_altered",
                                  "budgets_doubled", "lags_ignored",
                                  "stamp_altered", "control"])
def test_swarm_fault_is_caught(checkout, monkeypatch, kind):
    _swarm_fault(monkeypatch, kind)
    line = _run(checkout, "tiny-swarm.warmup")
    assert not line["correct"], line


def test_train_cell_runs_and_is_correct(checkout):
    line = _run(checkout, "tiny-qwen.train")
    assert line["correct"], line
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    traced = _run(checkout, "tiny-qwen.train", trace=1)
    assert traced["correct"], traced
    assert traced["metrics"]["train.mfu"]["value"] > 0


def _train_fault(monkeypatch, kind):
    import repro.dist.fl_step as fl
    if kind == "state_unchanged":
        monkeypatch.setattr(fl, "adamw_update",
                            lambda g, opt, params, lr: (params, opt))
    elif kind == "half_batch":
        orig = fl.train_loss

        def half(cfg, p, x, y, **kw):
            h = x.shape[0] // 2
            return orig(cfg, p, x[:h], y[:h], **kw)
        monkeypatch.setattr(fl, "train_loss", half)
    elif kind == "answer_altered":
        # The update, altered where it is produced: one leaf moved twice
        # as far as AdamW says.
        orig = fl.adamw_update

        def doubled(g, opt, params, lr):
            new_p, new_opt = orig(g, opt, params, lr=lr)
            m = dict(new_opt.master)
            m["final_norm"] = 2 * m["final_norm"] - opt.master["final_norm"]
            return new_p, new_opt._replace(master=m)
        monkeypatch.setattr(fl, "adamw_update", doubled)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "control"])
def test_train_fault_is_caught(checkout, monkeypatch, kind):
    if kind == "control":
        # The reference in float8 in the program's place.
        import jax.numpy as jnp
        drv_mod = harness.load_driver("fl_train", checkout)
        orig_setup = drv_mod.Driver.setup

        def setup(self):
            orig_setup(self)
            r = qref.run_steps(self.c, self.seed, self.L,
                               [(self.inputs[t], self.labels[t])
                                for t in range(3)], self.opt_hp,
                               dtype=jnp.float8_e4m3fn)
            self.loss_p, self.grad_p, self.delta_p = (
                r["losses"], r["grad"], r["delta"])
        monkeypatch.setattr(drv_mod.Driver, "setup", setup)
    else:
        _train_fault(monkeypatch, kind)
    line = _run(checkout, "tiny-qwen.train")
    assert not line["correct"], line
