"""Multi-device dist-layer tests (torrent collective, FL step, dry-run).

These need >1 XLA device, so each runs in a SUBPROCESS that sets
XLA_FLAGS before importing jax (the main pytest process must keep
seeing the single real CPU device — see dryrun.py note).
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.dist     # deselect with `-m "not dist"`

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 900):
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SUBPROC_OK" in res.stdout, res.stdout[-2000:]
    return res.stdout


def test_torrent_fedavg_matches_oracle():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(AxisType.Auto,)*2)
    from repro.dist.torrent import torrent_fedavg
    key = jax.random.PRNGKey(0)
    ups = {"w": jax.random.normal(key, (4, 16, 8)),
           "b": jax.random.normal(key, (4, 24))}
    weights = jnp.array([1., 2., 3., 4.])
    active = jnp.array([1., 1., 0., 1.])
    with mesh:
        out = jax.jit(lambda u, w, a: torrent_fedavg(
            u, w, a, mesh=mesh, n_blocks=4))(ups, weights, active)
    wa = np.array(weights) * np.array(active); wa /= wa.sum()
    want = np.einsum("p,pij->ij", wa, np.array(ups["w"]))
    assert abs(np.array(out["w"]) - want).max() < 1e-5
    # int8 wire compression: small relative error
    with mesh:
        outc = jax.jit(lambda u, w, a: torrent_fedavg(
            u, w, a, mesh=mesh, compress=True))(ups, weights, active)
    rel = abs(np.array(outc["w"]) - want).max() / abs(want).max()
    assert rel < 0.02, rel
    """)


def test_torrent_collective_schedule_in_hlo():
    """The compiled schedule contains the explicit ppermute ring stages
    (P-1 stages x n_blocks) — the paper's dissemination schedule."""
    _run("""
    import jax, jax.numpy as jnp, re
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(AxisType.Auto,)*2)
    from repro.dist.torrent import torrent_fedavg
    ups = {"w": jnp.ones((4, 64))}
    w = jnp.ones(4); a = jnp.ones(4)
    with mesh:
        txt = jax.jit(lambda u, ww, aa: torrent_fedavg(
            u, ww, aa, mesh=mesh, n_blocks=4)).lower(ups, w, a).as_text()
    n_cp = len(re.findall(r"collective.permute", txt))
    assert n_cp >= 3 * 4, n_cp   # (P-1)=3 stages x 4 blocks
    """)


def test_fl_step_equals_data_parallel():
    """Full participation + equal weights: FedAvg-over-pods == DP-SGD."""
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(AxisType.Auto,)*2)
    from repro.models import ArchConfig, init_params
    from repro.optim import adamw_init
    from repro.optim.schedules import constant_lr
    from repro.dist.fl_step import make_fl_train_step
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=4, n_kv=2, head_dim=8, d_ff=64, vocab=128,
                     dtype="float32")
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    batch = {"inputs": jax.random.randint(key, (4, 4, 16), 0, 128),
             "labels": jax.random.randint(key, (4, 4, 16), 0, 128)}
    w = jnp.ones(4); a = jnp.ones(4)
    s4 = make_fl_train_step(cfg, mesh, lr_schedule=constant_lr(1e-3),
                            n_pods=4)
    s1 = make_fl_train_step(cfg, mesh, lr_schedule=constant_lr(1e-3),
                            n_pods=1)
    with mesh:
        p4, _, m4 = jax.jit(s4)(params, opt, batch, w, a)
        p1, _, m1 = jax.jit(s1)(params, opt, batch, w, a)
    diff = max(float(jnp.abs(x - y).max()) for x, y in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p4)))
    assert diff < 1e-4, diff
    """)


def test_fl_step_straggler_mask():
    """A masked pod (active=0) contributes nothing — fault tolerance is
    a mask, never a blocked collective."""
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                     axis_types=(AxisType.Auto,)*2)
    from repro.models import ArchConfig, init_params
    from repro.optim import adamw_init
    from repro.optim.schedules import constant_lr
    from repro.dist.fl_step import make_fl_train_step
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=4, n_kv=2, head_dim=8, d_ff=64, vocab=128,
                     dtype="float32")
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    b = {"inputs": jax.random.randint(key, (4, 4, 16), 0, 128),
         "labels": jax.random.randint(key, (4, 4, 16), 0, 128)}
    step = make_fl_train_step(cfg, mesh, lr_schedule=constant_lr(1e-3),
                              n_pods=4)
    w = jnp.ones(4)
    with mesh:
        # corrupting pod 3's batch has NO effect when pod 3 is masked
        a = jnp.array([1., 1., 1., 0.])
        p_ref, _, _ = jax.jit(step)(params, opt, b, w, a)
        b2 = dict(b)
        b2["inputs"] = b["inputs"].at[3].set(0)
        p_alt, _, _ = jax.jit(step)(params, opt, b2, w, a)
    diff = max(float(jnp.abs(x - y).max()) for x, y in zip(
        jax.tree_util.tree_leaves(p_ref), jax.tree_util.tree_leaves(p_alt)))
    assert diff < 1e-6, diff
    """)


@pytest.mark.slow
def test_dryrun_cell_small():
    """One real dry-run cell on 8 fake devices (mesh (2,2,2))."""
    _run("""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.specs import build_cell, to_shardings
    from repro.launch import hlo_analysis
    from repro.sharding.api import DEFAULT_RULES, axis_rules
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,)*3)
    cfg = get_config("gemma2-2b", reduced=True)
    shape = ShapeSpec("t", 64, 8, "train")
    with mesh, axis_rules(DEFAULT_RULES, mesh):
        cell = build_cell(cfg, shape, mesh)
        compiled = jax.jit(
            cell["step"],
            in_shardings=to_shardings(mesh, cell["in_specs"]),
            out_shardings=to_shardings(mesh, cell["out_specs"])
        ).lower(*cell["args"]).compile()
    costs = hlo_analysis.analyze(compiled.as_text())
    assert costs.flops > 0 and costs.coll_bytes > 0
    """)


def test_moe_shardmap_matches_fallback():
    """§Perf cell-2: the shard_map expert-parallel MoE must compute the
    same outputs as the single-device path (neither drops tokens)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import ArchConfig
    from repro.models.layers import _init_attn, _moe_ffn
    from jax.sharding import AxisType
    from repro.sharding.api import DEFAULT_RULES, axis_rules
    cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=64,
                     n_heads=4, n_kv=4, head_dim=16, d_ff=0, vocab=128,
                     pattern=("moe",), n_experts=8, top_k=2, d_expert=32,
                     dtype="float32")
    p = _init_attn(cfg, "moe", jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
    ref, _ = _moe_ffn(cfg, p, h)                   # no mesh
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,)*2)
    with mesh, axis_rules(DEFAULT_RULES, mesh):
        out, _ = jax.jit(lambda pp, hh: _moe_ffn(cfg, pp, hh))(p, h)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-4, err
    """)
