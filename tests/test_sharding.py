"""Sharding rules: divisibility filters, ZeRO, no duplicate axes,
elastic behaviour on odd dims (granite's vocab 49155)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import init_params
from jax.sharding import AxisType

from repro.sharding.api import (DEFAULT_RULES, axis_rules,
                                logical_constraint, param_specs,
                                spec_for_path)


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh with production axis names (trivial sizes) for rule
    # logic tests; real-mesh coverage happens in the dry-run.
    return jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)


def _axes_of(spec):
    out = []
    for e in spec:
        if e is None:
            continue
        out.extend(e if isinstance(e, tuple) else (e,))
    return out


def test_no_duplicate_mesh_axes_all_archs(mesh):
    for arch in ("olmoe-1b-7b", "gemma2-2b", "xlstm-350m",
                 "recurrentgemma-2b"):
        cfg = get_config(arch, reduced=True)
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        specs = param_specs(params, mesh)
        for s in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            axes = _axes_of(s)
            assert len(axes) == len(set(axes)), f"dup axes in {s}"


def test_divisibility_filter():
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 16))

    # vocab 49155 (granite) is not divisible by 16 -> unsharded
    s = spec_for_path("embed", (49155, 1024), FakeMesh(), DEFAULT_RULES,
                      stacked=False)
    assert s[0] is None
    # ZeRO falls to the d_model dim (1024 % 4 == 0)
    assert s[1] == "data"
    # divisible vocab shards over model
    s2 = spec_for_path("embed", (256000, 2304), FakeMesh(),
                       DEFAULT_RULES, stacked=False)
    assert s2[0] == "model"


def test_stacked_params_skip_leading_dim():
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 16))

    s = spec_for_path("cycles.slot0.w_up", (13, 2304, 9216), FakeMesh(),
                      DEFAULT_RULES, stacked=True)
    assert s[0] is None            # n_cycles stack dim never sharded
    assert s[2] == "model"         # ffn -> model
    assert s[1] == "data"          # ZeRO on the largest remaining dim


def test_moe_expert_sharding():
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 16))

    s = spec_for_path("cycles.slot0.moe_gate", (16, 64, 2048, 1024),
                      FakeMesh(), DEFAULT_RULES, stacked=True)
    assert s[1] == "model"         # expert axis -> EP over model
    assert "model" not in _axes_of(P(*s[2:]))   # no double use


def test_logical_constraint_noop_without_rules():
    x = jnp.ones((4, 4))
    y = logical_constraint(x, "batch", None)
    assert y is x


def test_logical_constraint_applies_in_context(mesh):
    with mesh, axis_rules(DEFAULT_RULES, mesh):
        @jax.jit
        def f(x):
            return logical_constraint(x, "batch", None) * 2
        out = f(jnp.ones((4, 4)))
        np.testing.assert_allclose(out, 2.0)
