"""The ``attn.flash_share`` reader, on the CPU.

It reads ``attn.flash_layers / attn.layers`` from the run's counters
alone, and nothing where either counter is missing (a program that
records no attention counters).

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench_harness
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402


@pytest.mark.parametrize("counters, want", [
    ({"attn.flash_layers": 30.0, "attn.layers": 30.0}, 1.0),
    ({"attn.flash_layers": 15.0, "attn.layers": 30.0}, 0.5),
    ({"attn.layers": 30.0}, None),
    ({"attn.flash_layers": 30.0}, None),
    ({}, None),
])
def test_attn_flash_share_reader(counters, want):
    spec = harness.load_spec()
    moon = harness.resolve_cell(spec, "moonlight-train")
    assert "attn.flash_share" in {m["name"] for m in moon.per_layer}
    ctx = SimpleNamespace(stats={"counters": counters, "steps": 5})
    assert harness.load_reader("attn.flash_share")(ctx) == want
