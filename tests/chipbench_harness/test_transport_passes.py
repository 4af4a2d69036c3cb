"""The fair-share transport's pass counter as the benchmark reads it.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench_harness
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


@pytest.mark.parametrize("counters, cycles, want", [
    ({"net.fg_passes": 3400.0, "net.fg_solves": 228.0}, 8, 425.0),
    ({"net.fg_solves": 228.0}, 8, None),     # a program that counts none
    ({"net.fg_passes": 3400.0}, 0, None),    # a window with no cycle
])
def test_transport_passes_per_cycle(counters, cycles, want):
    ctx = SimpleNamespace(stats={"cycles": cycles, "counters": counters})
    assert harness.load_reader("transport.passes")(ctx) == want


def test_transport_passes_reported_in_both_swarm_cells():
    spec = harness.load_spec()
    for cell in ("residential-warmup", "crosssilo-warmup"):
        names = [m["name"]
                 for m in harness.resolve_cell(spec, cell).per_layer]
        assert "transport.passes" in names
    train = harness.resolve_cell(spec, "qwen3-train")
    assert "transport.passes" not in [m["name"] for m in train.per_layer]
