"""Per-layer readers against traces whose program phases carry spans of
their own, nested inside the benchmark's ``bench.*`` spans, and the
existing readers pinned on the recorded fixtures.

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
from chipbench.trace_reduce import Trace  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"

# Eight directive cycles, as a swarm driver's window reports them.
STATS = {"cycles": 8, "steps": 8, "elapsed_s": 2.0, "solve_s": [0.05] * 8,
         "transport_s": [0.15] * 8, "compiles": 0,
         "counters": {"net.fg_solves": 228.0}, "model_flops": 1.5e14}


def _ctx(trace, stats=STATS):
    return SimpleNamespace(stats=stats, trace=trace,
                           peak={"bf16_flops": 197e12},
                           cell=SimpleNamespace(chips=1),
                           device={"memory_peak_bytes": 10_046_000_000})


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


@pytest.fixture(scope="module")
def nested():
    return Trace.from_json(str(FIXTURES / "program_spans_trace.json"))


# ----------------------------------------------------------------------
# the matching's round counter
# ----------------------------------------------------------------------

@pytest.mark.parametrize("counters, cycles, want", [
    ({"matching.rounds": 92.0, "net.fg_solves": 228.0}, 8, 11.5),
    ({"net.fg_solves": 228.0}, 8, None),     # a program that counts none
    ({"matching.rounds": 92.0}, 0, None),    # a window with no cycle
])
def test_matching_rounds_per_cycle(counters, cycles, want):
    stats = dict(STATS, counters=counters, cycles=cycles)
    assert _read("matching.rounds", _ctx(None, stats)) == want


def test_matching_rounds_reported_in_both_swarm_cells():
    spec = harness.load_spec()
    for cell in ("residential-warmup", "crosssilo-warmup"):
        names = [m["name"]
                 for m in harness.resolve_cell(spec, cell).per_layer]
        assert "matching.rounds" in names
    train = harness.resolve_cell(spec, "qwen3-train")
    assert "matching.rounds" not in [m["name"] for m in train.per_layer]


# ----------------------------------------------------------------------
# program spans nested inside the benchmark's spans (hand-computed)
# ----------------------------------------------------------------------

def test_idle_gaps_go_to_the_innermost_program_span(nested):
    # Busy [120,130] [190,198] [205,350] [455,600] [610,845] [970,990]:
    # the gap [130,190] lies in matching.prep, [198,205] in
    # matching.kernel, [350,455] has its midpoint in the first
    # transport.prep, [600,610] in transport.kernel, [845,970] in
    # driver.apply; [0,120] and [990,1000] in no span.
    gaps = dict(nested.idle_gaps(10))
    assert gaps == pytest.approx({
        "driver": 130e-9, "driver.apply": 125e-9,
        "transport.prep": 105e-9, "matching.prep": 60e-9,
        "transport.kernel": 10e-9, "matching.kernel": 7e-9})
    assert "bench.solve" not in gaps and "bench.transport" not in gaps
    assert sum(gaps.values()) == pytest.approx(
        nested.window_s - nested.busy_s())


@pytest.mark.parametrize("span, host_s, device_s", [
    ("matching.prep", 80e-9, 10e-9),
    ("matching.sync", 20e-9, 8e-9),
    ("matching.kernel", 160e-9, 145e-9),
    ("matching.decode", 35e-9, 0.0),
    ("transport.prep", 48e-9, 0.0),          # two rows: 30 + 18
    ("transport.kernel", 400e-9, 380e-9),
    ("transport.stamp", 40e-9, 0.0),
    ("driver.apply", 60e-9, 0.0),
])
def test_program_span_seconds(nested, span, host_s, device_s):
    assert nested.span_s(span) == pytest.approx(host_s)
    assert nested.device_in_span_s(span) == pytest.approx(device_s,
                                                          abs=1e-15)


def test_program_spans_cover_the_benchmark_spans(nested):
    # The four matching phases leave 5 ns of bench.solve uncovered, the
    # transport phases 2 + 10 ns of bench.transport.
    matching = sum(nested.span_s(f"matching.{p}")
                   for p in ("prep", "sync", "kernel", "decode"))
    transport = sum(nested.span_s(f"transport.{p}")
                    for p in ("prep", "kernel", "stamp"))
    assert nested.span_s("bench.solve") - matching == pytest.approx(5e-9)
    assert nested.span_s("bench.transport") - transport == \
        pytest.approx(12e-9)


def test_existing_readers_on_the_nested_trace(nested):
    ctx = _ctx(nested)
    # device 163 of 300 ns inside bench.solve, 380 of 500 ns inside
    # bench.transport, over 8 cycles, in ms
    assert _read("matching.device_ms", ctx) == pytest.approx(163e-6 / 8)
    assert _read("matching.host_ms", ctx) == pytest.approx(137e-6 / 8)
    assert _read("transport.device_ms", ctx) == pytest.approx(380e-6 / 8)
    top = nested.top_ops(2)
    assert [n for n, _ in top] == ["jit_fairshare_transport/while.2",
                                   "jit_matching_rounds/while.1"]


# ----------------------------------------------------------------------
# existing readers, pinned on the recorded fixtures
# ----------------------------------------------------------------------

PINNED = {
    "synthetic_trace.json": {
        "driver.host_ms": 50.0, "matching.device_ms": 6.875e-06,
        "matching.host_ms": 3.0625e-05, "transport.device_ms": 1.125e-05,
        "transport.solves": 28.5, "swarm.window_compiles": 0.0,
        "train.mfu": 38.07106598984772, "train.step_device_ms": None,
        "device.peak_hbm_gb": 10.046},
    # its spans run past the window's edges, so no span counts whole
    "chip_trace.json": {
        "driver.host_ms": 50.0, "matching.device_ms": None,
        "matching.host_ms": None, "transport.device_ms": None,
        "transport.solves": 28.5, "swarm.window_compiles": 0.0,
        "train.mfu": 38.07106598984772, "train.step_device_ms": None,
        "device.peak_hbm_gb": 10.046},
}


@pytest.mark.parametrize("fixture, metric", [
    (f, m) for f, vals in PINNED.items() for m in vals])
def test_existing_reader_pinned(fixture, metric):
    t = Trace.from_json(str(FIXTURES / fixture))
    want = PINNED[fixture][metric]
    got = _read(metric, _ctx(t))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_recorded_chip_trace_reductions_pinned():
    t = Trace.from_json(str(FIXTURES / "chip_trace.json"))
    assert t.busy_s() == pytest.approx(0.000311929, rel=1e-12)
    assert t.device_in_span_s("bench.solve") == pytest.approx(
        0.000311929, rel=1e-12)
    assert t.device_in_span_s("bench.transport") == 0.0
    assert t.span_s("bench.solve") == pytest.approx(0.002581245,
                                                    rel=1e-12)
    assert t.span_s("bench.transport") == pytest.approx(0.001902555,
                                                        rel=1e-12)
    assert t.idle_gaps(10) == [["bench.solve", pytest.approx(
        0.004188071, rel=1e-12)]]
