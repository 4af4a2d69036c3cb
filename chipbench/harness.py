"""Plumbing shared by every cell: the spec, the cell's files, the device
check, the compile cache and counter, and the result line.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own that this module finds by
the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json``   -- the deployment or model;
* ``chipbench/traffic/<traffic>.json``  -- the traffic parameters,
  naming the driver that generates the load;
* ``chipbench/drivers/<driver>.py``     -- one driver per kind of path;
* ``chipbench/layer_metrics/<metric>.py`` -- one reader per metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# Fixed, inside the checkout: the directory is part of each entry's key.
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A run that cannot report: exit non-zero, print no result."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_spec(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, traffic and the
    metrics it reports."""
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in spec["configs"] if c["name"] == wl["config"]),
                None)
    if conf is None:
        raise BenchError(f"workload {name!r} names no known config "
                         f"{wl['config']!r}")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(wl["chips"]),
                config=_load_json(root / conf["file"]),
                traffic=_load_json(root / "chipbench" / "traffic"
                                   / f"{wl['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


_MODULES: dict = {}


def _load_module(path: Path, label: str):
    """The module in file ``path``, loaded once per process."""
    if not path.is_file():
        raise BenchError(f"no {label} at {path}")
    key = str(path.resolve())
    if key not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{label}_{path.stem}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def load_driver(name: str, root: Path = ROOT):
    return _load_module(root / "chipbench" / "drivers" / f"{name}.py",
                        "driver")


def load_reader(metric: str, root: Path = ROOT):
    """``read(ctx)`` of a per-layer metric: a number, or None where the
    run has nothing to read for it."""
    mod = _load_module(root / "chipbench" / "layer_metrics" / f"{metric}.py",
                       "metric")
    return mod.read


# ----------------------------------------------------------------------
# device
# ----------------------------------------------------------------------

def load_peaks(root: Path = ROOT) -> dict:
    return _load_json(root / "chipbench" / "peaks.json")["devices"]


def check_device(chips: int, peaks: dict, devices) -> dict:
    """The accelerator the cell runs on, as JAX reports it; refuses a
    host without a TPU, with fewer chips than the cell asks for, or of
    a kind the peaks table does not know."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise BenchError(f"needs a TPU; JAX found {plat!r} devices only")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips, found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """Persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every
    program however fast it compiled."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileMeter:
    """XLA compiles seen by this process, from JAX's monitoring events:
    a persistent-cache hit is counted as a compile whose seconds are
    the cache read."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


def span(name: str, on: bool):
    """A profiler span (``bench.<layer>``) around a call into the
    program, when the run is traced."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------------
# result
# ----------------------------------------------------------------------

def metric_entry(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit(line: dict, checks: dict) -> None:
    """Print the compared numbers beside their limits as the last lines
    of stderr, and the result as the last line of stdout, the checks
    under the key that comes last."""
    out = dict(line)
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
