"""From a profiler trace to per-layer numbers.

The harness wraps its calls into each layer in
``jax.profiler.TraceAnnotation`` spans named ``bench.<layer>``, and the
whole measured window in ``bench.window``.  This module reads the
``.xplane.pb`` the profiler writes, takes those host spans and the
device's operations, and attributes device time to host spans by
overlap on the profiler's common clock -- never by an operation's own
name, which several programs share (both fair-share kernels are named
``kern``).

Device operations are the events on the ``XLA Ops`` lines of the
``/device:`` planes; where a plane has no such line, all its lines
count.  Busy time is the union of their intervals, averaged over the
chips whose planes carry events.
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np

SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (k, 2) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2).astype(np.float64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > reach[:-1]]
    first = np.flatnonzero(new)
    return np.stack([iv[first, 0], np.maximum.reduceat(iv[:, 1], first)],
                    1).astype(np.float64)


def _measure_before(u: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted union ``u`` that lies below each
    point of ``x``."""
    lens = u[:, 1] - u[:, 0]
    cum = np.r_[0.0, np.cumsum(lens)]
    i = np.searchsorted(u[:, 0], x, side="right") - 1
    inside = np.clip(x - u[np.maximum(i, 0), 0], 0.0, lens[np.maximum(i, 0)])
    return np.where(i >= 0, cum[np.maximum(i, 0)] + inside, 0.0)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted unions."""
    if len(a) == 0 or len(b) == 0:
        return 0.0
    return float(np.sum(_measure_before(a, b[:, 1])
                        - _measure_before(a, b[:, 0])))


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.size == 0:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def _module_name(name: str) -> str:
    """``jit_kern(123456)`` -> ``jit_kern``."""
    return name.split("(", 1)[0]


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...), ...`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _qualify(names, iv, mods) -> list:
    """Each operation's name prefixed with the program (XLA module) it
    ran in, found by time: ``jit_kern/fusion.3``."""
    if not mods:
        return names
    mods.sort()
    starts = np.asarray([m[0] for m in mods], np.float64)
    ends = np.asarray([m[1] for m in mods], np.float64)
    i = np.searchsorted(starts, iv[:, 0], side="right") - 1
    out = []
    for k, nm in zip(i, names):
        if k >= 0 and iv[len(out), 0] <= ends[k]:
            out.append(f"{mods[k][2]}/{nm}")
        else:
            out.append(nm)
    return out


@dataclass
class Trace:
    """Host spans and device operations of one traced window, in
    nanoseconds on the profiler's clock."""

    spans: dict              # name -> (k, 2) float64 [start, end]
    ops: list                # per chip: (names list, (m, 2) intervals)

    # -- construction --------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        spans: dict = {}
        ops = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                lines = list(plane.lines)
                chosen = [ln for ln in lines if ln.name == "XLA Ops"]
                mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                         _module_name(ev.name))
                        for ln in lines if ln.name == "XLA Modules"
                        for ev in ln.events]
                names, iv = [], []
                for ln in chosen or lines:
                    for ev in ln.events:
                        names.append(_op_name(ev.name))
                        iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                if iv:
                    iv = np.asarray(iv, np.float64)
                    ops.append((_qualify(names, iv, mods), iv))
            else:
                for ln in plane.lines:
                    for ev in ln.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.setdefault(ev.name, []).append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
        return cls({k: np.asarray(v, np.float64) for k, v in spans.items()},
                   ops)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        """A trace kept as JSON (the test fixture): ``{"spans": {name:
        [[s, e], ...]}, "ops": [{"names": [...], "iv": [[s, e], ...]}]}``."""
        with open(path) as f:
            d = json.load(f)
        return cls({k: np.asarray(v, np.float64).reshape(-1, 2)
                    for k, v in d["spans"].items()},
                   [(o["names"], np.asarray(o["iv"], np.float64).reshape(-1, 2))
                    for o in d["ops"]])

    def to_json(self) -> dict:
        return {"spans": {k: v.tolist() for k, v in self.spans.items()},
                "ops": [{"names": list(n), "iv": iv.tolist()}
                        for n, iv in self.ops]}

    # -- the window ----------------------------------------------------
    @property
    def window(self) -> tuple[float, float]:
        w = self.spans.get(WINDOW)
        if w is None or len(w) == 0:
            raise ValueError("trace has no bench.window span")
        return float(w[:, 0].min()), float(w[:, 1].max())

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def _busy(self) -> list[np.ndarray]:
        lo, hi = self.window
        return [_union(_clip(iv, lo, hi)) for _, iv in self.ops]

    @property
    def chips(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device inside the
        window, averaged over the chips."""
        busy = self._busy()
        if not busy:
            return 0.0
        return float(np.mean([np.sum(b[:, 1] - b[:, 0]) for b in busy])) * 1e-9

    def span_s(self, name: str) -> float:
        """Total seconds of the host spans ``name`` inside the window."""
        lo, hi = self.window
        iv = self.spans.get(name)
        if iv is None:
            return 0.0
        u = _union(_clip(iv, lo, hi))
        return float(np.sum(u[:, 1] - u[:, 0])) * 1e-9

    def span_count(self, name: str) -> int:
        lo, hi = self.window
        iv = self.spans.get(name)
        if iv is None:
            return 0
        return int(np.sum((iv[:, 0] >= lo) & (iv[:, 1] <= hi)))

    def device_in_span_s(self, name: str) -> float:
        """Device busy seconds that fall inside the host spans ``name``,
        averaged over the chips."""
        lo, hi = self.window
        iv = self.spans.get(name)
        busy = self._busy()
        if iv is None or not busy:
            return 0.0
        u = _union(_clip(iv, lo, hi))
        return float(np.mean([_overlap(b, u) for b in busy])) * 1e-9

    # -- breakdown -----------------------------------------------------
    def top_ops(self, k: int = 10) -> list:
        """The device operations that took most time in the window, in
        seconds summed over their events (first chip)."""
        if not self.ops:
            return []
        lo, hi = self.window
        names, iv = self.ops[0]
        dur = np.minimum(iv[:, 1], hi) - np.maximum(iv[:, 0], lo)
        keep = dur > 0
        uniq, inv = np.unique(np.asarray(names, object)[keep].astype(str),
                              return_inverse=True)
        tot = np.bincount(inv, weights=dur[keep]) * 1e-9
        order = np.argsort(-tot)[:k]
        return [[str(uniq[i]), float(tot[i])] for i in order]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle seconds of the device inside the window, by what the
        host was doing: the shortest ``bench.*`` span that covers each
        idle gap's midpoint, else ``driver`` (no span open)."""
        lo, hi = self.window
        busy = self._busy()
        if not busy:
            return []
        edges = np.concatenate([[lo], busy[0].ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        mid = 0.5 * (gaps[:, 0] + gaps[:, 1])
        owner = np.full(mid.size, "driver", dtype=object)
        best = np.full(mid.size, np.inf)
        for name, iv in self.spans.items():
            if name == WINDOW or len(iv) == 0:
                continue
            iv = iv[np.argsort(iv[:, 0], kind="stable")]
            i = np.searchsorted(iv[:, 0], mid, side="right") - 1
            ok = i >= 0
            j = np.maximum(i, 0)
            hit = ok & (iv[j, 1] >= mid)
            ln = iv[j, 1] - iv[j, 0]
            win = hit & (ln < best)
            owner[win] = name
            best[win] = ln[win]
        uniq, inv = np.unique(owner.astype(str), return_inverse=True)
        tot = np.bincount(inv, weights=gaps[:, 1] - gaps[:, 0]) * 1e-9
        order = np.argsort(-tot)[:k]
        return [[str(uniq[i]), float(tot[i])] for i in order]
