"""Tracker matching (core/jit_engine.py): device-busy milliseconds per
directive cycle inside the ``bench.solve`` spans."""


def read(ctx):
    t, s = ctx.trace, ctx.stats
    if t is None or not s.get("cycles") or not t.span_count("bench.solve"):
        return None
    return 1e3 * t.device_in_span_s("bench.solve") / s["cycles"]
