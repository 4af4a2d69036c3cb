"""Tracker matching (core/jit_engine.py): milliseconds per directive
cycle of the ``bench.solve`` spans in which the device was idle --
candidate preparation, uploads, the inventory sync and decoding the
grants on the host."""


def read(ctx):
    t, s = ctx.trace, ctx.stats
    if t is None or not s.get("cycles") or not t.span_count("bench.solve"):
        return None
    host = t.span_s("bench.solve") - t.device_in_span_s("bench.solve")
    return 1e3 * host / s["cycles"]
