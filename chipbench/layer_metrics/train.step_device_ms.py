"""FL train step: device-busy milliseconds per step inside the
``bench.step`` spans (the jitted step and its loss fetch)."""


def read(ctx):
    t, s = ctx.trace, ctx.stats
    if t is None or not s.get("steps") or not t.span_count("bench.step"):
        return None
    return 1e3 * t.device_in_span_s("bench.step") / s["steps"]
