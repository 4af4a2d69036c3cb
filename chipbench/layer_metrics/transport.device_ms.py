"""Fair-share transport (net/fairshare.py via
net/engine.py::EventEngine.warmup_cycle): device-busy milliseconds per
directive cycle inside the ``bench.transport`` spans."""


def read(ctx):
    t, s = ctx.trace, ctx.stats
    if (t is None or not s.get("cycles")
            or not t.span_count("bench.transport")):
        return None
    return 1e3 * t.device_in_span_s("bench.transport") / s["cycles"]
