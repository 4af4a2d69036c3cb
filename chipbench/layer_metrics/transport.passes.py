"""Fair-share transport: water-fill passes per directive cycle, summed
over the cycle's re-solves, from the program's ``net.fg_passes``
counter."""


def read(ctx):
    s = ctx.stats
    passes = s.get("counters", {}).get("net.fg_passes")
    if passes is None or not s.get("cycles"):
        return None
    return passes / s["cycles"]
