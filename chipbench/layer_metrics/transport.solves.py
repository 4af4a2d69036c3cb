"""Fair-share transport: water-filling re-solves per directive cycle,
from the program's ``net.fg_solves`` counter."""


def read(ctx):
    s = ctx.stats
    solves = s.get("counters", {}).get("net.fg_solves")
    if solves is None or not s.get("cycles"):
        return None
    return solves / s["cycles"]
