"""Round driver (core/simulator.py, core/state.py): host milliseconds
per directive cycle spent outside the timed solve and transport calls
-- applying transfers, the warm-up loop, round construction and spray.
Host clock around the benchmark's own spans."""


def read(ctx):
    s = ctx.stats
    if not s.get("cycles"):
        return None
    rest = s["elapsed_s"] - sum(s["solve_s"]) - sum(s["transport_s"])
    return 1e3 * rest / s["cycles"]
