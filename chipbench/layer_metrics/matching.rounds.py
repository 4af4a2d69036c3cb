"""Tracker matching (core/jit_engine.py): grant rounds of the matching
kernel's loop per directive cycle, from the program's
``matching.rounds`` counter, which the program keeps while a recorder
is installed (the traced window)."""


def read(ctx):
    s = ctx.stats
    rounds = s.get("counters", {}).get("matching.rounds")
    if rounds is None or not s.get("cycles"):
        return None
    return rounds / s["cycles"]
