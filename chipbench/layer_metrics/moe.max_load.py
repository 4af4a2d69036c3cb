"""MoE expert layer (models/layers.py::moe_held): the busiest held
expert's pairs over the mean held expert's, in the worst MoE layer of a
step, averaged over the window's steps; from the program's
``moe.max_load`` counter (a sum over steps), kept while a recorder is
installed (the traced window).  1 is an even load."""


def read(ctx):
    s = ctx.stats
    load = s.get("counters", {}).get("moe.max_load")
    if load is None or not s.get("steps"):
        return None
    return load / s["steps"]
