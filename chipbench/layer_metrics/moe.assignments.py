"""MoE expert layer (models/layers.py::moe_held): token-expert pairs
the held experts computed, per token of the window and per MoE layer,
from the program's ``moe.assignments`` counter, which the FL step keeps
while a recorder is installed (the traced window).  Under uniform
routing it reads top-k x held / router outputs (6 x 8/64 = 0.75)."""


def read(ctx):
    s = ctx.stats
    pairs = s.get("counters", {}).get("moe.assignments")
    if pairs is None or not s.get("tokens") or not s.get("moe_layers"):
        return None
    return pairs / (s["tokens"] * s["moe_layers"])
