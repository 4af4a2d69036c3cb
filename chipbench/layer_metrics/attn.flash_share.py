"""FL train step (kernels/ops.py::flash_block): the share of the
step's attention layers that run on the causal flash kernels, from the
program's ``attn.flash_layers`` and ``attn.layers`` counters, which the
FL step adds each step while a recorder is installed (the traced
window).  1.0 where every full-sequence causal layer takes the flash
path on the chip."""


def read(ctx):
    c = ctx.stats.get("counters", {})
    flash, layers = c.get("attn.flash_layers"), c.get("attn.layers")
    if flash is None or not layers:
        return None
    return flash / layers
