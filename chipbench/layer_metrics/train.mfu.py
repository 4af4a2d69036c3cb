"""FL train step (dist/fl_step.py, models/): model FLOPs of the steps
completed in the window (forward and backward, no recompute) over the
window's seconds times the chips times the chip's bf16 peak, in %."""


def read(ctx):
    s = ctx.stats
    if not s.get("steps"):
        return None
    peak = ctx.peak["bf16_flops"] * ctx.cell.chips
    return 100.0 * s["model_flops"] / (s["elapsed_s"] * peak)
